"""Checkpoint loading: HF safetensors -> stacked sharded device buffers.

The serving analogue of checkpoint/resume (SURVEY.md section 5.4): the
reference's only persistence is an HF model-cache volume consumed by vLLM;
here weights load directly into the engine's stacked-layer pytree, sharded
per the mesh rules at placement time (safetensors -> jax.device_put per
shard), so a v5e-8 load never materializes a full replica per host.

Name mapping follows the HF `Qwen2ForCausalLM` / `MixtralForCausalLM` /
`BertModel` / `Qwen3NextForCausalLM` / `NemotronHForCausalLM` conventions; torch linear weights are [out, in] and transposed
into the einsum-friendly [in, out] layout used by models/decoder.py.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from vgate_tpu.logging_config import get_logger
from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.observability.perf import note_boot

logger = get_logger(__name__)

Params = Dict[str, Any]
# get(name) -> np.ndarray accessor abstracting safetensors files / state dicts
TensorGetter = Callable[[str], np.ndarray]


def _stack(getter: TensorGetter, template: str, num_layers: int, transpose=False):
    arrs = []
    for i in range(num_layers):
        arr = np.asarray(getter(template.format(i)))
        arrs.append(arr.T if transpose else arr)
    return np.stack(arrs)


def _hybrid_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``Qwen3NextForCausalLM`` names -> the hybrid pytree of
    models/hybrid.py (``layers = {"linear": [P, n, ...], "full": [P,
    ...]}``).  The checkpoint holds ``in_proj_qkvz`` and ``in_proj_ba``
    grouped by KEY head (per key head ``[q | k | its value heads' v |
    their z]`` and ``[their b | their a]``); both are un-interleaved
    here into ``[q | k | v | z]`` and ``[b | a]``.  A chip's share: the
    experts ``first_expert ..`` of the router's width, and the first
    ``vocab_size`` rows of embedding and head."""
    P, n = spec.num_periods, spec.linear_per_period
    period = spec.full_attention_interval
    Hk, Hv = spec.linear_num_key_heads, spec.linear_num_value_heads
    dk, dv = spec.linear_key_head_dim, spec.linear_value_head_dim
    r = Hv // Hk
    E, first = spec.num_experts, spec.first_expert
    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: get(i, f"{name}.weight").T  # torch [out, in]

    def moe(i):
        ex = lambda e, w: lin(i, f"mlp.experts.{first + e}.{w}")
        out = {
            "router": lin(i, "mlp.gate"),
            "gate": {"w": np.stack([ex(e, "gate_proj") for e in range(E)])},
            "up": {"w": np.stack([ex(e, "up_proj") for e in range(E)])},
            "down": {"w": np.stack([ex(e, "down_proj") for e in range(E)])},
        }
        if spec.shared_expert_intermediate_size:
            out.update({
                "shared_gate": {"w": lin(i, "mlp.shared_expert.gate_proj")},
                "shared_up": {"w": lin(i, "mlp.shared_expert.up_proj")},
                "shared_down": {"w": lin(i, "mlp.shared_expert.down_proj")},
                "shared_router": lin(i, "mlp.shared_expert_gate")[:, 0],
            })
        return out

    def norms(i):
        return {
            "input_norm": get(i, "input_layernorm.weight"),
            "post_norm": get(i, "post_attention_layernorm.weight"),
        }

    def full_layer(i):
        out = {
            **norms(i), **moe(i),
            "q": {"w": lin(i, "self_attn.q_proj")},
            "k": {"w": lin(i, "self_attn.k_proj")},
            "v": {"w": lin(i, "self_attn.v_proj")},
            "o": {"w": lin(i, "self_attn.o_proj")},
        }
        if spec.qk_norm:
            out["q_norm"] = get(i, "self_attn.q_norm.weight")
            out["k_norm"] = get(i, "self_attn.k_norm.weight")
        return out

    def linear_layer(i):
        D = spec.hidden_size
        qkvz = lin(i, "linear_attn.in_proj_qkvz").reshape(
            D, Hk, 2 * dk + 2 * r * dv)
        cuts = np.cumsum([dk, dk, r * dv])
        parts = np.split(qkvz, cuts, axis=-1)  # q, k, v, z by key head
        ba = lin(i, "linear_attn.in_proj_ba").reshape(D, Hk, 2 * r)
        return {
            **norms(i), **moe(i),
            "in_qkvz": {"w": np.concatenate(
                [p.reshape(D, -1) for p in parts], axis=-1)},
            "in_ba": {"w": np.concatenate(
                [ba[..., :r].reshape(D, Hv), ba[..., r:].reshape(D, Hv)],
                axis=-1)},
            "conv": get(i, "linear_attn.conv1d.weight")[:, 0, :],
            "a_log": get(i, "linear_attn.A_log"),
            "dt_bias": get(i, "linear_attn.dt_bias"),
            "gdn_norm": get(i, "linear_attn.norm.weight"),
            "out": {"w": lin(i, "linear_attn.out_proj")},
        }

    stack = lambda trees: jax.tree.map(lambda *xs: np.stack(xs), *trees)
    np_dtype = np.dtype(dtype)
    keep_f32 = ("a_log", "dt_bias")

    def cast(tree):
        return {
            k: (np.asarray(v, np.float32) if k in keep_f32
                else jax.tree.map(lambda x: np.asarray(x).astype(np_dtype),
                                  v))
            for k, v in tree.items()
        }

    V = spec.vocab_size
    params: Params = {
        "embed": np.asarray(getter("model.embed_tokens.weight"))[:V]
        .astype(np_dtype),
        "layers": {
            "linear": cast(stack([
                stack([linear_layer(p * period + j) for j in range(n)])
                for p in range(P)
            ])),
            "full": cast(stack([
                full_layer(p * period + n) for p in range(P)
            ])),
        },
        "final_norm": np.asarray(getter("model.norm.weight"))
        .astype(np_dtype),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = (
            np.asarray(getter("lm_head.weight"))[:V].T.astype(np_dtype)
        )
    return params


def _pattern_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``NemotronHForCausalLM`` names -> the pytree of models/hybrid.py
    for a ``layer_pattern`` spec (``layers = {"mamba" | "attn" | "moe":
    [P, n, ...]}``).  Layer ``i`` of the checkpoint is the i-th letter of
    the pattern; its one sub-block is ``backbone.layers.{i}.mixer``,
    whatever its kind, behind ``backbone.layers.{i}.norm``.  ``in_proj``
    is held as the checkpoint has it, ``[z | x | B | C | dt]``;
    ``conv1d.weight`` ``[channels, 1, taps]`` loses its middle axis.  A
    chip's share: the experts ``first_expert ..`` of the router's width,
    and the first ``vocab_size`` rows of embedding and head."""
    E, first = spec.num_experts, spec.first_expert
    get = lambda i, name: np.asarray(getter(f"backbone.layers.{i}.{name}"))
    lin = lambda i, name: {"w": get(i, f"mixer.{name}.weight").T}

    def mamba(i):
        out = {
            "in_proj": lin(i, "in_proj"),
            "conv": get(i, "mixer.conv1d.weight")[:, 0, :],
            "a_log": get(i, "mixer.A_log"),
            "d": get(i, "mixer.D"),
            "dt_bias": get(i, "mixer.dt_bias"),
            "ssm_norm": get(i, "mixer.norm.weight"),
            "out": lin(i, "out_proj"),
        }
        if spec.mamba_conv_bias:
            out["conv_bias"] = get(i, "mixer.conv1d.bias")
        return out

    def attn(i):
        return {n: lin(i, f"{n}_proj") for n in "qkvo"}

    def moe(i):
        experts = lambda w: {"w": np.stack([
            get(i, f"mixer.experts.{first + e}.{w}_proj.weight").T
            for e in range(E)])}
        out = {
            "router": lin(i, "gate")["w"],
            "router_bias": get(i, "mixer.gate.e_score_correction_bias"),
            "up": experts("up"), "down": experts("down"),
            "shared_up": lin(i, "shared_experts.up_proj"),
            "shared_down": lin(i, "shared_experts.down_proj"),
        }
        if spec.moe_latent_size:
            out["latent_in"] = lin(i, "fc1_latent_proj")
            out["latent_out"] = lin(i, "fc2_latent_proj")
        return out

    np_dtype = np.dtype(dtype)
    keep_f32 = ("a_log", "d", "dt_bias", "router_bias")
    load = {"mamba": mamba, "attn": attn, "moe": moe}
    per_kind: Dict[str, list] = {}
    blocks, per = spec.period_blocks, spec.layers_per_period
    for i in range(spec.num_layers):
        kind = blocks[i % per][0]
        tree = dict(load[kind](i), norm=get(i, "norm.weight"))
        per_kind.setdefault(kind, []).append({
            k: (np.asarray(v, np.float32) if k in keep_f32
                else jax.tree.map(lambda x: x.astype(np_dtype), v))
            for k, v in tree.items()
        })
    P = spec.num_periods
    V = spec.vocab_size
    params: Params = {
        "embed": np.asarray(getter("backbone.embeddings.weight"))[:V]
        .astype(np_dtype),
        "layers": {
            kind: jax.tree.map(
                lambda *xs: np.stack(xs).reshape(
                    (P, len(xs) // P) + xs[0].shape), *trees)
            for kind, trees in per_kind.items()
        },
        "final_norm": np.asarray(getter("backbone.norm_f.weight"))
        .astype(np_dtype),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = (
            np.asarray(getter("lm_head.weight"))[:V].T.astype(np_dtype)
        )
    return params


def _deinterleave(w: np.ndarray, dim: int) -> np.ndarray:
    """[..., n] whose last ``dim`` columns pair rotary dimensions (2i,
    2i + 1) -> the same columns in halves (evens, then odds): what
    rotate-half expects.  A dot product of two vectors re-ordered alike
    is unchanged, so only the rotation has to know."""
    head, tail = w[..., :-dim], w[..., -dim:]
    return np.concatenate([head, tail[..., 0::2], tail[..., 1::2]], axis=-1)


def _mla_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``mistral4`` names (ASSUMED to be DeepSeek-V3's, whose config keys
    the published config's are one for one; no checkpoint was read) ->
    the pytree of models/hybrid.py for a latent-attention spec (``layers
    = {"layer": [L, 1, ...]}``).  ``kv_b_proj`` ``[heads x (nope + v),
    kv_lora_rank]`` is split per head into ``kv_b_k`` (W_uk) and
    ``kv_b_v`` (W_uv), ``[kv_lora_rank, heads, .]``.  With
    ``rope_interleave`` the rotary columns of every head of ``q_b_proj``
    and of ``kv_a_proj_with_mqa`` are de-interleaved, so that the
    program rotates halves.  A chip's share: the experts ``first_expert
    ..`` of the router's width, and the first ``vocab_size`` rows of
    embedding and head."""
    E, first, H = spec.num_experts, spec.first_expert, spec.num_heads
    kl, nope, rope = (spec.kv_lora_rank, spec.qk_nope_head_dim,
                      spec.qk_rope_head_dim)
    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: get(i, f"{name}.weight").T
    unpair = _deinterleave if spec.rope_interleave else (lambda w, _: w)

    def layer(i):
        q_b = lin(i, "self_attn.q_b_proj")  # [q_lora, H x (nope + rope)]
        q_b = unpair(q_b.reshape(-1, H, nope + rope), rope)
        kv_b = lin(i, "self_attn.kv_b_proj").reshape(kl, H, -1)
        experts = lambda w: {"w": np.stack([
            lin(i, f"mlp.experts.{first + e}.{w}_proj")
            for e in range(E)])}
        out = {
            "input_norm": get(i, "input_layernorm.weight"),
            "post_norm": get(i, "post_attention_layernorm.weight"),
            "q_a": {"w": lin(i, "self_attn.q_a_proj")},
            "q_a_norm": get(i, "self_attn.q_a_layernorm.weight"),
            "q_b": {"w": q_b.reshape(q_b.shape[0], -1)},
            "kv_a": {"w": unpair(lin(i, "self_attn.kv_a_proj_with_mqa"),
                                 rope)},
            "kv_a_norm": get(i, "self_attn.kv_a_layernorm.weight"),
            "kv_b_k": {"w": kv_b[..., :nope]},
            "kv_b_v": {"w": kv_b[..., nope:]},
            "o": {"w": lin(i, "self_attn.o_proj")},
            "router": lin(i, "mlp.gate"),
            "gate": experts("gate"), "up": experts("up"),
            "down": experts("down"),
        }
        if spec.shared_expert_intermediate_size:
            for n in ("gate", "up", "down"):
                out[f"shared_{n}"] = {
                    "w": lin(i, f"mlp.shared_experts.{n}_proj")}
        return out

    np_dtype, V = np.dtype(dtype), spec.vocab_size
    cast = lambda x: np.asarray(x).astype(np_dtype)
    trees = [layer(i) for i in range(spec.num_layers)]
    params: Params = {
        "embed": cast(np.asarray(getter("model.embed_tokens.weight"))[:V]),
        "layers": {"layer": jax.tree.map(
            lambda *xs: cast(np.stack(xs)[:, None]), *trees)},
        "final_norm": cast(getter("model.norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight"))[:V].T)
    return params


def _dsa_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``glm_moe_dsa`` names (ASSUMED: DeepSeek-V3's for the latent
    attention and the expert layer, DeepSeek-V3.2's for the indexer,
    ``self_attn.indexer.{wq_b, wk, k_norm, weights_proj}``; no checkpoint
    was read) -> the pytree of models/hybrid.py for an
    ``indexer_pattern`` spec: ``layers = {"lead": (a tree a leading
    layer, ...), "pick" | "reuse": [P, n, ...]}``, layer ``i`` of the
    checkpoint in the place ``ModelSpec`` gives it.  With
    ``rope_interleave`` / ``indexer_rope_interleave`` the rotary columns
    are de-interleaved, so that the program rotates halves: the LAST
    ``qk_rope_head_dim`` of every head of ``q_b_proj`` and of
    ``kv_a_proj_with_mqa``, the FIRST ``qk_rope_head_dim`` of every index
    head and of the index key (its LayerNorm's weight and bias with it).
    A chip's share: the experts ``first_expert ..`` of the router's
    width, and the first ``vocab_size`` rows of embedding and head.  The
    multi-token-prediction module's tensors are not read."""
    E, first, H = spec.num_experts, spec.first_expert, spec.num_heads
    kl, nope, rope = (spec.kv_lora_rank, spec.qk_nope_head_dim,
                      spec.qk_rope_head_dim)
    Hi, di = spec.index_n_heads, spec.index_head_dim
    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: get(i, f"{name}.weight").T
    unpair = _deinterleave if spec.rope_interleave else (lambda w, _: w)
    # the indexer rotates its FIRST dimensions: flip, unpair the last, flip
    head = (lambda w: _deinterleave(w[..., ::-1], rope)[..., ::-1]
            ) if spec.indexer_rope_interleave else (lambda w: w)
    np_dtype, V = np.dtype(dtype), spec.vocab_size
    cast = lambda x: np.asarray(x).astype(np_dtype)

    def layer(i):
        mixer, ff = spec.stack[i]
        q_b = unpair(lin(i, "self_attn.q_b_proj").reshape(
            -1, H, nope + rope), rope)
        kv_b = lin(i, "self_attn.kv_b_proj").reshape(kl, H, -1)
        out = {
            "input_norm": get(i, "input_layernorm.weight"),
            "post_norm": get(i, "post_attention_layernorm.weight"),
            "q_a": {"w": lin(i, "self_attn.q_a_proj")},
            "q_a_norm": get(i, "self_attn.q_a_layernorm.weight"),
            "q_b": {"w": q_b.reshape(q_b.shape[0], -1)},
            "kv_a": {"w": unpair(lin(i, "self_attn.kv_a_proj_with_mqa"),
                                 rope)},
            "kv_a_norm": get(i, "self_attn.kv_a_layernorm.weight"),
            "kv_b_k": {"w": kv_b[..., :nope]},
            "kv_b_v": {"w": kv_b[..., nope:]},
            "o": {"w": lin(i, "self_attn.o_proj")},
        }
        if mixer == "dsa":
            pre = "self_attn.indexer."
            wq = head(lin(i, pre + "wq_b").reshape(-1, Hi, di))
            out.update({
                "index_q": {"w": wq.reshape(wq.shape[0], -1)},
                "index_k": {"w": head(lin(i, pre + "wk"))},
                "index_k_norm": head(get(i, pre + "k_norm.weight")),
                "index_k_bias": head(get(i, pre + "k_norm.bias")),
                "index_w": {"w": lin(i, pre + "weights_proj")},
            })
        if ff == "mlp":
            for n in ("gate", "up", "down"):
                out[n] = {"w": lin(i, f"mlp.{n}_proj")}
            return jax.tree.map(cast, out)
        out["router"] = lin(i, "mlp.gate")
        for n in ("gate", "up", "down"):
            out[n] = {"w": np.stack([
                lin(i, f"mlp.experts.{first + e}.{n}_proj")
                for e in range(E)])}
            if spec.shared_expert_intermediate_size:
                out[f"shared_{n}"] = {
                    "w": lin(i, f"mlp.shared_experts.{n}_proj")}
        out = jax.tree.map(cast, out)
        if spec.router_scoring == "sigmoid":
            out["router_bias"] = np.asarray(
                get(i, "mlp.gate.e_score_correction_bias"), np.float32)
        return out

    lead, P = spec.lead_layers, spec.num_periods
    layers: Dict[str, Any] = {
        "lead": tuple(layer(i) for i in range(lead))}
    for group, mixer in (("pick", "dsa"), ("reuse", "mla")):
        trees = [layer(i) for i in range(lead, spec.num_layers)
                 if spec.stack[i][0] == mixer]
        if trees:
            layers[group] = jax.tree.map(
                lambda *xs: np.stack(xs).reshape(
                    (P, len(xs) // P) + xs[0].shape), *trees)
    params: Params = {
        "embed": cast(np.asarray(getter("model.embed_tokens.weight"))[:V]),
        "layers": layers,
        "final_norm": cast(getter("model.norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight"))[:V].T)
    return params


def _kv_dsa_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``KeyeVL2`` names (ASSUMED: the language model's are Qwen3-MoE's,
    ``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``, ``mlp.gate``
    and ``mlp.experts.<e>.{gate,up,down}_proj``, under ``model.`` or,
    where the checkpoint nests it beside the tower, under
    ``model.language_model.``; the indexer's are DeepSeek-V3.2's,
    ``self_attn.indexer.{wq_b, wk, k_norm, weights_proj}``; no checkpoint
    was read) -> the pytree of models/hybrid.py for a ``kv_rows`` spec:
    ``layers = {"layer": [layers, 1, ...]}``.  The tower's ``visual.*``
    tensors are not read.  A chip's share: the experts ``first_expert ..``
    of the router's width, and the first ``vocab_size`` rows of
    embedding and head."""
    E, first = spec.num_experts, spec.first_expert
    np_dtype, V = np.dtype(dtype), spec.vocab_size
    cast = lambda x: np.asarray(x).astype(np_dtype)

    def base(name):
        """The tensor under the language model's prefix, whichever the
        checkpoint uses."""
        for prefix in ("model.language_model.", "model."):
            try:
                return np.asarray(getter(prefix + name))
            except KeyError:
                continue
        raise KeyError(name)

    get = lambda i, name: base(f"layers.{i}.{name}")
    lin = lambda i, name: get(i, f"{name}.weight").T

    def layer(i):
        pre = "self_attn.indexer."
        out = {
            "input_norm": get(i, "input_layernorm.weight"),
            "post_norm": get(i, "post_attention_layernorm.weight"),
            "q_norm": get(i, "self_attn.q_norm.weight"),
            "k_norm": get(i, "self_attn.k_norm.weight"),
            "index_q": {"w": lin(i, pre + "wq_b")},
            "index_k": {"w": lin(i, pre + "wk")},
            "index_k_norm": get(i, pre + "k_norm.weight"),
            "index_k_bias": get(i, pre + "k_norm.bias"),
            "index_w": {"w": lin(i, pre + "weights_proj")},
            "router": lin(i, "mlp.gate"),
        }
        for n in ("q", "k", "v", "o"):
            out[n] = {"w": lin(i, f"self_attn.{n}_proj")}
        for n in ("gate", "up", "down"):
            out[n] = {"w": np.stack([
                lin(i, f"mlp.experts.{first + e}.{n}_proj")
                for e in range(E)])}
        return jax.tree.map(cast, out)

    trees = [layer(i) for i in range(spec.num_layers)]
    params: Params = {
        "embed": cast(base("embed_tokens.weight")[:V]),
        "layers": {"layer": jax.tree.map(
            lambda *xs: np.stack(xs)[:, None], *trees)},
        "final_norm": cast(base("norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight"))[:V].T)
    return params


def _window_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``exaone_moe`` names (ASSUMED: the attention and norm names of
    ``transformers``' EXAONE-4, the expert layer's of DeepSeek-V3, whose
    config keys the published config's are; no checkpoint was read),
    and ``mellum`` names (ASSUMED: the Qwen3-MoE lineage's, whose config
    keys the published config's are, which are the SAME names:
    ``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``, ``mlp.gate``,
    ``mlp.experts.<e>.{gate,up,down}_proj``, and no selection bias under
    its softmax router; no checkpoint was read; the multi-token-
    prediction head's tensors are not read) ->
    the pytree of models/hybrid.py for a ``window_pattern`` spec:
    ``layers = {"lead": (a tree a leading layer, ...), "window" |
    "global": [P, n, ...]}``, layer ``i`` of the checkpoint in the place
    ``ModelSpec`` gives it.  A chip's share: the experts ``first_expert
    ..`` of the router's width, and the first ``vocab_size`` rows of
    embedding and head.  The multi-token-prediction module's tensors are
    not read."""
    E, first = spec.num_experts, spec.first_expert
    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: {"w": get(i, f"{name}.weight").T}
    np_dtype, V = np.dtype(dtype), spec.vocab_size
    cast = lambda x: np.asarray(x).astype(np_dtype)

    def layer(i):
        out = {
            "input_norm": get(i, "input_layernorm.weight"),
            "post_norm": get(i, "post_attention_layernorm.weight"),
            **{n: lin(i, f"self_attn.{n}_proj") for n in "qkvo"},
        }
        if spec.qk_norm:
            out["q_norm"] = get(i, "self_attn.q_norm.weight")
            out["k_norm"] = get(i, "self_attn.k_norm.weight")
        if spec.stack[i][1] == "mlp":
            for n in ("gate", "up", "down"):
                out[n] = lin(i, f"mlp.{n}_proj")
            return jax.tree.map(cast, out)
        out["router"] = lin(i, "mlp.gate")["w"]
        for n in ("gate", "up", "down"):
            out[n] = {"w": np.stack([
                lin(i, f"mlp.experts.{first + e}.{n}_proj")["w"]
                for e in range(E)])}
            if spec.shared_expert_intermediate_size:
                out[f"shared_{n}"] = lin(i, f"mlp.shared_experts.{n}_proj")
        out = jax.tree.map(cast, out)
        if spec.router_scoring == "sigmoid":
            out["router_bias"] = np.asarray(
                get(i, "mlp.gate.e_score_correction_bias"), np.float32)
        return out

    lead, P = spec.lead_layers, spec.num_periods
    layers: Dict[str, Any] = {
        "lead": tuple(layer(i) for i in range(lead))}
    for group, mixer in (("window", "swa"), ("global", "attn")):
        trees = [layer(i) for i in range(lead, spec.num_layers)
                 if spec.stack[i][0] == mixer]
        if trees:
            layers[group] = jax.tree.map(
                lambda *xs: np.stack(xs).reshape(
                    (P, len(xs) // P) + xs[0].shape), *trees)
    params: Params = {
        "embed": cast(np.asarray(getter("model.embed_tokens.weight"))[:V]),
        "layers": layers,
        "final_norm": cast(getter("model.norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight"))[:V].T)
    return params


def _conv_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``lfm2_moe`` names (ASSUMED from ``transformers``' LFM2 family:
    ``operator_norm`` / ``ffn_norm``, ``conv.in_proj`` / ``conv.conv`` /
    ``conv.out_proj``, ``self_attn.{q,k,v}_proj`` / ``out_proj`` with
    ``q_layernorm`` / ``k_layernorm``, ``feed_forward.w1`` / ``w3`` /
    ``w2`` dense and ``feed_forward.experts.N.*`` beside
    ``feed_forward.gate`` and ``feed_forward.expert_bias``,
    ``model.embedding_norm``; no checkpoint was read) -> the pytree of
    models/hybrid.py for a ``conv_pattern`` spec: ``layers = {"lead": (a
    tree a leading layer, ...), "conv" | "attn": [P, n, ...]}``.  The
    depth-wise taps come as torch's ``conv1d`` holds them, ``[C, 1, K]``,
    squeezed.  A chip's share: the experts ``first_expert ..`` of the
    router's width."""
    E, first = spec.num_experts, spec.first_expert
    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: {"w": get(i, f"{name}.weight").T}
    np_dtype = np.dtype(dtype)
    cast = lambda x: np.asarray(x).astype(np_dtype)
    ff = {"gate": "w1", "up": "w3", "down": "w2"}

    def layer(i):
        out = {"input_norm": get(i, "operator_norm.weight"),
               "post_norm": get(i, "ffn_norm.weight")}
        if spec.stack[i][0] == "conv":
            out["in_proj"] = lin(i, "conv.in_proj")
            out["out_proj"] = lin(i, "conv.out_proj")
            taps = get(i, "conv.conv.weight")
            out["conv"] = taps.reshape(taps.shape[0], taps.shape[-1])
            if spec.conv_bias:
                out["conv_bias"] = get(i, "conv.conv.bias")
        else:
            for n in "qkv":
                out[n] = lin(i, f"self_attn.{n}_proj")
            out["o"] = lin(i, "self_attn.out_proj")
            out["q_norm"] = get(i, "self_attn.q_layernorm.weight")
            out["k_norm"] = get(i, "self_attn.k_layernorm.weight")
        if spec.stack[i][1] == "mlp":
            for n, theirs in ff.items():
                out[n] = lin(i, f"feed_forward.{theirs}")
            return jax.tree.map(cast, out)
        out["router"] = lin(i, "feed_forward.gate")["w"]
        for n, theirs in ff.items():
            out[n] = {"w": np.stack([
                lin(i, f"feed_forward.experts.{first + e}.{theirs}")["w"]
                for e in range(E)])}
        out = jax.tree.map(cast, out)
        out["router_bias"] = np.asarray(
            get(i, "feed_forward.expert_bias"), np.float32)
        return out

    lead, P = spec.lead_layers, spec.num_periods
    layers: Dict[str, Any] = {
        "lead": tuple(layer(i) for i in range(lead))}
    for group in ("conv", "attn"):
        trees = [layer(i) for i in range(lead, spec.num_layers)
                 if spec.stack[i][0] == group]
        if trees:
            layers[group] = jax.tree.map(
                lambda *xs: np.stack(xs).reshape(
                    (P, len(xs) // P) + xs[0].shape), *trees)
    params: Params = {
        "embed": cast(getter("model.embed_tokens.weight")),
        "layers": layers,
        "final_norm": cast(getter("model.embedding_norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight")).T)
    return params


def _mamba_mlp_params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype
) -> Params:
    """``GraniteMoeHybridForCausalLM`` names (ASSUMED from
    ``transformers``' module as the writer knows it: ``input_layernorm``
    / ``post_attention_layernorm``, ``mamba.{in_proj, conv1d, A_log, D,
    dt_bias, norm, out_proj}``, ``self_attn.{q,k,v,o}_proj``,
    ``shared_mlp.input_linear`` holding ``[gate | up]`` and
    ``shared_mlp.output_linear``, ``model.norm``; no checkpoint was
    read) -> the pytree of models/hybrid.py for a ``mamba_pattern``
    spec: ``layers = {"mamba" | "attn": [P, n, ...]}``, a layer's dense
    SwiGLU in its mixer's group.  ``in_proj`` is held as the checkpoint
    has it, ``[z | x | B | C | dt]``, with zero columns up to whole lane
    groups behind it (models/hybrid.py ``mamba_proj_pad``);
    ``conv1d.weight`` ``[channels, 1, taps]`` loses its middle axis.
    The head is the embedding (``tie_word_embeddings``), unscaled by
    ``embedding_multiplier``."""
    from vgate_tpu.models.hybrid import mamba_proj_pad

    get = lambda i, name: np.asarray(getter(f"model.layers.{i}.{name}"))
    lin = lambda i, name: {"w": get(i, f"{name}.weight").T}
    np_dtype = np.dtype(dtype)
    cast = lambda x: np.asarray(x).astype(np_dtype)
    keep_f32 = ("a_log", "d", "dt_bias")
    F = spec.intermediate_size

    def layer(i):
        out = {"input_norm": get(i, "input_layernorm.weight"),
               "post_norm": get(i, "post_attention_layernorm.weight")}
        if spec.stack[i][0] == "mamba":
            out.update(
                in_proj={"w": mamba_proj_pad(
                    lin(i, "mamba.in_proj")["w"])},
                conv=get(i, "mamba.conv1d.weight")[:, 0, :],
                a_log=get(i, "mamba.A_log"), d=get(i, "mamba.D"),
                dt_bias=get(i, "mamba.dt_bias"),
                ssm_norm=get(i, "mamba.norm.weight"),
                out=lin(i, "mamba.out_proj"))
            if spec.mamba_conv_bias:
                out["conv_bias"] = get(i, "mamba.conv1d.bias")
        else:
            for n in "qkvo":
                out[n] = lin(i, f"self_attn.{n}_proj")
        both = lin(i, "shared_mlp.input_linear")["w"]  # [D, 2 F]
        out["gate"], out["up"] = {"w": both[:, :F]}, {"w": both[:, F:]}
        out["down"] = lin(i, "shared_mlp.output_linear")
        return {k: (np.asarray(v, np.float32) if k in keep_f32
                    else jax.tree.map(cast, v)) for k, v in out.items()}

    P = spec.num_periods
    layers: Dict[str, Any] = {}
    for group in ("mamba", "attn"):
        trees = [layer(i) for i in range(spec.num_layers)
                 if spec.stack[i][0] == group]
        if trees:
            layers[group] = jax.tree.map(
                lambda *xs: np.stack(xs).reshape(
                    (P, len(xs) // P) + xs[0].shape), *trees)
    params: Params = {
        "embed": cast(getter("model.embed_tokens.weight")),
        "layers": layers,
        "final_norm": cast(getter("model.norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = cast(np.asarray(getter("lm_head.weight")).T)
    return params


def params_from_getter(
    spec: ModelSpec, getter: TensorGetter, dtype=jnp.bfloat16
) -> Params:
    """Assemble the decoder pytree from HF-named tensors (host numpy)."""
    if spec.eva_layers:
        # no checkpoint or index file of this family is in the repository
        # to hold tensor names against: random weights alone
        raise NotImplementedError(
            f"{spec.name}: loading a checkpoint of an EVA stack is not "
            "supported (its tensor names are unverified); serve it on "
            "random weights")
    if spec.kv_rows:
        return _kv_dsa_params_from_getter(spec, getter, dtype)
    if spec.is_dsa:
        return _dsa_params_from_getter(spec, getter, dtype)
    if spec.is_mla:
        return _mla_params_from_getter(spec, getter, dtype)
    if spec.window_pattern:
        return _window_params_from_getter(spec, getter, dtype)
    if spec.conv_pattern:
        return _conv_params_from_getter(spec, getter, dtype)
    if spec.mamba_pattern:
        return _mamba_mlp_params_from_getter(spec, getter, dtype)
    if spec.layer_pattern:
        return _pattern_params_from_getter(spec, getter, dtype)
    if spec.is_hybrid:
        return _hybrid_params_from_getter(spec, getter, dtype)
    L = spec.num_layers
    pre = "model.layers.{}."
    layers: Dict[str, Any] = {
        "input_norm": _stack(getter, pre + "input_layernorm.weight", L),
        "post_norm": _stack(getter, pre + "post_attention_layernorm.weight", L),
        "q": {"w": _stack(getter, pre + "self_attn.q_proj.weight", L, True)},
        "k": {"w": _stack(getter, pre + "self_attn.k_proj.weight", L, True)},
        "v": {"w": _stack(getter, pre + "self_attn.v_proj.weight", L, True)},
        "o": {"w": _stack(getter, pre + "self_attn.o_proj.weight", L, True)},
    }
    if spec.qkv_bias:
        layers["q"]["b"] = _stack(getter, pre + "self_attn.q_proj.bias", L)
        layers["k"]["b"] = _stack(getter, pre + "self_attn.k_proj.bias", L)
        layers["v"]["b"] = _stack(getter, pre + "self_attn.v_proj.bias", L)
    if spec.ffn_sandwich:
        # Gemma-2 sandwich norms (HF Gemma2ForCausalLM names)
        layers["pre_ffn_norm"] = _stack(
            getter, pre + "pre_feedforward_layernorm.weight", L
        )
        layers["post_ffn_norm"] = _stack(
            getter, pre + "post_feedforward_layernorm.weight", L
        )
    if spec.is_moe:
        E = spec.num_experts
        layers["router"] = _stack(
            getter, pre + "block_sparse_moe.gate.weight", L, True
        )
        def stack_experts(w_name, transpose):
            per_layer = []
            for i in range(L):
                per_expert = [
                    np.asarray(
                        getter(
                            f"model.layers.{i}.block_sparse_moe.experts."
                            f"{e}.{w_name}.weight"
                        )
                    )
                    for e in range(E)
                ]
                stacked = np.stack(
                    [w.T if transpose else w for w in per_expert]
                )
                per_layer.append(stacked)
            return np.stack(per_layer)  # [L, E, ...]

        layers["gate"] = {"w": stack_experts("w1", True)}
        layers["down"] = {"w": stack_experts("w2", True)}
        layers["up"] = {"w": stack_experts("w3", True)}
    else:
        layers["gate"] = {"w": _stack(getter, pre + "mlp.gate_proj.weight", L, True)}
        layers["up"] = {"w": _stack(getter, pre + "mlp.up_proj.weight", L, True)}
        layers["down"] = {"w": _stack(getter, pre + "mlp.down_proj.weight", L, True)}

    params: Params = {
        "embed": np.asarray(getter("model.embed_tokens.weight")),
        "layers": layers,
        "final_norm": np.asarray(getter("model.norm.weight")),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = np.asarray(getter("lm_head.weight")).T
    # Stay on the HOST: leaves are numpy (bf16 via ml_dtypes), so the single
    # device placement happens later at parallel/sharding.shard_params —
    # jax.device_put(np_leaf, NamedSharding) transfers each mesh shard
    # directly, never materializing a full replica in HBM (a 7B bf16
    # replica would OOM a 16 GB v5e chip before sharding could fix it).
    np_dtype = np.dtype(dtype)
    return jax.tree.map(lambda x: np.asarray(x).astype(np_dtype), params)


def params_from_torch_state_dict(
    spec: ModelSpec, state_dict, dtype=jnp.float32
) -> Params:
    """Build params from an in-memory torch state dict (used by the
    parity tests against transformers' reference implementation)."""

    def getter(name: str) -> np.ndarray:
        tensor = state_dict[name]
        return tensor.detach().to("cpu").float().numpy()

    return params_from_getter(spec, getter, dtype)


def safetensors_getter(checkpoint_path: str):
    """Index every ``*.safetensors`` shard under a directory.

    Returns ``(getter, files)`` — the getter resolves an HF tensor name to a
    host numpy array, tolerating an optional model prefix (e.g. ``bert.``)
    in the stored names."""
    from safetensors import safe_open

    files = sorted(
        os.path.join(checkpoint_path, f)
        for f in os.listdir(checkpoint_path)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(
            f"no .safetensors files under {checkpoint_path}"
        )
    handles = [safe_open(f, framework="np") for f in files]
    index: Dict[str, Any] = {}
    for handle in handles:
        for name in handle.keys():
            index[name] = handle
    prefixes = ("", "model.", "bert.")

    def getter(name: str) -> np.ndarray:
        if name not in index:
            for p in prefixes:
                if p + name in index:
                    name = p + name
                    break
            else:
                # e.g. tied-embedding checkpoints omit lm_head
                raise KeyError(f"tensor {name} missing from checkpoint")
        return index[name].get_tensor(name)

    return getter, files


def params_from_safetensors(
    spec: ModelSpec,
    checkpoint_path: str,
    dtype=jnp.bfloat16,
) -> Params:
    """Load from a local directory of ``*.safetensors`` shards.

    Returns HOST numpy leaves; the engine's ``shard_params`` performs the
    one and only device placement with each tensor's NamedSharding."""
    getter, files = safetensors_getter(checkpoint_path)
    params = params_from_getter(spec, getter, dtype)
    logger.info(
        "checkpoint loaded",
        extra={
            "extra_data": {
                "path": checkpoint_path,
                "files": len(files),
                "params_mb": round(
                    sum(
                        x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(params)
                    )
                    / 1e6
                ),
            }
        },
    )
    return params


def load_digests(params: Params) -> dict:
    """Per-shard load-time digests (host numpy, same positional-sum
    formula as the device-side integrity sweep — integrity.py
    host_leaf_digest) logged as load provenance: when a later checksum
    sweep flags a shard, the load-time digest answers "was it already
    wrong on disk, or did HBM flip it?".  The AUTHORITATIVE serving
    baseline is recorded post-placement (post-quantize/shard) by
    EngineIntegrity; these digests describe the host tree as loaded."""
    from vgate_tpu.integrity import digest_summary, host_leaf_digest

    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    digests = {
        jax.tree_util.keystr(p): host_leaf_digest(np.asarray(x))
        for p, x in leaves
    }
    return digest_summary(digests)


def load_or_init_params(
    spec: ModelSpec,
    checkpoint_path: Optional[str],
    dtype=jnp.bfloat16,
    seed: int = 0,
    log_digests: bool = False,
    mesh=None,
) -> Params:
    """Checkpoint when available, random init otherwise (zero-egress path).

    ``log_digests`` (integrity.enabled callers) logs the per-shard
    load-time digest summary — one full host pass over the tree, paid
    once at load.  With a multi-device ``mesh`` the random init runs
    under jit with the serving shardings as ``out_shardings``, so every
    chip draws only its own shard: drawn eagerly, a 7B tree (one gate
    tensor alone is 7.6 GB in float32) lands whole on the default device
    and runs it out of memory before ``shard_params`` can spread it."""
    from vgate_tpu import faults

    faults.check("weight_load", payload=checkpoint_path)
    if checkpoint_path and os.path.isdir(checkpoint_path):
        params = params_from_safetensors(spec, checkpoint_path, dtype)
    else:
        from vgate_tpu.models.decoder import init_params

        logger.warning(
            "no checkpoint found; using random-init weights",
            extra={
                "extra_data": {"model": spec.name, "path": checkpoint_path}
            },
        )
        key = jax.random.PRNGKey(seed)
        if mesh is not None and mesh.devices.size > 1:
            from vgate_tpu.parallel.sharding import named, param_pspecs

            params = jax.jit(
                init_params,
                static_argnums=(0, 2),
                out_shardings=named(mesh, param_pspecs(spec, mesh)),
            )(spec, key, dtype)
        else:
            params = init_params(spec, key, dtype)
    if log_digests:
        digest_start = time.perf_counter()
        try:
            logger.info(
                "load-time weight digests",
                extra={"extra_data": load_digests(params)},
            )
        except Exception:  # digest provenance must never block a load
            logger.warning("load-time digest pass failed", exc_info=True)
        note_boot("digest", time.perf_counter() - digest_start)
    return params
