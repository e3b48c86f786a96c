"""The decoder families (Qwen2.x/Llama-3/Mistral dense, Mixtral MoE,
Gemma-2 sliding-window) as pure JAX functions.

Design (TPU-first, not a torch port):

* **Stacked layer params + ``lax.scan``** — all layers' weights live in one
  pytree with a leading layer axis, and the forward pass scans over it.  One
  layer body is traced/compiled regardless of depth, keeping compile times
  flat (SURVEY.md section 7: recompile-avoidance discipline).
* **Paged KV cache threaded through the scan as carry** — the FULL
  ``[L, ...]`` pools ride the layer scan's carry with layer-indexed
  in-place writes and layer-indexed attention reads, so a step program
  needs weights + ONE pool.  (Threading them as per-layer xs/ys cannot
  alias the ys stack onto the xs stack: every program then held a second
  pool — PERF.md "Bring-up".)  Pages are written with the reserved *trash
  page 0* trick: padded positions scatter into page 0, so no masking is
  needed on the write path.
* **Static shapes everywhere** — prompt lengths are bucketed by the caller;
  decode is a fixed ``[B]`` step.  fp32 softmax/norms, bf16 matmuls on MXU.

Architecture semantics match HF ``Qwen2ForCausalLM`` / ``MixtralForCausalLM``
(verified against torch in tests/test_model_parity.py), replacing the
capability the reference delegates to vLLM (vgate/backends/vllm_backend.py:51).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.ops.attention import (
    flash_prefill_attention,
    paged_decode_attention,
    paged_suffix_attention,
)
from vgate_tpu.ops.kv_quant import (
    is_quantized,
    kv_write_pages,
    kv_write_tokens,
    page_tokens,
)
from vgate_tpu.ops.head_pack import over_packed_pool, pack_rows
from vgate_tpu.ops.norms import rms_norm
from vgate_tpu.ops.quant import PackedQTensor, QTensor, weighted_einsum
from vgate_tpu.ops.rope import apply_rope

Params = Dict[str, Any]


def init_params(
    spec: ModelSpec, key: jax.Array, dtype=jnp.bfloat16
) -> Params:
    """Random-init a full parameter pytree (std 0.02 normal).

    Real checkpoints overwrite these via runtime/weights.py; random init is
    the zero-egress path used for benchmarks (throughput is weight-value
    independent).
    """
    keys = jax.random.split(key, 16)
    D, L = spec.hidden_size, spec.num_layers
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    F, V = spec.intermediate_size, spec.vocab_size

    def normal(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    # Gemma-family RMSNorm stores a delta around 1 (unit_offset_norm), so
    # identity init is zeros there, ones elsewhere.
    norm_init = jnp.zeros if spec.unit_offset_norm else jnp.ones
    if spec.is_hybrid:
        from vgate_tpu.models.hybrid import init_layers

        # one fused program a tensor (bits, scale and cast together):
        # drawn eagerly, every primitive of every shape compiles on its
        # own and a 0.8 G-element tensor stands in float32 twice; the
        # values are the eager draw's
        fused = jax.jit(normal, static_argnums=(1, 2))
        draw = lambda k, shape, scale=0.02: fused(k, tuple(shape), scale)
        params = {
            "embed": draw(keys[8], (V, D)),
            "layers": init_layers(spec, key, dtype, draw, norm_init),
            "final_norm": norm_init((D,), dtype),
        }
        if not spec.tie_embeddings:  # ``num_pred_heads`` heads' columns
            params["lm_head"] = draw(keys[9], (D, V * spec.num_pred_heads))
        return params
    layers: Dict[str, Any] = {
        "input_norm": norm_init((L, D), dtype),
        "post_norm": norm_init((L, D), dtype),
        "q": {"w": normal(keys[0], (L, D, H * hd))},
        "k": {"w": normal(keys[1], (L, D, KV * hd))},
        "v": {"w": normal(keys[2], (L, D, KV * hd))},
        "o": {"w": normal(keys[3], (L, H * hd, D))},
    }
    if spec.qkv_bias:
        layers["q"]["b"] = jnp.zeros((L, H * hd), dtype)
        layers["k"]["b"] = jnp.zeros((L, KV * hd), dtype)
        layers["v"]["b"] = jnp.zeros((L, KV * hd), dtype)
    if spec.ffn_sandwich:
        layers["pre_ffn_norm"] = norm_init((L, D), dtype)
        layers["post_ffn_norm"] = norm_init((L, D), dtype)
    if spec.is_moe:
        E, Fe = spec.num_experts, spec.expert_width
        layers["router"] = normal(keys[4], (L, D, spec.router_experts))
        layers["gate"] = {"w": normal(keys[5], (L, E, D, Fe))}
        layers["up"] = {"w": normal(keys[6], (L, E, D, Fe))}
        layers["down"] = {"w": normal(keys[7], (L, E, Fe, D))}
    else:
        layers["gate"] = {"w": normal(keys[5], (L, D, F))}
        layers["up"] = {"w": normal(keys[6], (L, D, F))}
        layers["down"] = {"w": normal(keys[7], (L, F, D))}

    params: Params = {
        "embed": normal(keys[8], (V, D)),
        "layers": layers,
        "final_norm": norm_init((D,), dtype),
    }
    if not spec.tie_embeddings:
        params["lm_head"] = normal(keys[9], (D, V))
    return params


# jax.named_scope below is HLO metadata only: the device trace's op
# names then say which block of a layer a fusion belongs to (embed /
# qkv / kv_write / attention / o_proj / mlp / logits; the step programs
# add "sample").  Nothing runs for it.

@jax.named_scope("qkv_proj")
def _project_qkv(x, lp, spec: ModelSpec):
    """x: [..., D] -> q [..., H, hd], k/v [..., KV, hd]."""
    ik, i8 = spec.quant_kernel, spec.int8_native
    q = weighted_einsum("...d,dh->...h", x, lp["q"]["w"], quant_kernel=ik,
                        int8_native=i8)
    k = weighted_einsum("...d,dh->...h", x, lp["k"]["w"], quant_kernel=ik,
                        int8_native=i8)
    v = weighted_einsum("...d,dh->...h", x, lp["v"]["w"], quant_kernel=ik,
                        int8_native=i8)
    if spec.qkv_bias:
        q = q + lp["q"]["b"]
        k = k + lp["k"]["b"]
        v = v + lp["v"]["b"]
    q = q.reshape(*q.shape[:-1], spec.num_heads, spec.head_dim)
    k = k.reshape(*k.shape[:-1], spec.num_kv_heads, spec.head_dim)
    v = v.reshape(*v.shape[:-1], spec.num_kv_heads, spec.head_dim)
    return q, k, v


def _act(x32, spec: ModelSpec):
    """MLP activation in fp32: SiLU (Qwen/Llama/Mixtral), tanh-approx
    GELU (Gemma's ``gelu_pytorch_tanh``) or squared ReLU."""
    if spec.act == "gelu_tanh":
        return jax.nn.gelu(x32, approximate=True)
    if spec.act == "relu2":  # Nemotron-H's experts: relu(.)^2
        return jnp.square(jax.nn.relu(x32))
    return jax.nn.silu(x32)


def _dense_mlp(x, lp, spec: ModelSpec):
    ik, i8 = spec.quant_kernel, spec.int8_native
    gate = weighted_einsum("...d,df->...f", x, lp["gate"]["w"],
                           quant_kernel=ik, int8_native=i8)
    up = weighted_einsum("...d,df->...f", x, lp["up"]["w"], quant_kernel=ik,
                         int8_native=i8)
    return weighted_einsum(
        "...f,fd->...d",
        _act(gate.astype(jnp.float32), spec).astype(x.dtype) * up,
        lp["down"]["w"],
        quant_kernel=ik,
        int8_native=i8,
    )


@jax.named_scope("mlp")
def _mlp(x, lp, spec: ModelSpec):
    """Dense SwiGLU, or the ONE expert layer (ops/moe.py): Mixtral is
    its case "holds every expert, no shared expert", and drops nothing."""
    if not spec.is_moe:
        return _dense_mlp(x, lp, spec)
    from vgate_tpu.ops.moe import expert_layer

    return expert_layer(x, lp, spec, lambda x32: _act(x32, spec))[0]


def _final_norm(params: Params, spec: ModelSpec, x: jnp.ndarray):
    x = rms_norm(
        x, params["final_norm"], spec.rms_eps, spec.unit_offset_norm
    )
    if spec.fp32_residual:  # the product takes the weights' type
        x = x.astype(params["final_norm"].dtype)
    return x


@jax.named_scope("logits")
def _logits(params: Params, spec: ModelSpec, x: jnp.ndarray,
            all_heads: bool = False) -> jnp.ndarray:
    """The final norm and the output layer.  A spec with several
    prediction heads (``num_pred_heads``: head p scores the token at t +
    1 + p) serves head 0's ``vocab_size`` columns; ``all_heads``: every
    head's, ``[..., heads x vocab]``."""
    from vgate_tpu.ops.attention import _softcap

    x = _final_norm(params, spec, x)
    if spec.num_pred_heads > 1 and not all_heads:
        params = {**params,
                  "lm_head": params["lm_head"][:, :spec.vocab_size]}
    if spec.tie_embeddings:
        # embeddings are never quantized (gathers stay high-precision)
        logits = jnp.einsum(
            "...d,vd->...v", x, params["embed"],
            preferred_element_type=jnp.float32,
        )
    else:
        # int8_native deliberately NOT applied to the lm_head: per-token
        # activation quantization error (~1% of logit absmax) can flip
        # the argmax between near-tied top logits under greedy decoding,
        # so the logits GEMM keeps the dequant path (W8A8 convention).
        logits = weighted_einsum(
            "...d,dv->...v", x, params["lm_head"],
            preferred_element_type=jnp.float32,
            quant_kernel=spec.quant_kernel,
        )
    if spec.logits_scaling != 1.0:  # Granite's: before any edit
        logits = logits / spec.logits_scaling
    return _softcap(logits, spec.final_softcap)


def decode_head_impl(
    params: Params, spec: ModelSpec, use_pallas: bool, mesh=None, *,
    rows: int, all_greedy: bool = False, num_logprobs: int = 0,
    penalised: bool = False, bias_width: int = 0, stop_width: int = 0,
) -> str:
    """What ends a decode chunk's step, for these static arguments:
    ``"fused"``, ``greedy_head`` below (the step's logits stay on the
    chip: a token and a flag word a row come out), or ``"logits"``,
    ``_logits`` and what the chunk does with the array.  The fused pass
    serves a chunk whose rows need nothing else of the logits: every
    row greedy and none asking for logprobs, no penalties, edits narrow
    enough to be compares, no soft cap, an unquantised head on one
    device.  ``_decode_chunk`` selects through this and the engine
    reports it (the decode-dispatch spans' ``head``, /debug/perf ->
    totals.decode_steps_fused_head)."""
    from vgate_tpu.ops.pallas.greedy_head import worth_fusing
    from vgate_tpu.ops.sampling import COMPARE_MAX_IDS

    head = params["embed" if spec.tie_embeddings else "lm_head"]
    fused = (
        use_pallas
        and all_greedy and num_logprobs == 0 and not penalised
        and max(bias_width, stop_width) <= COMPARE_MAX_IDS
        and spec.final_softcap == 0
        and (mesh is None or mesh.size == 1)
        and not isinstance(head, (QTensor, PackedQTensor))
        and worth_fusing(rows, spec.vocab_size, head.shape)
    )
    return "fused" if fused else "logits"


@jax.named_scope("head")
def greedy_head(params: Params, spec: ModelSpec, x: jnp.ndarray,
                bias_ids, bias_vals, stop_ids, guard: bool,
                guard_threshold: float):
    """The final norm and the output layer of a greedy step whose
    logits nobody reads (``decode_head_impl``): ``(next_tokens [B]
    int32, guard flags [B] uint8)`` of ``_logits``' array under the
    ``logit_bias`` and the live stop ids' floor, the array itself never
    in HBM (ops/pallas/greedy_head.py).  A spec with several prediction
    heads serves head 0's ``vocab_size`` columns where they stand."""
    from vgate_tpu.ops.pallas.greedy_head import greedy_head_pallas

    tied = spec.tie_embeddings
    return greedy_head_pallas(
        _final_norm(params, spec, x),
        params["embed" if tied else "lm_head"],
        bias_ids, bias_vals, stop_ids, tied=tied, vocab=spec.vocab_size,
        guard=guard, threshold=guard_threshold,
        divisor=spec.logits_scaling,
    )


def _query_scale(spec: ModelSpec):
    """Attention query scale override (Gemma-2's query_pre_attn_scalar,
    latent attention's scale under YaRN, Granite's attention_multiplier);
    None selects the default head_dim**-0.5 inside the attention ops."""
    if spec.is_mla:
        return spec.mla_softmax_scale
    if spec.attention_multiplier > 0:
        return spec.attention_multiplier
    return spec.query_scale ** -0.5 if spec.query_scale > 0 else None


@jax.named_scope("embed")
def _embed(params: Params, spec: ModelSpec, tokens: jnp.ndarray):
    x = params["embed"][tokens]
    if spec.embed_scale:
        # Gemma scales embeddings by sqrt(hidden), cast to the model dtype
        # BEFORE the multiply (the HF convention, needed for parity).
        x = x * jnp.asarray(spec.hidden_size ** 0.5, x.dtype)
    if spec.embedding_multiplier != 1.0:  # Granite's: a published number
        x = x * jnp.asarray(spec.embedding_multiplier, x.dtype)
    return x


def _layer_windows(spec: ModelSpec) -> jnp.ndarray:
    """[L] int32 per-layer attention window for the layer scan (all zeros
    for global-attention families)."""
    return jnp.asarray(spec.layer_windows, jnp.int32)


def _axis(mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1)) if mesh is not None else 1


def _kernel_or_twin(spec: ModelSpec, use_pallas: bool, mesh) -> str:
    """Shared tail of the selectors below: the Pallas kernel, the kernel
    per tp shard (parallel/tp_attention.py), or the auto-partitioned jnp
    twin when heads don't divide tp (strictly better than a GSPMD-
    replicated pallas_call)."""
    if not use_pallas:
        return "jnp"
    if _axis(mesh, "tp") > 1:
        from vgate_tpu.parallel.tp_attention import tp_divisible

        if tp_divisible(mesh, spec.num_heads, spec.num_kv_heads):
            return "pallas_tp"
        return "jnp"
    return "pallas"


# the prompt passes that shard a sequence's rows or relay them between
# stages: theirs is the whole bucket, whatever the prompts hold
WHOLE_BUCKET_IMPLS = ("pp_relay", "ring")


def prefill_attention_impl(
    spec: ModelSpec, use_pallas: bool, mesh=None
) -> str:
    """The attention implementation ``prefill_forward`` traces for these
    static arguments.  The forwards select through these functions, and
    the engine reports their answer per compiled program in /stats →
    engine.attention, so a shape or mesh gate that swaps a kernel for
    its jnp twin is visible instead of silent."""
    if _axis(mesh, "pp") > 1:
        return "pp_relay"
    if _axis(mesh, "sp") > 1:
        return "ring"
    return _kernel_or_twin(spec, use_pallas, mesh)


def decode_attention_impl(
    spec: ModelSpec, use_pallas: bool, mesh=None
) -> str:
    """As ``prefill_attention_impl``, for ``decode_forward``."""
    if _axis(mesh, "pp") > 1:
        return "pp_relay"
    if _axis(mesh, "sp") > 1:
        return "sp_shard"
    return _kernel_or_twin(spec, use_pallas, mesh)


def decode_kv_write(
    spec: ModelSpec, use_pallas: bool, mesh=None, quantized: bool = False
) -> str:
    """Who puts a decode step's new K and V into the pool, for these
    static arguments and this kind of pool: ``"kernel"``, the Pallas
    decode kernel itself (ops/pallas/paged_attention.py: the token's
    page goes back through its own DMA descriptors), or ``"scatter"``,
    ``kv_write_tokens`` before the attention (the jnp twin, an int8
    pool, the kernel per tp shard, the sp and pp paths).
    ``decode_forward`` selects through this and the engine reports it
    (/stats → engine.kv_write, the decode-dispatch spans)."""
    kernel = (
        decode_attention_impl(spec, use_pallas, mesh) == "pallas"
        and not quantized
        # under a selection the kernel fetches rows and writes none
        and not spec.is_dsa
    )
    return "kernel" if kernel else "scatter"


def _mla_write_attend(spec: ModelSpec, impl: str, kernel_writes: bool,
                      page_tables, seq_lens, page_ids, page_off):
    """``decode_forward``'s cache step for latent attention:
    ``write_attend(q, row, None, pages, None, layer)`` puts the token's
    latent row into the ONE pool and attends over it in the absorbed
    form (ops/pallas/paged_attention.py mla_decode_attention_pallas, or
    its jnp twin after ``kv_write_tokens``)."""
    from vgate_tpu.ops.attention import mla_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        mla_decode_attention_pallas,
    )

    kw = dict(v_width=spec.kv_lora_rank, scale=spec.mla_softmax_scale)

    def write_attend(q, row, _v, kp, vp, layer):
        if kernel_writes:
            with jax.named_scope("attention"):
                attn, kp = mla_decode_attention_pallas(
                    q, kp, page_tables, seq_lens, layer, row, **kw)
            return attn, kp, vp
        with jax.named_scope("kv_write"):
            kp = kv_write_tokens(
                kp, page_ids, page_off, row[:, None], layer=layer)
        with jax.named_scope("attention"):
            fn = (mla_decode_attention_pallas if impl == "pallas"
                  else mla_decode_attention)
            attn = fn(q, kp, page_tables, seq_lens, layer, **kw)
        return attn, kp, vp

    return write_attend


def _dsa_decode_steps(spec: ModelSpec, impl: str, page_tables, seq_lens,
                      page_ids, page_off, page_size: int):
    """``decode_forward``'s cache steps for attention under a learned
    selection (models/hybrid.py ``_dsa_step``; ``_kv_dsa_step`` for GQA
    attention over a pool of K over V, whose ``attend`` takes the
    token's ``(k, v)`` where the latent form's takes its row), ``(pick,
    attend, the rows a pick holds)``:

    * ``pick(qi, w, key, index_pages, layer)`` puts the token's index key
      into the pool's second array, scores the slot's live keys
      (ops/pallas/dsa.py ``dsa_index_scores_pallas``, or the jnp twin),
      takes the ``index_topk`` positions and returns where those rows
      lie in a layer of the latent pool, [B, k] (ops/dsa.py
      ``order_picks``);
    * ``attend(q, row, rows, pages, layer)`` puts the token's latent row
      into the pool and attends over the picked rows alone, which the
      kernel fetches itself (ops/dsa.py ``dsa_decode_attention``).

    While no live context is longer than ``index_topk`` the pick is
    everything, without scores: the same attention over the first
    ``seq_lens`` rows of each slot."""
    from vgate_tpu.ops import dsa
    from vgate_tpu.ops.attention import mla_gather_rows
    from vgate_tpu.ops.pallas.dsa import dsa_index_scores_pallas

    kernel = impl == "pallas"
    B, n_pages = page_tables.shape
    tokens = n_pages * page_size
    k = min(spec.index_topk, tokens)
    short = jnp.max(seq_lens) <= k
    n_sel = jnp.minimum(seq_lens, k)

    def pick(qi, w, key, ip, layer):
        with jax.named_scope("kv_write"):
            ip = kv_write_tokens(
                ip, page_ids, page_off, key[:, None], layer=layer)

        def scored():
            with jax.named_scope("dsa_index"):
                if kernel:
                    scores = dsa_index_scores_pallas(
                        qi, w, ip, page_tables, seq_lens, layer)
                else:
                    keys = mla_gather_rows(ip, page_tables, layer)
                    scores = dsa.index_scores(
                        qi[:, None], w[:, None], keys)[:, 0]
                    scores = jnp.where(
                        jnp.arange(tokens)[None, :] < seq_lens[:, None],
                        scores, dsa.NEG_INF)
            with jax.named_scope("dsa_select"):
                return dsa.select_positions(scores, k)

        everything = lambda: jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32), (B, k))
        sel = jax.lax.cond(short, everything, scored)
        with jax.named_scope("dsa_select"):
            return dsa.order_picks(page_tables, sel, n_sel, page_size), ip

    def attend(q, row, rows, kp, layer):
        # ``row``: the token's latent row or, for GQA over a pool of K
        # over V (``ModelSpec.kv_rows``), its (k, v)
        with jax.named_scope("kv_write"):
            if spec.kv_rows:
                kp = dsa.kv_rows_write_tokens(
                    kp, page_ids, page_off, *row, layer)
            else:
                kp = kv_write_tokens(
                    kp, page_ids, page_off, row[:, None], layer=layer)
        with jax.named_scope("dsa_attend"):
            if spec.kv_rows:
                attn = dsa.kv_rows_decode_attention(
                    q, kp, rows, n_sel, layer, use_pallas=kernel,
                    scale=_query_scale(spec) or spec.head_dim ** -0.5)
            else:
                attn = dsa.dsa_decode_attention(
                    q, kp, rows, n_sel, layer, use_pallas=kernel,
                    v_width=spec.kv_lora_rank,
                    scale=spec.mla_softmax_scale)
        return attn, kp

    return pick, attend, k


def _dsa_prefill_attend(spec: ModelSpec, impl: str, seq_lens, rows: int):
    """A prompt pass's attention under a selection, ``attend(q, k, v,
    mask)`` (models/hybrid.py ``_dsa_prompt``): the flash kernel with the
    mask beside each key block, under a name of its own
    (ops/pallas/dsa.py), for a whole prompt's rows against their own
    keys; else the plain jnp twin."""
    from vgate_tpu.ops import dsa

    scale = _query_scale(spec) or spec.head_dim ** -0.5
    if impl == "pallas":
        from vgate_tpu.ops.pallas.dsa import dsa_prefill_attention_pallas

        block = 1024 if rows >= 4096 else 256
        return lambda q, k, v, mask: dsa_prefill_attention_pallas(
            q, k, v, seq_lens, mask, scale=scale, block_q=block,
            block_k=block)
    return lambda q, k, v, mask: dsa.masked_attention(q, k, v, mask, scale)


def _swa_prefill_attend(spec: ModelSpec, impl: str, seq_lens):
    """``prefill_forward``'s attention for a window layer of a
    ``window_pattern`` spec, ``attend(q, k, v)`` over the prompt's own
    rows: the flash kernel over a band of key blocks under a name of its
    own (ops/pallas/flash_prefill.py swa_prefill_attention_pallas), or
    the blockwise jnp twin with the window as a mask."""
    if not spec.swa_layers:
        return None
    if impl == "pallas":
        from vgate_tpu.ops.pallas.flash_prefill import (
            swa_prefill_attention_pallas,
        )

        # nothing reads a padding row's attention: their query blocks
        # are left out
        return lambda q, k, v: swa_prefill_attention_pallas(
            q, k, v, seq_lens, spec.sliding_window, skip_padding=True)
    return lambda q, k, v: flash_prefill_attention(
        q, k, v, seq_lens, window=spec.sliding_window)


def _eva_prefill_attend(spec: ModelSpec, impl: str):
    """``prefill_forward``'s attention for a whole prompt of whole
    windows of an EVA spec (models/hybrid.py ``_eva_prompt``),
    ``attend(q, k, v, lens, q_offset, k_start)``: a window's queries
    from key ``q_offset`` on, causal, over the keys ``k_start .. lens -
    1``.  The flash kernel under a name of its own, in blocks of up to
    1,024 rows that divide the summaries' count and the window, or the
    blockwise jnp twin."""
    if not spec.eva_layers:
        return None
    if impl == "pallas":
        from vgate_tpu.ops.pallas.flash_prefill import (
            flash_prefill_attention_pallas,
        )

        def attend(q, k, v, lens, q_offset, k_start):
            return flash_prefill_attention_pallas(
                q, k, v, lens, q_offsets=q_offset, k_starts=k_start,
                block_q=min(1024, q.shape[1]),
                block_k=min(1024, math.gcd(k.shape[1], q.shape[1])),
                skip_padding=True, name="eva_prefill_attention_pallas")

        return attend
    return lambda q, k, v, lens, q_offset, k_start: flash_prefill_attention(
        q, k, v, lens, q_offset=q_offset, k_start=k_start,
        block_k=math.gcd(k.shape[1], 256))


def prefill_attn_tiles(spec: ModelSpec, S: int, lens) -> Tuple[int, int]:
    """(tiles, interior tiles) a query head computes in the Pallas prompt
    attention launches of ONE ``prefill_forward`` over ``[len(lens), S]``
    rows that hold ``lens`` tokens (ints on the host), summed over the
    spec's layers, each in the blocks its launch takes above (a full
    layer's, plain or under a selection; a window layer's band; an EVA
    layer's windows): ops/pallas/flash_prefill.py ``tile_counts``, the
    kernel's own predicate, for ``/debug/perf -> totals.prefill_attn``."""
    import numpy as np

    from vgate_tpu.models.hybrid import prompt_rows
    from vgate_tpu.ops.pallas.flash_prefill import swa_blocks, tile_counts

    lens = [int(n) for n in lens]
    block = 1024 if S >= 4096 else 256
    # (the launch leaves padding query blocks out where the pass reads
    # none: a stack of several kinds, and the dense stack's packed pass)
    skip = spec.is_hybrid or not isinstance(
        prompt_rows(spec, S, np.asarray(lens))[1], int)
    W, c = spec.eva_window, spec.eva_chunk
    # ``tile_counts``' arguments of a layer's launch -> layers that make it
    launches: Dict[tuple, int] = {}
    form = lambda *args, **kw: (args, tuple(sorted(kw.items())))
    for kinds, window in zip(spec.stack, spec.layer_windows):
        if "swa" in kinds:
            bq, bk = swa_blocks(window, S)
            launch = form(
                tuple(lens), S, S, bq, bk, window=window,
                band=bq // bk + -(-(window - 1) // bk), skip_padding=True)
        elif "eva" in kinds and S > W:
            if S % W:
                continue  # no whole windows: the jnp twin's
            nw, ns = S // W, S // c
            own = [min(max(n - w * W, 0), W) for n in lens
                   for w in range(nw)]
            launch = form(
                tuple(ns + n for n in own), W, ns + W, min(1024, W),
                min(1024, math.gcd(ns + W, W)), q_offsets=(ns,) * len(own),
                k_starts=tuple(ns - w * W // c
                               for _ in lens for w in range(nw)),
                skip_padding=True)
        elif {"attn", "mla", "dsa", "eva"} & set(kinds):
            launch = form(tuple(lens), S, S, block, block, window=window,
                          skip_padding=skip)
        else:
            continue
        launches[launch] = launches.get(launch, 0) + 1
    tiles = interior = 0
    for (args, kw), layers in launches.items():
        some, inside = tile_counts(*args, **dict(kw))
        tiles, interior = tiles + layers * some, interior + layers * inside
    return tiles, interior


def packed_group(spec: ModelSpec) -> int:
    """Query heads a row of a PACKED pool serves (2 G); 0 where rows
    hold one head."""
    return spec.num_heads // spec.cache_heads if spec.kv_head_pack > 1 else 0


def multitok_attention_impl(
    use_pallas: bool, mesh=None, rows: int = 1, unaligned: bool = False,
    latent: bool = False, group: int = 0,
) -> str:
    """As above, for the paged multi-token attention of
    ``prefill_suffix_forward`` (``rows`` = suffix bucket) and
    ``spec_verify_forward``.  The kernel holds all query rows in VMEM
    (it was sized for speculative verify): at 1024 rows, G=6, hd=128 the
    f32 acc/m/l/scores blocks total ~15 MB; 2048 doubles that and
    serializes huge per-program dots, so wider buckets keep the
    blockwise jnp path (row-tiling the kernel is the future fix).  Its
    DMA ranges assume page-aligned starts, so the COW ``unaligned``
    variant rides jnp too.  tp>1: the jnp path auto-partitions; the
    kernel would be GSPMD-replicated (parallel/tp_attention.py)."""
    if _axis(mesh, "sp") > 1:
        return "sp_shard"
    if latent:  # no kernel yet for query rows against a latent prefix
        return "jnp"
    # over packed rows (``group`` = ``packed_group``: 2 G query heads a
    # row of 128 lanes) the kernel's blocks grow with the group: 1,024
    # rows x 14 heads (the 0.5B) run out of VMEM where 1,024 x 8 (LFM2)
    # compile (tests/test_tpu_aot.py), so the rows it takes shrink
    kernel_fits = (rows * max(group, 8) <= 8192 and not unaligned
                   and _axis(mesh, "tp") == 1)
    return "pallas" if use_pallas and kernel_fits else "jnp"


def _last_rows(x, lens):
    """x [B, S, D] -> the row at ``lens - 1`` of each sequence, [B, D]."""
    last_idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(
        x, last_idx[:, None, None].repeat(x.shape[-1], axis=-1), axis=1
    )[:, 0]


def _kv_layer_scan(params, spec: ModelSpec, body, x0, k_pages, v_pages):
    """The one layer-scan scaffold every plain-mesh forward shares.

    ``body(h, lp, win, kp, vp, layer)`` runs one transformer layer
    against the FULL stacked pools (``layer`` is the traced layer index;
    writes and attention reads are layer-indexed, updated in place) and
    returns ``(h, kp, vp)``.  Returns ``(x, k_pages, v_pages)``."""
    def fn(carry, per_layer):
        h, kp, vp = carry
        lp, win, l = per_layer
        h, kp, vp = body(h, lp, win, kp, vp, l)
        return (h, kp, vp), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        fn,
        (x0, k_pages, v_pages),
        (
            params["layers"],
            _layer_windows(spec),
            jnp.arange(spec.num_layers, dtype=jnp.int32),
        ),
    )
    return x, k_pages, v_pages


def prefill_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, S] padded to a bucket; S % page_size == 0
    seq_lens: jnp.ndarray,  # [B]
    k_pages: jnp.ndarray,  # [L, KV, P, ps, hd] (head-major, kv_cache.py)
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, S // ps] page ids for this prompt
    mesh=None,  # jax.sharding.Mesh; sp>1 routes attention through the ring
    use_pallas: bool = False,
    state=None,  # hybrid specs: the recurrent state (models/hybrid.py)
    slots=None,  # [B] decode slot of each row (its row of the state)
    all_heads: bool = False,  # every prediction head's logits (_logits)
) -> Tuple[jnp.ndarray, ...]:
    """Run the prompt pass: returns (last-token logits [B, V], k_pages,
    v_pages), and the recurrent state after them for a hybrid spec.

    Attention is flash-style on every path — blockwise online softmax, no
    [B,H,S,S] score materialization: the Pallas kernel
    (ops/pallas/flash_prefill.py) when ``use_pallas``, the jnp blockwise
    twin otherwise.  With a mesh whose ``sp`` axis is >1, attention runs
    sequence-parallel instead: each sp shard computes its query block and KV
    blocks rotate over ICI (parallel/ring_attention.py) — the long-context
    path (SURVEY.md section 5.7, absent in the reference).  ``S`` must
    divide by sp.
    """
    B, S = tokens.shape
    impl = prefill_attention_impl(spec, use_pallas, mesh)
    if impl == "pp_relay":
        from vgate_tpu.parallel.pipeline import pp_prefill_forward

        return pp_prefill_forward(
            params, spec, tokens, seq_lens, k_pages, v_pages, page_tables,
            mesh=mesh, use_pallas=use_pallas,
        )
    if impl == "ring":
        # sliding-window/softcap families (Gemma-2) ride the ring too:
        # per-layer window masks compose with the ring's global block-
        # position masks (parallel/ring_attention.py ring_attention_shard)
        from vgate_tpu.parallel.ring_attention import ring_prefill_attention

        attn_fn = functools.partial(
            ring_prefill_attention, mesh=mesh, softcap=spec.attn_softcap,
            scale=_query_scale(spec),
        )
    elif impl == "jnp":
        attn_fn = functools.partial(
            flash_prefill_attention,
            softcap=spec.attn_softcap,
            scale=_query_scale(spec),
        )
    else:
        from vgate_tpu.ops.pallas.flash_prefill import (
            flash_prefill_attention_pallas,
        )

        # a 4,096- or 8,192-row bucket walked in 256-row blocks is bound
        # by grid steps, not arithmetic (32 x 32 blocks a head: 25.8 ms
        # a layer at 8,192 rows x 32 heads against 6.9 ms in 1,024-row
        # blocks; chip, PR 33).  No bucket under 4,096 rows changes
        blocks = {"block_q": 1024, "block_k": 1024} if S >= 4096 else {}
        attn_fn = functools.partial(
            flash_prefill_attention_pallas,
            softcap=spec.attn_softcap,
            scale=_query_scale(spec),
            **blocks,
        )
        if impl == "pallas_tp":
            # run the kernel per shard — GSPMD has no partition rule for
            # pallas_call and would replicate the sharded q/k/v heads
            from vgate_tpu.parallel.tp_attention import (
                tp_flash_prefill_attention,
            )

            attn_fn = functools.partial(
                tp_flash_prefill_attention, attn_fn, mesh
            )
    from vgate_tpu.models import hybrid

    # a stack of one kind: a group of two row blocks or more is worked
    # on packed, the real rows end to end
    n_rows = S if spec.is_hybrid else hybrid.prompt_rows(
        spec, S, seq_lens, whole=impl not in WHOLE_BUCKET_IMPLS)[1]
    if not isinstance(n_rows, int):
        if impl == "pallas":  # this pass never reads a padding row
            attn_fn = functools.partial(attn_fn, skip_padding=True)
        x, k_pages, v_pages = _packed_prompt_pass(
            params, spec, tokens, seq_lens, k_pages, v_pages, page_tables,
            attn_fn, n_rows)
        return _logits(params, spec, x, all_heads), k_pages, v_pages
    x = _embed(params, spec, tokens)  # [B, S, D]
    # the prompt pass only WRITES pages (attention runs over the fresh
    # k/v): layer-indexed in-place writes on the carried pools
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if spec.is_hybrid:
        # the kernel leaves out the query blocks of padding rows, which
        # this pass never reads
        skip = {"skip_padding": True} if impl == "pallas" else {}
        x, k_pages, v_pages, state = hybrid.prompt_forward(
            params, spec, x, seq_lens, positions, k_pages, v_pages, state,
            slots, jnp.ones((B,), bool), page_tables,
            lambda q, k, v, kp, vp, layer: attn_fn(
                q, k, v, seq_lens, **skip),
            use_pallas,
            swa_attend=_swa_prefill_attend(spec, impl, seq_lens),
            dsa_attend=(_dsa_prefill_attend(spec, impl, seq_lens, S)
                        if spec.is_dsa else None),
            eva_attend=_eva_prefill_attend(spec, impl),
        )
        return (_logits(params, spec, _last_rows(x, seq_lens), all_heads),
                k_pages, v_pages, state)

    def body(h, lp, win, kp, vp, layer):
        q, k, v, kp, vp = _prefill_qkv_write(
            h, lp, spec, positions, page_tables, kp, vp, layer=layer
        )
        win_arg = win if spec.sliding_window > 0 else None
        with jax.named_scope("attention"):
            if win_arg is None:
                attn = attn_fn(q, k, v, seq_lens)
            else:
                attn = attn_fn(q, k, v, seq_lens, window=win_arg)
        return _finish_layer(h, attn, lp, spec), kp, vp

    x, k_pages, v_pages = _kv_layer_scan(
        params, spec, body, x, k_pages, v_pages
    )
    return (_logits(params, spec, _last_rows(x, seq_lens), all_heads),
            k_pages, v_pages)


def _packed_prompt_pass(params, spec: ModelSpec, tokens, seq_lens, k_pages,
                        v_pages, page_tables, attn_fn, n_rows):
    """The dense stack's whole-prompt pass over the group's REAL rows:
    row ``s < seq_lens[b]`` of sequence ``b`` lies at packed row
    ``offset[b] + s``, the sequences end to end, and the residual stream
    stays packed ``[1, B x S, D]`` through the layer scan.  What is
    position-wise (the norms, the projections and rotary at the rows'
    own positions, ``o_proj``, the feed-forward, the adds) runs over the
    ``n_rows`` (models/hybrid.py ``prompt_rows``: traced) packed rows
    that hold a real row, in blocks (``_by_row_blocks``); a layer unpacks
    only q, k and v, for the page write (whole pages of ``[B, S]``,
    padding to the trash page as ever) and for the attention, which
    stays one launch over ``[B, S]``, rows apart, and packs only the
    attention's result.  A padding row of the group (length 1) costs one
    packed row.  Behind a sequence's last real row ``unpack`` leaves the
    next sequences' rows, which are finite and which nothing reads.
    Returns (each sequence's last real row [B, D], k_pages, v_pages)."""
    from vgate_tpu.models.hybrid import _by_row_blocks, _heads_flat

    B, S = tokens.shape
    offsets = jnp.cumsum(seq_lens) - seq_lens  # offset[b] + S <= B x S

    def pack(t):  # [B, S, ...] -> [1, B x S, ...]; later rows win
        out = jnp.zeros((B * S,) + t.shape[2:], t.dtype)
        for b in range(B):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, t[b], offsets[b], 0)
        return out[None]

    def unpack(t):  # [1, B x S, ...] -> [B, S, ...]
        return jnp.stack([
            jax.lax.dynamic_slice_in_dim(t[0], offsets[b], S, 0)
            for b in range(B)])

    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)
    positions = pack(jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)))
    x = _embed(params, spec, pack(tokens))  # [1, B x S, D]

    def body(h, lp, win, kp, vp, layer):
        with jax.named_scope("qkv"):
            q, k, v = map(unpack, by_rows(
                lambda rows, at: _prefill_qkv(rows, lp, spec, at),
                h, positions))
            kp, vp = _write_whole_pages(
                k, v, spec, page_tables, kp, vp, layer)
        window = {"window": win} if spec.sliding_window > 0 else {}
        with jax.named_scope("attention"):
            attn = attn_fn(q, k, v, seq_lens, **window)
        h = by_rows(lambda rows, attn: _finish_layer(rows, attn, lp, spec),
                    h, pack(_heads_flat(attn)))
        return h, kp, vp

    x, k_pages, v_pages = _kv_layer_scan(
        params, spec, body, x, k_pages, v_pages)
    return _last_rows(unpack(x), seq_lens), k_pages, v_pages


@jax.named_scope("qkv")
def _prefill_qkv_write(
    h, lp, spec: ModelSpec, positions, page_tables, k_pages_l, v_pages_l,
    layer=None, offsets=None,
):
    """Shared prompt-pass front half: norm + qkv projection + rope at the
    given (possibly offset) positions, then write this layer's KV into its
    pages (trash-page-0 absorbs padding).  Pages are head-major
    [KV, P, ps, hd]: the fresh KV transposes to [B, n_pages, KV, ps, hd]
    so each (head, page) lands as one contiguous (ps, hd) tile.  With
    ``layer`` (a traced scalar) the pools carry a leading [L] dim and
    the write is a layer-indexed in-place update (the plain-mesh scan);
    without it they are one layer's slice (the pp stage relay).

    ``offsets`` ([B] int32) switches to the UNALIGNED write used by
    copy-on-write prefix sharing (runtime/radix_cache.py): row ``b``'s
    first token lands at slot ``offsets[b]`` of its first page (the COW
    page, whose head holds the copied shared KV and must not be
    clobbered), so writes become a per-token (page, slot) scatter
    instead of whole-page sets.  ``page_tables`` must then carry one
    extra page column (``S // ps + 1``): an offset start can spill the
    suffix into one more page."""
    B, S = h.shape[:2]
    ps = k_pages_l.shape[-2]
    q, k, v = _prefill_qkv(h, lp, spec, positions)
    if offsets is not None:
        idx = offsets[:, None] + jnp.arange(S)[None, :]  # [B, S] in-suffix
        slot = idx % ps
        pages_bs = jnp.take_along_axis(page_tables, idx // ps, axis=1)
        k_pages_l = kv_write_tokens(
            k_pages_l, pages_bs, slot, pack_rows(k, spec.kv_head_pack),
            layer=layer,
        )
        v_pages_l = kv_write_tokens(
            v_pages_l, pages_bs, slot, pack_rows(v, spec.kv_head_pack),
            layer=layer,
        )
        return q, k, v, k_pages_l, v_pages_l
    return (q, k, v, *_write_whole_pages(
        k, v, spec, page_tables, k_pages_l, v_pages_l, layer))


def _prefill_qkv(h, lp, spec: ModelSpec, positions):
    """Rows [..., S, D] at ``positions`` [..., S] -> q [..., S, H, hd], k
    and v [..., S, KV, hd]: input norm, projections, rope."""
    normed = rms_norm(
        h, lp["input_norm"], spec.rms_eps, spec.unit_offset_norm
    )
    q, k, v = _project_qkv(normed, lp, spec)
    q = apply_rope(q, positions, spec.rope_theta, spec.rope_scaling)
    k = apply_rope(k, positions, spec.rope_theta, spec.rope_scaling)
    return q, k, v


def _write_whole_pages(k, v, spec: ModelSpec, page_tables, k_pages_l,
                       v_pages_l, layer):
    """A prompt's fresh k and v [B, S, KV, hd] into its pages, whole
    pages from the first row on (the pool's rows: ``kv_head_pack`` heads
    side by side, which of k's flat heads is a reshape)."""
    B, S = k.shape[:2]
    ps = k_pages_l.shape[-2]
    n_pages = S // ps
    pt = page_tables[:, :n_pages]
    to_pages = lambda t: jnp.transpose(
        t.reshape(B, n_pages, ps, spec.cache_heads, spec.cache_head_dim),
        (0, 1, 3, 2, 4),
    )  # [B, n_pages, KV, ps, hd]
    k_pages_l = kv_write_pages(k_pages_l, pt, to_pages(k), layer=layer)
    v_pages_l = kv_write_pages(v_pages_l, pt, to_pages(v), layer=layer)
    return k_pages_l, v_pages_l


def _finish_layer(h, attn, lp, spec: ModelSpec):
    """Shared layer back half: o-projection residual + post-norm MLP.

    With ``ffn_sandwich`` (Gemma-2) the post-attention norm applies to the
    attention OUTPUT before the residual add, and the FFN is wrapped in its
    own pre/post norms (sandwich normalization)."""
    attn = attn.reshape(*h.shape[:-1], spec.q_dim)
    uo = spec.unit_offset_norm
    with jax.named_scope("o_proj"):
        attn_out = weighted_einsum(
            "...h,hd->...d", attn, lp["o"]["w"],
            quant_kernel=spec.quant_kernel, int8_native=spec.int8_native,
        )
    if spec.ffn_sandwich:
        attn_out = rms_norm(attn_out, lp["post_norm"], spec.rms_eps, uo)
        h = h + attn_out
        normed2 = rms_norm(h, lp["pre_ffn_norm"], spec.rms_eps, uo)
        mlp_out = rms_norm(
            _mlp(normed2, lp, spec), lp["post_ffn_norm"], spec.rms_eps, uo
        )
        return h + mlp_out
    h = h + attn_out
    normed2 = rms_norm(h, lp["post_norm"], spec.rms_eps, uo)
    return h + _mlp(normed2, lp, spec)


def prefill_layer(
    h, lp, k_pages_l, v_pages_l, *, spec: ModelSpec, seq_lens, page_tables,
    attn_fn, window=None,
):
    """One transformer layer of the prompt pass (shared by the plain scan
    path above and the pipeline-parallel stage scan).  ``window`` is this
    layer's attention window (int32 scalar, 0 = global), threaded only for
    sliding-window families."""
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k, v, k_pages_l, v_pages_l = _prefill_qkv_write(
        h, lp, spec, positions, page_tables, k_pages_l, v_pages_l
    )
    with jax.named_scope("attention"):
        if window is None:
            attn = attn_fn(q, k, v, seq_lens)
        else:
            attn = attn_fn(q, k, v, seq_lens, window=window)
    return _finish_layer(h, attn, lp, spec), k_pages_l, v_pages_l


@jax.named_scope("qkv")
def _decode_qkv(h, lp, spec: ModelSpec, positions):
    """Per-layer decode prologue shared by every decode path (xs/ys
    scan, carry scan, sp shard, pp relay): input norm + qkv projection +
    rope at the step positions.  q [B,H,hd], k/v [B,KV,hd]."""
    normed = rms_norm(
        h, lp["input_norm"], spec.rms_eps, spec.unit_offset_norm
    )
    q, k, v = _project_qkv(normed, lp, spec)
    q = apply_rope(
        q[:, None], positions[:, None], spec.rope_theta,
        spec.rope_scaling,
    )[:, 0]
    k = apply_rope(
        k[:, None], positions[:, None], spec.rope_theta,
        spec.rope_scaling,
    )[:, 0]
    return q, k, v


def decode_layer(
    h, lp, k_pages_l, v_pages_l, *, spec: ModelSpec, positions, page_ids,
    page_off, page_tables, seq_lens, attn_fn, window=None, sp_mesh=None,
):
    """One transformer layer of the decode step (shared by the plain scan
    path below and the pipeline-parallel stage scan,
    parallel/pipeline.py).  With ``sp_mesh`` the KV write and attention
    run sequence-parallel over the sp-sharded page pool
    (parallel/sp_decode.py) — the long-context decode path."""
    q, k, v = _decode_qkv(h, lp, spec, positions)
    if sp_mesh is not None:
        from vgate_tpu.parallel.sp_decode import (
            sp_decode_attention_and_write,
        )

        attn, k_pages_l, v_pages_l = sp_decode_attention_and_write(
            q, k, v, k_pages_l, v_pages_l, page_ids, page_off,
            page_tables, seq_lens, sp_mesh, window=window,
            softcap=spec.attn_softcap, scale=_query_scale(spec),
        )
        return _finish_layer(h, attn, lp, spec), k_pages_l, v_pages_l
    with jax.named_scope("kv_write"):
        k_pages_l = kv_write_tokens(k_pages_l, page_ids, page_off, k)
        v_pages_l = kv_write_tokens(v_pages_l, page_ids, page_off, v)
    with jax.named_scope("attention"):
        if window is None:
            attn = attn_fn(q, k_pages_l, v_pages_l, page_tables, seq_lens)
        else:
            attn = attn_fn(
                q, k_pages_l, v_pages_l, page_tables, seq_lens,
                window=window,
            )
    return _finish_layer(h, attn, lp, spec), k_pages_l, v_pages_l


def decode_attn_inputs(positions, page_tables, active, page_size):
    """Derive the per-slot KV write targets for one decode step; inactive
    slots write the reserved trash page 0."""
    B = positions.shape[0]
    seq_lens = positions + 1
    page_slot = positions // page_size
    page_off = positions % page_size
    page_ids = page_tables[jnp.arange(B), page_slot]  # [B]
    if active is not None:
        page_ids = jnp.where(active, page_ids, 0)
    return seq_lens, page_ids, page_off


def decode_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B] current token per slot
    positions: jnp.ndarray,  # [B] 0-indexed position of `tokens`
    k_pages: jnp.ndarray,  # [L, KV, P, ps, hd] (head-major, kv_cache.py)
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    active: Optional[jnp.ndarray] = None,  # [B] bool; inactive slots write page 0
    use_pallas: bool = False,
    mesh=None,  # pp>1 routes through the pipeline-parallel stage relay
    state=None,  # hybrid specs: the recurrent state, row = slot
    all_heads: bool = False,  # every prediction head's logits (_logits)
    head=None,  # what ends the step instead of _logits (greedy_head)
) -> Tuple[jnp.ndarray, ...]:
    """One continuous-batching decode step: returns (logits [B, V],
    caches); a hybrid spec adds the recurrent state and the expert
    layers' counters (ops/moe.py STAT_NAMES).  ``head(params, spec,
    x)`` takes the last layer's rows ``[B, D]`` in ``_logits``' place,
    and its result the logits' place in what comes back."""
    if head is None:
        head = functools.partial(_logits, all_heads=all_heads)
    impl = decode_attention_impl(spec, use_pallas, mesh)
    if impl == "pp_relay":
        from vgate_tpu.parallel.pipeline import pp_decode_forward

        return pp_decode_forward(
            params, spec, tokens, positions, k_pages, v_pages, page_tables,
            active=active, mesh=mesh, use_pallas=use_pallas, head=head,
        )
    sp_mesh = mesh if impl == "sp_shard" else None
    if sp_mesh is not None:
        # sequence-parallel decode: attention + KV write run per-shard
        # over the sp-sharded page pool (parallel/sp_decode.py)
        ps = k_pages.shape[3]
        seq_lens, page_ids, page_off = decode_attn_inputs(
            positions, page_tables, active, ps
        )
        x = _embed(params, spec, tokens)  # [B, D]
        windows = _layer_windows(spec)

        def sp_layer_fn(h, per_layer):
            lp, win, k_pages_l, v_pages_l = per_layer
            h, k_pages_l, v_pages_l = decode_layer(
                h, lp, k_pages_l, v_pages_l, spec=spec,
                positions=positions, page_ids=page_ids,
                page_off=page_off, page_tables=page_tables,
                seq_lens=seq_lens, attn_fn=None,
                window=win if spec.sliding_window > 0 else None,
                sp_mesh=sp_mesh,
            )
            return h, (k_pages_l, v_pages_l)

        x, (k_pages, v_pages) = jax.lax.scan(
            sp_layer_fn, x, (params["layers"], windows, k_pages, v_pages)
        )
        return head(params, spec, x), k_pages, v_pages
    if impl == "jnp":
        attn_fn = functools.partial(
            paged_decode_attention,
            softcap=spec.attn_softcap,
            scale=_query_scale(spec),
        )
    else:
        # the decode kernel supports window/softcap/scale natively (and
        # skips DMA for pages below the window), so local-attention
        # families ride it too
        from vgate_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas,
        )

        attn_fn = functools.partial(
            paged_decode_attention_pallas,
            softcap=spec.attn_softcap,
            scale=_query_scale(spec),
        )
        if impl == "pallas_tp":
            # params and the pool's kv-head dim are GSPMD-sharded over
            # tp; a pallas_call does NOT partition automatically — it
            # must run per shard via shard_map (parallel/tp_attention.py)
            # or GSPMD would all-gather the pool
            from vgate_tpu.parallel.tp_attention import (
                tp_paged_decode_attention,
            )

            attn_fn = functools.partial(
                tp_paged_decode_attention, attn_fn, mesh
            )
    # a pool of packed rows (two heads of 64 a row): the same launch at
    # (KV / 2, 2 G, 128), each head's own lanes taken of the result
    attn_fn = over_packed_pool(attn_fn, spec)
    ps = page_tokens(k_pages, spec.kv_rows)
    # the rows a step attends to and writes among, as a paged sequence:
    # the sequence's own pages, or an EVA spec's view of them
    attn_tables, attn_rows = page_tables, positions
    if spec.eva_layers:
        from vgate_tpu.ops import eva

        win_pages = state["eva_pages"][:tokens.shape[0]]
        attn_tables, attn_rows = eva.decode_view(
            page_tables, win_pages, positions, spec.eva_window,
            spec.eva_chunk, ps)
    seq_lens, page_ids, page_off = decode_attn_inputs(
        attn_rows, attn_tables, active, ps
    )
    if impl != "jnp" and active is not None:
        # the kernel spends nothing on a row of length 0 (no DMA, no
        # iteration, zeros out); the twin keeps length 1 for such a row,
        # whose mean over nothing would be garbage
        seq_lens = jnp.where(active, seq_lens, 0)

    kernel_writes = decode_kv_write(
        spec, use_pallas, mesh, is_quantized(k_pages)
    ) == "kernel"

    def cache_step(attn_fn, tables, page_ids):
        """``write_attend(q, k, v, kp, vp, layer, window=None)`` over
        the pools that ``tables`` index: the new token's K and V into
        them at ``layer`` and its attention over them: (attention,
        k_pages, v_pages)."""
        def write_attend(q, k, v, kp, vp, layer, window=None):
            if kernel_writes:
                with jax.named_scope("attention"):
                    return attn_fn(
                        q, kp, vp, tables, seq_lens, layer=layer,
                        window=window, k_new=k, v_new=v,
                    )
            with jax.named_scope("kv_write"):
                kp = kv_write_tokens(
                    kp, page_ids, page_off,
                    pack_rows(k, spec.kv_head_pack), layer=layer)
                vp = kv_write_tokens(
                    vp, page_ids, page_off,
                    pack_rows(v, spec.kv_head_pack), layer=layer)
            with jax.named_scope("attention"):
                attn = attn_fn(
                    q, kp, vp, tables, seq_lens, layer=layer,
                    window=window,
                )
            return attn, kp, vp

        return write_attend

    write_attend = cache_step(attn_fn, attn_tables, page_ids)
    x = _embed(params, spec, tokens)  # [B, D]
    if spec.is_mla:
        write_attend = _mla_write_attend(
            spec, impl, kernel_writes, page_tables, seq_lens, page_ids,
            page_off)
    dsa_steps = None
    if spec.is_dsa:
        dsa_steps = _dsa_decode_steps(
            spec, impl, page_tables, seq_lens, page_ids, page_off, ps)
    if spec.is_hybrid:
        from vgate_tpu.models import hybrid

        ring_step = None
        if spec.swa_layers:
            # a window layer's cache step: the same, over the slots'
            # rings (row = slot) through the ring's arithmetic table
            R = hybrid.ring_pages(spec, ps)
            ring_tables = hybrid.ring_tables(
                jnp.arange(tokens.shape[0]), page_tables.shape[1],
                (state["ring_k"].shape[2] - 1) // R, R)
            ring_fn = attn_fn
            if impl == "pallas":
                from vgate_tpu.ops.pallas.paged_attention import (
                    swa_decode_attention_pallas,
                )

                ring_fn = functools.partial(
                    swa_decode_attention_pallas,
                    softcap=spec.attn_softcap, scale=_query_scale(spec))
            ring_step = functools.partial(
                cache_step(
                    ring_fn, ring_tables,
                    decode_attn_inputs(positions, ring_tables, active, ps)[1]),
                window=spec.sliding_window)
        eva_step = None
        if spec.eva_layers:
            # the windows this step's rows close: once, for every layer
            closers = eva.decode_closers(
                page_tables, win_pages, positions, active, spec.eva_window,
                spec.eva_chunk, ps)
            eva_step = lambda kp, vp, lp, layer: eva.decode_close(
                kp, vp, lp["eva_phi"], lp["eva_mu"], layer, closers,
                spec.eva_chunk, spec.head_dim ** -0.5)
        x, k_pages, v_pages, state, stats = hybrid.decode_forward(
            params, spec, x, positions, k_pages, v_pages, state, active,
            write_attend, use_pallas, ring_write_attend=ring_step,
            dsa_steps=dsa_steps, eva_close=eva_step,
        )
        return head(params, spec, x), k_pages, v_pages, state, stats

    # the FULL [L, ...] pools ride the scan carry with layer-indexed
    # in-place updates, and attention reads the pool at layer l directly
    # (Pallas: layer-indexed DMA; jnp: one composed gather)
    def body(h, lp, win, kp, vp, layer):
        q, k, v = _decode_qkv(h, lp, spec, positions)
        attn, kp, vp = write_attend(
            q, k, v, kp, vp, layer,
            window=win if spec.sliding_window > 0 else None,
        )
        return _finish_layer(h, attn, lp, spec), kp, vp

    x, k_pages, v_pages = _kv_layer_scan(
        params, spec, body, x, k_pages, v_pages
    )
    return head(params, spec, x), k_pages, v_pages


def prefill_suffix_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, S] suffix tokens, S a bucket, S % ps == 0
    prefix_lens: jnp.ndarray,  # [B] cached tokens already resident (page-aligned)
    suffix_lens: jnp.ndarray,  # [B] real suffix tokens (<= S)
    k_pages: jnp.ndarray,  # [L, KV, P, ps, hd]
    v_pages: jnp.ndarray,
    suffix_page_tables: jnp.ndarray,  # [B, S // ps (+1 if unaligned)]
    ctx_page_tables: jnp.ndarray,  # [B, ctx_pages] window covering prefix+suffix
    use_pallas: bool = False,  # multitok kernel for the context attention
    mesh=None,  # sp>1 routes write+attention through the sp shard path
    unaligned: bool = False,  # COW prefix sharing: prefix_lens % ps != 0
    state=None,  # hybrid specs: the recurrent state (models/hybrid.py)
    slots=None,  # [B] decode slot of each row
) -> Tuple[jnp.ndarray, ...]:
    """Prompt pass for only the uncached suffix of a prefix-cache hit.

    The first ``prefix_lens`` tokens' KV is already resident in shared
    pages (runtime/kv_cache.py prefix caching) — this writes just the
    suffix KV into its own pages (page-aligned suffixes pack pages from
    offset 0 exactly like a fresh prefill) and attends suffix-queries
    vs the paged context window (ops/attention.py
    paged_suffix_attention, blockwise).  The saved work is the whole
    prefix prompt pass: O(prefix) projections + O(S * prefix) attention
    FLOPs never run.

    ``unaligned`` is the copy-on-write variant (runtime/radix_cache.py):
    ``prefix_lens`` may fall mid-page, the first suffix token writes at
    slot ``prefix_lens % ps`` of the COW page (whose head holds the
    device-copied shared KV), and ``suffix_page_tables`` carries one
    extra page column.  The attention masks are positional already, so
    only the KV write changes (scatter instead of whole-page sets);
    sp > 1 never takes this variant (the engine gates COW off there).
    Returns (last-token logits [B, V], k_pages, v_pages).
    """
    B, S = tokens.shape
    positions = prefix_lens[:, None] + jnp.arange(S)[None, :]  # absolute
    total_lens = prefix_lens + suffix_lens
    offsets = (prefix_lens % page_tokens(k_pages, spec.kv_rows)
               ) if unaligned else None
    x = _embed(params, spec, tokens)  # [B, S, D]

    impl = multitok_attention_impl(
        use_pallas, mesh, rows=S, unaligned=unaligned,
        latent=spec.rows_cache,
        group=packed_group(spec),
    )
    kernels = use_pallas  # below, use_pallas narrows to the multitok kernel
    sp_mesh = mesh if impl == "sp_shard" else None
    if sp_mesh is not None:
        # prefix caching on the sp-sharded pool: per-layer write +
        # blockwise partial attention run per shard, partials LSE-merge
        # over sp (parallel/sp_decode.py sp_suffix_attention_and_write)
        from vgate_tpu.parallel.sp_decode import (
            sp_suffix_attention_and_write,
        )

        windows = _layer_windows(spec)

        def sp_layer_fn(h, per_layer):
            lp, win, kp, vp = per_layer
            normed = rms_norm(
                h, lp["input_norm"], spec.rms_eps, spec.unit_offset_norm
            )
            q, k, v = _project_qkv(normed, lp, spec)
            q = apply_rope(q, positions, spec.rope_theta, spec.rope_scaling)
            k = apply_rope(k, positions, spec.rope_theta, spec.rope_scaling)
            attn, kp, vp = sp_suffix_attention_and_write(
                q, k, v, kp, vp, suffix_page_tables, ctx_page_tables,
                prefix_lens, total_lens, sp_mesh,
                window=win if spec.sliding_window > 0 else None,
                softcap=spec.attn_softcap, scale=_query_scale(spec),
            )
            return _finish_layer(h, attn, lp, spec), (kp, vp)

        x, (k_pages, v_pages) = jax.lax.scan(
            sp_layer_fn, x, (params["layers"], windows, k_pages, v_pages)
        )
        return (_logits(params, spec, _last_rows(x, suffix_lens)),
                k_pages, v_pages)

    use_pallas = impl == "pallas"
    if use_pallas:
        from vgate_tpu.ops.pallas.paged_attention import (
            paged_multitok_attention_pallas,
        )

        multitok_fn = over_packed_pool(paged_multitok_attention_pallas, spec)
    suffix_fn = over_packed_pool(paged_suffix_attention, spec)

    if spec.is_hybrid:
        # a later chunk of a chunked prefill: the rows continue from the
        # slot's recurrent state (zeros where nothing precedes them);
        # prefix-cache hits never get here (the engine turns matching
        # off for a spec with recurrent layers)
        assert not unaligned, "hybrid specs have no copy-on-write prefix"
        from vgate_tpu.models import hybrid

        ps = page_tokens(k_pages, spec.kv_rows)

        def attend(q, k, v, kp, vp, layer):
            if spec.rows_cache:
                # K and V expanded from the context's latent rows
                # (hybrid.py _mla_prompt), or gathered from a pool of K
                # over V (_kv_dsa_prompt): the blockwise jnp attention
                # with the rows' offset.  A Pallas kernel for query rows
                # against such a prefix is not written yet
                return flash_prefill_attention(
                    q, k, v, total_lens, q_offset=prefix_lens,
                    scale=_query_scale(spec),
                    block_k=256 if k.shape[1] % 256 == 0 else ps,
                )
            if use_pallas:
                return multitok_fn(
                    q, kp, vp, ctx_page_tables, prefix_lens, suffix_lens,
                    layer=layer, scale=_query_scale(spec),
                )
            return suffix_fn(
                q, kp, vp, ctx_page_tables, prefix_lens, total_lens,
                scale=_query_scale(spec), layer=layer,
            )

        x, k_pages, v_pages, state = hybrid.prompt_forward(
            params, spec, x, suffix_lens, positions, k_pages, v_pages,
            state, slots, prefix_lens == 0, suffix_page_tables, attend,
            kernels, ctx_tables=(
                ctx_page_tables if spec.rows_cache or spec.eva_layers
                else None),
            prefix_lens=(prefix_lens if spec.swa_layers or spec.eva_layers
                         else None),
            # a later chunk's or a suffix's rows under a selection: the
            # jnp twin over the gathered context (no kernel yet)
            dsa_attend=(_dsa_prefill_attend(spec, "jnp", total_lens, S)
                        if spec.is_dsa else None),
            total_lens=total_lens,
        )
        return (_logits(params, spec, _last_rows(x, suffix_lens)),
                k_pages, v_pages, state)

    # both the suffix write AND the paged context read are layer-indexed
    # on the full [L, ...] buffers — no per-layer pool slice ever
    # materializes (the chunked-prefill hot path runs this once per chunk)
    def body(h, lp, win, kp, vp, layer):
        q, _k, _v, kp, vp = _prefill_qkv_write(
            h, lp, spec, positions, suffix_page_tables, kp, vp,
            layer=layer, offsets=offsets,
        )
        window = win if spec.sliding_window > 0 else None
        with jax.named_scope("attention"):
            if use_pallas:
                # the multitok kernel IS suffix attention: S query rows
                # starting at an arbitrary position, causal within the
                # rows, live-page DMA only (the suffix KV was just
                # written)
                attn = multitok_fn(
                    q, kp, vp, ctx_page_tables, prefix_lens, suffix_lens,
                    window=window, layer=layer,
                    softcap=spec.attn_softcap, scale=_query_scale(spec),
                )
            else:
                attn = suffix_fn(
                    q, kp, vp, ctx_page_tables, prefix_lens,
                    total_lens, softcap=spec.attn_softcap,
                    window=window, scale=_query_scale(spec), layer=layer,
                )
        return _finish_layer(h, attn, lp, spec), kp, vp

    x, k_pages, v_pages = _kv_layer_scan(
        params, spec, body, x, k_pages, v_pages
    )
    return _logits(params, spec, _last_rows(x, suffix_lens)), k_pages, v_pages


def spec_verify_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, S]: [current, draft_1, ..., draft_{S-1}]
    positions0: jnp.ndarray,  # [B] global position of tokens[:, 0]
    input_lens: jnp.ndarray,  # [B] 1 + real drafts this row (<= S)
    k_pages: jnp.ndarray,  # [L, KV, P, ps, hd]
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    active: Optional[jnp.ndarray] = None,  # [B] bool
    use_pallas: bool = False,
    mesh=None,  # sp>1 routes write+attention through the sp shard path
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Speculative-decoding verification: score ``S`` candidate tokens per
    slot in one pass over the paged KV cache (runtime/speculative.py).

    A multi-token decode step: KV for all candidates is written at
    positions ``p..p+S-1`` (invalid rows and inactive slots scatter to
    trash page 0), then each candidate attends the context window — via
    the multi-token Pallas kernel (ops/pallas/paged_attention.py
    paged_multitok_attention_pallas: live-page DMA only) when
    ``use_pallas``, the blockwise jnp suffix attention otherwise (unlike
    the page-aligned prefix-cache suffix pass, ``positions0`` here is
    arbitrary, which the per-token scatter handles).  Tokens past the
    accepted prefix leave garbage KV beyond the sequence's new length;
    later steps mask it via ``seq_lens`` and overwrite it in place — the
    paged-KV form of "no rollback needed".  Returns (logits [B, S, V],
    k_pages, v_pages).
    """
    if spec.is_hybrid:
        raise NotImplementedError(
            "speculative verification needs a recurrent state that can "
            "roll back; the engine refuses it for hybrid specs"
        )
    B, S = tokens.shape
    ps = k_pages.shape[3]
    width = page_tables.shape[1]
    positions = positions0[:, None] + jnp.arange(S)[None, :]  # [B, S]
    # overshoot rows stay in-bounds (same discipline as decode's
    # max_position clamp); their writes are trashed anyway
    positions = jnp.minimum(positions, width * ps - 1)
    valid = jnp.arange(S)[None, :] < input_lens[:, None]  # [B, S]
    write_ok = valid if active is None else (valid & active[:, None])
    page_slot = positions // ps
    page_off = positions % ps
    page_ids = jnp.take_along_axis(page_tables, page_slot, axis=1)
    page_ids = jnp.where(write_ok, page_ids, 0)  # trash page 0
    total_lens = positions0 + input_lens
    x = _embed(params, spec, tokens)  # [B, S, D]

    impl = multitok_attention_impl(use_pallas, mesh, rows=S,
                                   group=packed_group(spec))
    sp_mesh = mesh if impl == "sp_shard" else None
    use_pallas = impl == "pallas"
    if sp_mesh is not None:
        # speculative verify on an sp-sharded pool: per-token scatter
        # writes + blockwise partials per shard, LSE merge over sp
        # (parallel/sp_decode.py sp_multitok_attention_and_write; the
        # r3 spec x sp gate is gone, r4)
        from vgate_tpu.parallel.sp_decode import (
            sp_multitok_attention_and_write,
        )

        windows = _layer_windows(spec)

        def sp_layer_fn(h, per_layer):
            lp, win, kp, vp = per_layer
            normed = rms_norm(
                h, lp["input_norm"], spec.rms_eps, spec.unit_offset_norm
            )
            q, k, v = _project_qkv(normed, lp, spec)
            q = apply_rope(q, positions, spec.rope_theta, spec.rope_scaling)
            k = apply_rope(k, positions, spec.rope_theta, spec.rope_scaling)
            attn, kp, vp = sp_multitok_attention_and_write(
                q, k, v, kp, vp, page_ids, page_off, page_tables,
                positions0, total_lens, sp_mesh,
                window=win if spec.sliding_window > 0 else None,
                softcap=spec.attn_softcap, scale=_query_scale(spec),
            )
            return _finish_layer(h, attn, lp, spec), (kp, vp)

        x, (k_pages, v_pages) = jax.lax.scan(
            sp_layer_fn, x, (params["layers"], windows, k_pages, v_pages)
        )
        return _logits(params, spec, x), k_pages, v_pages

    if use_pallas:
        from vgate_tpu.ops.pallas.paged_attention import (
            paged_multitok_attention_pallas,
        )

        multitok_fn = over_packed_pool(paged_multitok_attention_pallas, spec)
    suffix_fn = over_packed_pool(paged_suffix_attention, spec)

    def body(h, lp, win, kp, vp, layer):
        """One verify layer against the full stacked pools."""
        normed = rms_norm(
            h, lp["input_norm"], spec.rms_eps, spec.unit_offset_norm
        )
        q, k, v = _project_qkv(normed, lp, spec)
        q = apply_rope(q, positions, spec.rope_theta, spec.rope_scaling)
        k = apply_rope(k, positions, spec.rope_theta, spec.rope_scaling)
        kp = kv_write_tokens(kp, page_ids, page_off,
                             pack_rows(k, spec.kv_head_pack), layer=layer)
        vp = kv_write_tokens(vp, page_ids, page_off,
                             pack_rows(v, spec.kv_head_pack), layer=layer)
        window = win if spec.sliding_window > 0 else None
        with jax.named_scope("attention"):
            if use_pallas:
                attn = multitok_fn(
                    q, kp, vp, page_tables, positions0,
                    input_lens, window=window, layer=layer,
                    softcap=spec.attn_softcap, scale=_query_scale(spec),
                )
            else:
                attn = suffix_fn(
                    q, kp, vp, page_tables, positions0,
                    total_lens, softcap=spec.attn_softcap, window=window,
                    scale=_query_scale(spec), layer=layer,
                )
        return _finish_layer(h, attn, lp, spec), kp, vp

    x, k_pages, v_pages = _kv_layer_scan(
        params, spec, body, x, k_pages, v_pages
    )
    return _logits(params, spec, x), k_pages, v_pages
