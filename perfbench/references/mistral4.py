"""Plain reference: the Mistral-Small-4 language model (``model_type``
``mistral4``: multi-head latent attention, YaRN rotary, a softmax-routed
expert layer with an ungated shared expert) in straightforward
``jax.numpy`` float32 -- no kernels, no cache, no batching, attention in
its NON-absorbed form for every position as one masked softmax, every
held expert applied to the tokens that chose it.  It shares no code with
``vgate_tpu/`` and no mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.mistral4 CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config`` and the module structure of ``transformers``'
DeepSeek-V3 attention as the writer knows it (no network here; each
point the config does not itself state is listed under ``assumed`` in
the configuration file).  eps = ``rms_norm_eps``; ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``, the PLAIN weight.  No biases anywhere.

* Every layer: ``h <- h + MLA(N(h; w_in))`` then ``h <- h + MoE(N(h;
  w_post))`` (``first_k_dense_replace`` 0); then ``N(h; w_f)`` and an
  untied head.  The vision encoder is left out.
* Queries: ``c_q = N(x W_qa; w_qa)`` (hidden -> ``q_lora_rank``); ``q =
  c_q W_qb`` -> heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim``),
  per head ``[q_nope | q_rope]``.
* Latent: ``x W_kva`` (hidden -> ``kv_lora_rank`` +
  ``qk_rope_head_dim``) split ``[c | k_r]``; ``c_kv = N(c; w_kva)``;
  ``k_rope = R(k_r, pos)``, ONE rotary key shared by all heads.
* ``R``: rotate-half on all rotary dimensions, YaRN inverse frequencies
  (``rope_parameters``: per frequency a linear ramp between ``f /
  factor`` and ``f`` by the rotations it makes in
  ``original_max_position_embeddings`` positions, the ramp's ends
  rounded down and up); the cos / sin carry NO attention factor
  (``mscale`` = ``mscale_all_dim``: ratio 1).  The weights are held
  de-interleaved (``rope_interleave`` is undone at load), so this file
  and the program rotate halves.
* Scores: ``k_h = [c_kv W_uk,h | k_rope]``, ``v_h = c_kv W_uv,h``,
  ``W_kvb`` [``kv_lora_rank``, heads x (nope + v)] split per head; ``s =
  sigma gamma(pos_q) (q . k)``, ``sigma = (nope + rope)^-0.5 m^2``, ``m
  = 0.1 mscale_all_dim ln(factor) + 1``; ``gamma(pos) = 1 +
  llama_4_scaling_beta ln(1 + floor(pos / original_max))``; causal
  softmax; ``out = [o_1 .. o_H] W_o``.
* Experts: ``p = softmax(x W_r)`` over the router's full width; top
  ``num_experts_per_tok``; ``w <- w / (sum w + 1e-20)``
  (``norm_topk_prob``), ``x routed_scaling_factor``.  ``E(x) = (silu(x
  W_g) * x W_u) W_d``.  ``out = sum_e w_e E_e(x)`` over the chosen
  experts that are HELD (``n_routed_experts`` of them from
  ``first_expert``: what the absent ones would add is left out,
  model-configs guide section 4) ``+ S(x)``, ``S`` one SwiGLU of width
  ``n_shared_experts x moe_intermediate_size`` with no gate.

Weights.  ``draw_weights`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py _init_mla_layers``):
embedding and head from keys 8 and 9 of ``split(PRNGKey(seed), 16)``;
the layers' tensors from ``split(fold_in(PRNGKey(seed), 33), 32)``,
tensor ``j`` of layer ``i`` from ``fold_in(key j, i)``, normal x 0.02
cast to the served dtype, but ``q_b`` and ``kv_b`` x 0.05; norm weights
at one.  The recipe, not the code, is shared.  Arithmetic is float32 at
highest precision on the served-dtype weights, one layer at a time and
one expert at a time, so that 3.7 B parameters in float32 never stand in
memory at once.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# which of the 32 split keys draws which tensor
KEYS = {"q_a": 0, "q_b": 1, "kv_a": 2, "kv_b": 3, "o": 4, "router": 5,
        "gate": 6, "up": 7, "down": 8, "shared_gate": 9, "shared_up": 10,
        "shared_down": 11}
WIDE = ("q_b", "kv_b")  # drawn x 0.05


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "ql": cfg["q_lora_rank"],
        "kl": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "E": cfg["n_routed_experts"],
        "R": cfg.get("router_width") or cfg["n_routed_experts"],
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"], "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg.get("n_shared_experts", 0) * cfg["moe_intermediate_size"],
    }


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int]) -> Dict[str, tuple]:
    D, H = z["D"], z["H"]
    out = {
        "q_a": (D, z["ql"]), "q_b": (z["ql"], H * (z["nope"] + z["rope"])),
        "kv_a": (D, z["kl"] + z["rope"]),
        "kv_b": (z["kl"], H, z["nope"] + z["vd"]), "o": (H * z["vd"], D),
        "router": (D, z["R"]), "gate": (z["E"], D, z["Fe"]),
        "up": (z["E"], D, z["Fe"]), "down": (z["E"], z["Fe"], D),
    }
    if z["Fs"]:
        out.update({"shared_gate": (D, z["Fs"]), "shared_up": (D, z["Fs"]),
                    "shared_down": (z["Fs"], D)})
    return out


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype=jnp.bfloat16
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors by the program's recipe."""
    mk = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 33), 32)
    return {
        name: (jax.random.normal(jax.random.fold_in(mk[KEYS[name]], i),
                                 shape, F32)
               * (0.05 if name in WIDE else 0.02)).astype(dtype)
        for name, shape in layer_shapes(sizes(cfg)).items()
    }


def draw_ends(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
              ) -> Dict[str, jax.Array]:
    z = sizes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    normal = lambda k, shape: (
        jax.random.normal(k, shape, F32) * 0.02).astype(dtype)
    return {"embed": normal(keys[8], (z["V"], z["D"])),
            "lm_head": normal(keys[9], (z["D"], z["V"]))}


# ------------------------------------------------------ the mathematics

def norm(x: jax.Array, eps: float, w: Optional[jax.Array] = None
         ) -> jax.Array:
    """x / rms(x) * w; w = 1 (identity) when the weights have none."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * w


def yarn_inv_freq(dim: int, rp: Dict[str, Any]) -> jax.Array:
    """Inverse frequencies [dim / 2] under ``rope_parameters``."""
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = rp["original_max_position_embeddings"]
    freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def rotate(x: jax.Array, pos: jax.Array, rp: Dict[str, Any]) -> jax.Array:
    """Rotate-half of x [S, ..., dim] at positions pos [S]."""
    dim = x.shape[-1]
    angle = pos.astype(F32)[:, None] * yarn_inv_freq(dim, rp)  # [S, dim/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    rp = cfg["rope_parameters"]
    sigma = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rp["factor"] > 1 and rp.get("mscale_all_dim"):
        m = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0
        sigma *= m * m
    return sigma


def position_scale(pos: jax.Array, rp: Dict[str, Any]) -> jax.Array:
    """gamma(pos) = 1 + beta ln(1 + floor(pos / original maximum))."""
    steps = pos // rp["original_max_position_embeddings"]
    return 1.0 + rp.get("llama_4_scaling_beta", 0.0) * jnp.log1p(
        steps.astype(F32))


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
              pos: Optional[jax.Array] = None) -> jax.Array:
    """x: [S, D], the normed rows; the NON-absorbed form."""
    z, rp = sizes(cfg), cfg["rope_parameters"]
    S, H, nope, kl = x.shape[0], z["H"], z["nope"], z["kl"]
    pos = jnp.arange(S) if pos is None else pos
    cq = norm(x @ w["q_a"], cfg["rms_norm_eps"], w.get("q_a_norm"))
    q = (cq @ w["q_b"]).reshape(S, H, nope + z["rope"])
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], pos, rp)], -1)
    kv = x @ w["kv_a"]
    c_kv = norm(kv[:, :kl], cfg["rms_norm_eps"], w.get("kv_a_norm"))
    k_rope = rotate(kv[:, kl:], pos, rp)  # [S, rope], one for all heads
    kv_b = w["kv_b"].reshape(kl, H, nope + z["vd"])
    k_nope = jnp.einsum("tk,khn->thn", c_kv, kv_b[..., :nope])
    v = jnp.einsum("tk,khv->thv", c_kv, kv_b[..., nope:])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (S, H, z["rope"]))], -1)
    scores = jnp.einsum("shd,thd->hst", q, k) * softmax_scale(cfg)
    scores = scores * position_scale(pos, rp)[None, :, None]
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thv->shv", jax.nn.softmax(scores, -1), v)
    return attn.reshape(S, H * z["vd"]) @ w["o"]


def route(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(chosen experts [S, K] among the router's width, their weights)."""
    probs = jax.nn.softmax(x @ w["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return np.asarray(idx), np.asarray(
        vals * cfg.get("routed_scaling_factor", 1))


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
        shared: bool = True) -> jax.Array:
    """x: [S, D].  The held experts' part of the routed sum plus the
    shared expert (``shared`` False leaves it out: a test adds the
    shares of several chips and counts it once).  The experts' stacks
    may be in the served dtype: one expert is made float32 at a time."""
    z = sizes(cfg)
    idx, vals = route(x, w, cfg)
    out = jnp.zeros_like(x)
    for e in range(z["E"]):  # every held expert, its own tokens
        chose = idx == z["first"] + e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = jnp.asarray((vals * chose).sum(axis=1)[rows])
        y = swiglu(x[rows], *(w[n][e].astype(F32)
                              for n in ("gate", "up", "down")))
        out = out.at[rows].add(weight[:, None] * y)
    if shared and z["Fs"]:
        out = out + swiglu(x, *(w[n].astype(F32) for n in (
            "shared_gate", "shared_up", "shared_down")))
    return out


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          pos: Optional[jax.Array] = None) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(norm(x, eps, w.get("input_norm")), w, cfg, pos)
    return x + moe(norm(x, eps, w.get("post_norm")), w, cfg)


def f32_but_experts(lw: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """A layer's tensors in float32, the experts' stacks as they are."""
    return {k: (v if k in ("gate", "up", "down") else v.astype(F32))
            for k, v in lw.items()}


def hidden_states(cfg: Dict[str, Any], seed: int, dtype,
                  embed: jax.Array, sequences: List[List[int]],
                  layers: Optional[List[Dict[str, jax.Array]]] = None
                  ) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's weights drawn (or taken from ``layers``) at a time."""
    xs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    for i in range(cfg["num_hidden_layers"]):
        lw = draw_layer(cfg, seed, i, dtype) if layers is None else layers[i]
        w = f32_but_experts(lw)
        xs = [layer(x, w, cfg) for x in xs]
    return xs


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int],
             weights: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``.
    ``weights`` ({"embed", "lm_head", "layers", "final_norm"?}) replaces
    the draw (a test's checkpoint)."""
    with jax.default_matmul_precision("highest"):
        ends = weights or draw_ends(cfg, seed, dtype)
        head = ends["lm_head"].astype(F32)
        fw = ends.get("final_norm")
        out = []
        xs = hidden_states(cfg, seed, dtype, ends["embed"], sequences,
                           None if weights is None else weights["layers"])
        for x, s, f in zip(xs, sequences, first):
            h = norm(x[f - 1: len(s) - 1], cfg["rms_norm_eps"],
                     None if fw is None else fw.astype(F32))
            out.append(np.asarray(jax.nn.log_softmax(h @ head, axis=-1)))
    return out


def main(argv: List[str]) -> int:
    config_path, job_path, out_path = argv
    with open(config_path) as fh:
        cfg = json.load(fh)
    with open(job_path) as fh:
        job = json.load(fh)
    dtype = (jnp.float32 if cfg.get("torch_dtype") == "float32"
             else jnp.bfloat16)  # the type the server holds them in
    lps = logprobs(cfg, int(job["weights_seed"]), dtype, job["sequences"],
                   job["first"])
    result = [
        [[float(lp[pos, tid]) for tid in ids]
         for pos, ids in enumerate(seq_ids)]
        for lp, seq_ids in zip(lps, job["top_ids"])
    ]
    with open(out_path, "w") as fh:
        json.dump({"logprobs": result}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
