"""Plain reference: LFM2-24B-A2B (LiquidAI, ``model_type`` ``lfm2_moe``:
gated short convolutions beside grouped-query attention, two leading
dense layers, then sigmoid-routed experts with a selection bias and no
shared expert) in straightforward ``jax.numpy`` float32 -- no kernels,
no cache, no convolution tail, no batching.  It shares no code with
``vgate_tpu/`` and no mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.lfm2_moe CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config``.  ``N(x; w) = x / sqrt(mean(x^2) + norm_eps) *
w``; no bias anywhere.  One sequence at a time, rows ``0 .. S - 1``:

* every layer ``l``: ``h <- h + M_l(N(h; operator_norm))``, then ``h <-
  h + F_l(N(h; ffn_norm))``; after the last, ``logits = N(h;
  embedding_norm) E^T`` with ``E`` the embedding (tied).
* ``M``, ``layer_types[l] == "conv"``: ``[B | C | X] = u W_in`` (``D ->
  3 D``, in that order); ``z = B * X``; ``c_t = sum_{j = 0 .. L - 1}
  w[:, j] * z_{t - (L - 1) + j}`` with ``L = conv_L_cache`` taps a
  channel and ``z`` zero before row 0 (the sum over ``L`` shifted copies
  of ``z``, written out), no activation; ``y = (C * c) W_out``.
* ``M``, ``"full_attention"``: ``q = u W_q`` -> heads x 64, ``k = u
  W_k``, ``v = u W_v`` -> KV heads x 64, ``heads / KV`` query heads a KV
  head; ``N`` over each head's 64 values of q and of k (one weight of
  64 for q, one for k) BEFORE the rotation; rotate-half rotary over all
  64, theta from ``rope_parameters``; per head, the full ``S x S``
  scores ``q k^T / sqrt(64)`` under a causal mask, a softmax, times v;
  the heads side by side times ``W_o``.
* ``F``, ``l < num_dense_layers``: ``W_2(silu(u W_1) * u W_3)`` of width
  ``intermediate_size``.
* ``F``, the others: ``s = sigmoid(u W_g)`` over the router's full
  width (``router_width``; float32); the ``num_experts_per_tok`` largest
  of ``s + b`` (``use_expert_bias``: ``b`` moves the CHOICE only);
  weights ``s_i / (sum of the chosen s + 1e-6)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; the weighted sum of the chosen
  experts' ``W_2(silu(u W_1) * u W_3)`` of width
  ``moe_intermediate_size``, over those that are HELD (``num_experts``
  of them from ``first_expert``: what the absent ones would add is left
  out, model-configs guide section 4).  No shared expert.

What the row's ``config`` does not say, and this file assumes with the
configuration file's ``assumed`` list: tied embeddings; the split order
``B, C, X`` and the tap order (``w[:, L - 1]`` on the current row, the
torch ``conv1d`` layout); the router in float32; the ``1e-6``; the
selection bias drawn N(0, 0.02) and not trained; the held share.

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_conv_layers``): the embedding from key 8 of ``split(PRNGKey(seed),
16)``; the layers' tensors from ``split(fold_in(PRNGKey(seed), 47),
32)``, tensor ``j`` of layer ``i`` (its index in the whole stack) from
``fold_in(key j, i)``, normal x 0.02 cast to the served dtype -- but the
convolution's input projection, x ``hidden^-0.5``, and its taps, x 0.5
-- the selection bias float32; norm weights at one.  The recipe, not
the code, is shared.  Arithmetic is float32 at highest precision on the
served-dtype weights, one layer at a time and one expert at a time.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# which of the 32 split keys draws which tensor
KEYS = {"in_proj": 0, "conv": 1, "out_proj": 2, "q": 4, "k": 5, "v": 6,
        "o": 7, "w1_dense": 8, "w3_dense": 9, "w2_dense": 10, "router": 11,
        "w1": 12, "w3": 13, "w2": 14, "router_bias": 15}
EXPERTS = ("w1", "w3", "w2")


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "D": D, "V": cfg["vocab_size"], "H": H,
        "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or D // H,
        "F": cfg["intermediate_size"], "L": cfg["conv_L_cache"],
        "held": cfg["num_experts"],
        "R": cfg.get("router_width") or cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "top": cfg["num_experts_per_tok"],
        "Fe": cfg["moe_intermediate_size"],
    }


def is_conv(cfg: Dict[str, Any], i: int) -> bool:
    return cfg["layer_types"][i] == "conv"


def is_dense(cfg: Dict[str, Any], i: int) -> bool:
    return i < cfg.get("num_dense_layers", 0)


# ----------------------------------------------------------- the weights

def layer_tensors(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    """name -> (shape, the draw's scale) of layer ``i``'s tensors."""
    z = dims(cfg)
    D, H, KV, hd = z["D"], z["H"], z["KV"], z["hd"]
    if is_conv(cfg, i):
        out = {"in_proj": ((D, 3 * D), D ** -0.5),
               "conv": ((D, z["L"]), 0.5), "out_proj": ((D, D), 0.02)}
    else:
        out = {"q": ((D, H * hd), 0.02), "k": ((D, KV * hd), 0.02),
               "v": ((D, KV * hd), 0.02), "o": ((H * hd, D), 0.02)}
    if is_dense(cfg, i):
        out.update({"w1_dense": ((D, z["F"]), 0.02),
                    "w3_dense": ((D, z["F"]), 0.02),
                    "w2_dense": ((z["F"], D), 0.02)})
    else:
        E, Fe = z["held"], z["Fe"]
        out.update({"router": ((D, z["R"]), 0.02),
                    "router_bias": ((z["R"],), 0.02),
                    "w1": ((E, D, Fe), 0.02), "w3": ((E, D, Fe), 0.02),
                    "w2": ((E, Fe, D), 0.02)})
    return out


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype=jnp.bfloat16
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors by the program's recipe."""
    ck = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 47), 32)
    out = {}
    for name, (shape, scale) in layer_tensors(cfg, i).items():
        t = jax.random.normal(
            jax.random.fold_in(ck[KEYS[name]], i), shape, F32) * scale
        out[name] = t if name == "router_bias" else t.astype(dtype)
    return out


def draw_embedding(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
                   ) -> jax.Array:
    z = dims(cfg)
    key = jax.random.split(jax.random.PRNGKey(seed), 16)[8]
    return (jax.random.normal(key, (z["V"], z["D"]), F32) * 0.02
            ).astype(dtype)


# ------------------------------------------------------ the mathematics

def rms(x: jax.Array, eps: float, w: Optional[jax.Array] = None
        ) -> jax.Array:
    """N(x; w); w = 1 where the weights carry none."""
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if w is None else y * w


def short_conv(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
               ) -> jax.Array:
    """The gated short convolution on the normed rows u [S, D]."""
    S, L = u.shape[0], cfg["conv_L_cache"]
    b, c, x = jnp.split(u @ w["in_proj"], 3, axis=-1)
    z = b * x
    # row t of copy j is z[t - (L - 1) + j], zero before the sequence
    padded = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), F32), z])
    taps = sum(w["conv"][:, j] * padded[j:j + S] for j in range(L))
    return (c * taps) @ w["out_proj"]


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half of x [S, heads, 64] at rows' own positions."""
    S, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(S, dtype=F32)[:, None] * freq  # [S, d / 2]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
              ) -> jax.Array:
    """Grouped-query attention on the normed rows u [S, D]: a head at a
    time, full scores under the causal mask."""
    z, eps = dims(cfg), cfg["norm_eps"]
    S, H, KV, hd = u.shape[0], z["H"], z["KV"], z["hd"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = rotary(rms((u @ w["q"]).reshape(S, H, hd), eps, w.get("q_norm")),
               theta)
    k = rotary(rms((u @ w["k"]).reshape(S, KV, hd), eps, w.get("k_norm")),
               theta)
    v = (u @ w["v"]).reshape(S, KV, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    heads = []
    for h in range(H):
        g = h // (H // KV)  # the KV head this query head reads
        scores = (q[:, h] @ k[:, g].T) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        heads.append(p @ v[:, g])
    return jnp.concatenate(heads, axis=-1) @ w["o"]


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def choose(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(the chosen experts [S, top] among the router's width, their
    weights [S, top])."""
    s = jax.nn.sigmoid(u @ w["router"])
    picked = jnp.argsort(-(s + w["router_bias"]), axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    weight = jnp.take_along_axis(s, picked, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    return np.asarray(picked), np.asarray(
        weight * cfg.get("routed_scaling_factor", 1))


def experts(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
            first: Optional[int] = None, count: Optional[int] = None
            ) -> jax.Array:
    """The held experts' part of the routed sum on u [S, D]: ``count``
    experts from ``first`` of the router's width (the configuration's
    share by default), each applied to the rows that chose it.  The
    experts' stacks may be in the served dtype: one expert is made
    float32 at a time."""
    z = dims(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    picked, weight = choose(u, w, cfg)
    out = jnp.zeros_like(u)
    for e in range(count):
        mine = picked == first + e  # [S, top]
        rows = np.flatnonzero(mine.any(axis=1))
        if rows.size == 0:
            continue
        share = (weight * mine).sum(axis=1)[rows]
        # up to a power of two with rows of weight 0 (they add 0.0 to
        # row 0): a few shapes compile, not one an expert
        pad = (1 << int(rows.size - 1).bit_length()) - rows.size
        rows, share = np.pad(rows, (0, pad)), np.pad(share, (0, pad))
        y = swiglu(u[rows], *(w[n][e].astype(F32) for n in EXPERTS))
        out = out.at[rows].add(jnp.asarray(share)[:, None] * y)
    return out


def layer(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          i: int) -> jax.Array:
    eps = cfg["norm_eps"]
    u = rms(h, eps, w.get("operator_norm"))
    h = h + (short_conv(u, w, cfg) if is_conv(cfg, i)
             else attention(u, w, cfg))
    u = rms(h, eps, w.get("ffn_norm"))
    if is_dense(cfg, i):
        return h + swiglu(u, w["w1_dense"], w["w3_dense"], w["w2_dense"])
    return h + experts(u, w, cfg)


def f32_but_experts(lw: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """A layer's tensors in float32, the experts' stacks as they are."""
    return {k: (v if k in EXPERTS else v.astype(F32)) for k, v in lw.items()}


def hidden_states(cfg: Dict[str, Any], seed: int, dtype, embed: jax.Array,
                  sequences: List[List[int]],
                  layers: Optional[List[Dict[str, jax.Array]]] = None
                  ) -> List[jax.Array]:
    """The final norm's inputs [S, D] of every sequence: the whole
    stack, one layer's weights drawn (or taken from ``layers``) at a
    time."""
    hs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    for i in range(cfg["num_hidden_layers"]):
        lw = draw_layer(cfg, seed, i, dtype) if layers is None else layers[i]
        w = f32_but_experts(lw)
        hs = [layer(h, w, cfg, i) for h in hs]
    return hs


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int],
             weights: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``.
    ``weights`` ({"embed", "layers", "embedding_norm"?}) replaces the
    draw (a test's own tensors)."""
    with jax.default_matmul_precision("highest"):
        embed = (draw_embedding(cfg, seed, dtype) if weights is None
                 else weights["embed"])
        fw = None if weights is None else weights.get("embedding_norm")
        head = embed.astype(F32).T
        hs = hidden_states(cfg, seed, dtype, embed, sequences,
                           None if weights is None else weights["layers"])
        out = []
        for h, s, f in zip(hs, sequences, first):
            rows = rms(h[f - 1: len(s) - 1], cfg["norm_eps"],
                       None if fw is None else fw.astype(F32))
            out.append(np.asarray(jax.nn.log_softmax(rows @ head, axis=-1)))
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
