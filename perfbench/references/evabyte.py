"""Plain reference: EvaByte (``model_type`` ``evabyte``: a byte-level
language model whose every layer is EVA attention, exact inside a window
and through learned chunk summaries beyond it, in a float32 residual
stream, with eight prediction heads) in straightforward ``jax.numpy``
float32 -- no kernels, no cache, no window buffer, no batching: every
layer's attention is ONE softmax over full scores against every key of
the sequence AND every chunk's summary, under two explicit masks.  It
shares no code with ``vgate_tpu/`` and no mathematics with another
family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.evabyte CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics.  ``W`` =
``window_size``, ``c`` = ``chunk_size``, ``hd`` = ``hidden_size /
num_attention_heads``, ``s = hd^-0.5``, eps = ``rms_norm_eps``; ``N(x;
g) = x / sqrt(mean(x^2) + eps) * g``.  No biases (``attention_bias``
false).  Every layer alike:

1. ``a = N(x; 1 + w_in)``: the norm's weight is ``1 + w``
   (``norm_add_unit_offset``).
2. ``q, k, v = a W_q, a W_k, a W_v``, heads x ``hd`` (``num_key_value_heads``
   = ``num_attention_heads``: no grouping); rotate-half rotary on all
   ``hd`` dimensions of q and k at the absolute position (``rope_theta``,
   no scaling).
3. Chunk ``j`` is positions ``c j .. c j + c - 1``.  Per head ``h`` with
   two learned vectors ``phi_h``, ``mu_h``: ``alpha_{j,i} = softmax_{i in
   chunk j}(s k_i . phi_h)``, ``k~_j = sum_i alpha_{j,i} k_i + mu_h``,
   ``v~_j = sum_i alpha_{j,i} v_i``.
4. A query at ``t`` lies in window ``w = t // W``.  It attends EXACTLY
   to ``E(t) = {i : w W <= i <= t}`` and, through the summaries, to every
   chunk of every CLOSED window, ``C(t) = {j : j < (W / c) w}``, in one
   softmax: ``o_t = (sum_E e^{s q.k_i} v_i + sum_C e^{s q.k~_j} v~_j) /
   (sum_E e^{s q.k_i} + sum_C e^{s q.k~_j})``.  A window is whole
   chunks, so only complete chunks are ever read.
5. ``x <- x + o W_o`` (float32, ``fp32_skip_add``); ``m = N(x; 1 +
   w_post)``; ``x <- x + (silu(m W_gate) * m W_up) W_down``.
6. After the last layer ``N(x; 1 + w_f)`` and an untied head of
   ``num_pred_heads`` x ``vocab_size`` columns, float32 logits
   (``fp32_logits``): head ``p`` scores the byte at ``t + 1 + p``.
   Serving samples from head 0; ``logprobs`` is head 0's.

Where each line comes from: items 1, 2, 5, 6 and the sizes are the
catalog row's ``config``.  The form of 4 is the published EVA attention
(Zheng, Yuan, Wang, Kong, "Efficient Attention via Control Variates",
ICLR 2023).  ASSUMED, written from memory of the released modelling code
(no network here to re-read it), none of which changes an operation
count or a byte moved; the configuration file lists each under
``assumed``:

* the scale ``s`` on the chunk logit ``k_i . phi_h``;
* ``mu`` added to ``k~`` and not to ``v~``;
* rotary BEFORE summarising (the summaries pool rotated keys);
* the visibility rule ``j < (W / c) w`` (chunks of closed windows only);
* the pre-norm residual form of 1 and 5;
* head 0 for sampling;
* the byte tokenizer's offset (the program's 3; the model's own
  differs; the weights are random).

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py _init_eva_layers``):
embedding and head (all eight heads' columns, head 0 first) from keys 8
and 9 of ``split(PRNGKey(seed), 16)``, N(0, 0.02); the layers' tensors
from ``split(fold_in(PRNGKey(seed), 44), 16)``, tensor ``j`` of layer
``i`` from ``fold_in(key j, i)``: v, o and the three feed-forward
matrices N(0, 0.02), q and k N(0, 1 / hidden) (a query's scores then
spread by about 1, at the published width and at a toy one), ``phi`` and
``mu`` N(0, 1), all cast to the served dtype; the norms' ``w`` zero.  The
recipe, not the code, is shared.  Arithmetic is float32 at highest
precision on the served-dtype weights, one layer's weights at a time,
attention in blocks of query rows, so that an 8,200-byte sequence at the
published widths fits the host (scores 32 x 256 x 8,700 float32 = 285 MB
a block).
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# which of the 16 split keys draws which tensor, and its scale (None:
# hidden^-0.5)
KEYS = {"q": (0, None), "k": (1, None), "v": (2, 0.02), "o": (3, 0.02),
        "phi": (4, 1.0), "mu": (5, 1.0), "gate": (6, 0.02),
        "up": (7, 0.02), "down": (8, 0.02)}
QUERY_ROWS = 256  # query rows a block of attention takes


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": D, "H": H, "hd": cfg.get("head_dim") or D // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "P": cfg.get("num_pred_heads", 1), "W": cfg["window_size"],
            "c": cfg["chunk_size"]}


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int]) -> Dict[str, tuple]:
    D, H, hd, F = z["D"], z["H"], z["hd"], z["F"]
    return {"q": (D, H * hd), "k": (D, H * hd), "v": (D, H * hd),
            "o": (H * hd, D), "phi": (H, hd), "mu": (H, hd),
            "gate": (D, F), "up": (D, F), "down": (F, D)}


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype=jnp.bfloat16
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors by the program's recipe."""
    z = sizes(cfg)
    ek = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 44), 16)
    out = {}
    for name, shape in layer_shapes(z).items():
        j, scale = KEYS[name]
        scale = z["D"] ** -0.5 if scale is None else scale
        out[name] = (jax.random.normal(
            jax.random.fold_in(ek[j], i), shape, F32) * scale).astype(dtype)
    return out


def draw_ends(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
              ) -> Dict[str, jax.Array]:
    z = sizes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    normal = lambda k, shape: (
        jax.random.normal(k, shape, F32) * 0.02).astype(dtype)
    return {"embed": normal(keys[8], (z["V"], z["D"])),
            "lm_head": normal(keys[9], (z["D"], z["V"] * z["P"]))}


# ------------------------------------------------------ the mathematics

def norm(x: jax.Array, eps: float, w: Optional[jax.Array] = None
         ) -> jax.Array:
    """x / rms(x) * (1 + w); w = 0 when the weights have none."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * (1.0 + w)


def rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half of x [S, heads, dim] at positions pos [S]."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = pos.astype(F32)[:, None] * inv  # [S, dim / 2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def summaries(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              c: int):
    """Item 3 over the COMPLETE chunks of k, v [S, H, hd]: (k~, v~)
    [S // c, H, hd]."""
    S, H, hd = k.shape
    n = S // c
    kc, vc = k[:n * c].reshape(n, c, H, hd), v[:n * c].reshape(n, c, H, hd)
    alpha = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi) * hd ** -0.5, axis=1)
    return (jnp.einsum("nch,nchd->nhd", alpha, kc) + mu[None],
            jnp.einsum("nch,nchd->nhd", alpha, vc))


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
              ) -> jax.Array:
    """Items 2-4 and the output projection on normed rows x [S, D],
    positions 0 .. S - 1."""
    z = sizes(cfg)
    S, H, hd, W, c = x.shape[0], z["H"], z["hd"], z["W"], z["c"]
    pos = jnp.arange(S)
    theta = float(cfg["rope_theta"])
    q = rotate((x @ w["q"]).reshape(S, H, hd), pos, theta)
    k = rotate((x @ w["k"]).reshape(S, H, hd), pos, theta)
    v = (x @ w["v"]).reshape(S, H, hd)
    ks, vs = summaries(k, v, w["phi"], w["mu"], c)
    chunk = jnp.arange(ks.shape[0])
    keys, vals = jnp.concatenate([k, ks]), jnp.concatenate([v, vs])
    out = []
    for lo in range(0, S, QUERY_ROWS):  # blocks of query rows
        t = pos[lo:lo + QUERY_ROWS, None]
        exact = (pos[None, :] >= t // W * W) & (pos[None, :] <= t)  # E(t)
        closed = chunk[None, :] < (W // c) * (t // W)  # C(t)
        seen = jnp.concatenate([exact, closed], axis=1)
        scores = jnp.einsum("shd,thd->hst", q[lo:lo + QUERY_ROWS], keys)
        scores = jnp.where(seen[None], scores * hd ** -0.5, -jnp.inf)
        out.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1),
                              vals))
    return jnp.concatenate(out).reshape(S, H * hd) @ w["o"]


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
          ) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(norm(x, eps, w.get("input_norm")), w, cfg)
    m = norm(x, eps, w.get("post_norm"))
    return x + (jax.nn.silu(m @ w["gate"]) * (m @ w["up"])) @ w["down"]


def hidden_states(cfg: Dict[str, Any], seed: int, dtype,
                  embed: jax.Array, sequences: List[List[int]],
                  layers: Optional[List[Dict[str, jax.Array]]] = None
                  ) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's weights drawn (or taken from ``layers``) at a time."""
    xs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    for i in range(cfg["num_hidden_layers"]):
        lw = draw_layer(cfg, seed, i, dtype) if layers is None else layers[i]
        w = {name: t.astype(F32) for name, t in lw.items()}
        xs = [layer(x, w, cfg) for x in xs]
    return xs


def head_logits(cfg: Dict[str, Any], seed: int, dtype,
                sequences: List[List[int]], first: List[int],
                weights: Optional[Dict[str, Any]] = None
                ) -> List[np.ndarray]:
    """Every prediction head's logits [rows, heads, vocab] at positions
    ``first[i]-1 .. len-2`` of sequence i.  ``weights`` ({"embed",
    "lm_head", "layers", "final_norm"?}) replaces the draw (a test's)."""
    z = sizes(cfg)
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
            out.append(np.asarray(h @ head).reshape(-1, z["P"], z["V"]))
    return out


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int],
             weights: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
    """Head 0's log-softmax at positions ``first[i]-1 .. len-2`` of
    sequence i: the distributions that predicted tokens ``first[i] ..
    len-1``."""
    return [np.asarray(jax.nn.log_softmax(jnp.asarray(lg[:, 0]), axis=-1))
            for lg in head_logits(cfg, seed, dtype, sequences, first,
                                  weights)]


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
