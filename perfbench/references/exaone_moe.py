"""Plain reference: the K-EXAONE language model (``model_type``
``exaone_moe``: window and full attention layers in one stack, a leading
dense layer, a sigmoid-routed expert layer with a shared expert) in
straightforward ``jax.numpy`` float32 -- no kernels, no cache, no ring,
no batching: every layer's attention is ONE masked softmax over full
scores (the window is a mask), every held expert is applied to the
tokens that chose it.  It shares no code with ``vgate_tpu/`` and no
mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.exaone_moe CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config``; each point the config does not itself state is
listed under ``assumed`` in the configuration file.  eps =
``rms_norm_eps``; ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.  No biases.

* Every layer: ``h <- h + A(N(h; w_in))`` then ``h <- h + F(N(h;
  w_post))``; then ``N(h; w_f)`` and an untied head.  The multi-token-
  prediction module is left out (it feeds no logit of the main model).
* ``A``: ``q = x W_q`` -> heads x ``head_dim``, ``k = x W_k``, ``v = x
  W_v`` -> KV heads x ``head_dim`` (grouped: ``heads / KV`` query heads a
  KV head); a per-head RMSNorm on q and k; rotate-half rotary on all
  ``head_dim`` dimensions (theta from ``rope_parameters``, type
  ``default``) on the WINDOW layers only, none on a full layer; scores
  ``head_dim^-0.5 q . k``; a query at position t sees keys ``t - window
  + 1 .. t`` in a window layer (``layer_types[i] ==
  "sliding_attention"``, ``sliding_windows[i]``) and ``0 .. t`` in a full
  one; ``out = [o_1 .. o_H] W_o``.
* ``F``, the first ``first_k_dense_replace`` layers: ``W_d(silu(x W_g) *
  x W_u)`` of width ``intermediate_size``.
* ``F``, the others: ``s = sigmoid(x W_r)`` over the router's full
  width in float32; the top ``num_experts_per_tok`` of ``s + b`` (a
  selection bias, for the CHOICE only); weights ``s_i / (sum s + 1e-20)``
  over the chosen (``norm_topk_prob``) ``x routed_scaling_factor``; ``E(x)
  = (silu(x W_g) * x W_u) W_d`` of width ``moe_intermediate_size``;
  ``out = sum_e w_e E_e(x)`` over the chosen experts that are HELD
  (``num_experts`` of them from ``first_expert``: what the absent ones
  would add is left out, model-configs guide section 4) ``+ S(x)``, ``S``
  one ungated SwiGLU of width ``num_shared_experts x
  moe_intermediate_size``.

Departures from the published model: pre-norm residual sub-blocks (the
program's one residual form; EXAONE-4 norms a sub-block's output), the
selection bias drawn N(0, 0.02) and not trained, and the held share.

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_window_layers``): embedding and head from keys 8 and 9 of
``split(PRNGKey(seed), 16)``; the layers' tensors from
``split(fold_in(PRNGKey(seed), 38), 32)``, tensor ``j`` of layer ``i``
(its index in the whole stack) from ``fold_in(key j, i)``, normal x 0.02
cast to the served dtype, the selection bias float32; norm weights at
one.  The recipe, not the code, is shared.  Arithmetic is float32 at
highest precision on the served-dtype weights, one layer at a time, one
expert at a time and attention in blocks of query rows, so that neither
3.7 B parameters in float32 nor a 1,500 x 1,500 x 64 score tensor stand
in memory at once.
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
KEYS = {"q": 0, "k": 1, "v": 2, "o": 3, "mlp_gate": 4, "mlp_up": 5,
        "mlp_down": 6, "router": 7, "gate": 8, "up": 9, "down": 10,
        "router_bias": 11, "shared_gate": 12, "shared_up": 13,
        "shared_down": 14}
QUERY_ROWS = 256  # query rows a block of attention takes


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "F": cfg["intermediate_size"],
        "E": cfg["num_experts"],
        "R": cfg.get("router_width") or cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"], "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg.get("num_shared_experts", 0) * cfg["moe_intermediate_size"],
    }


def window_of(cfg: Dict[str, Any], i: int) -> int:
    """Layer ``i``'s window (0 = full attention): the config's own list,
    or its pattern's letter."""
    if "sliding_windows" in cfg:
        return cfg["sliding_windows"][i]
    pat = cfg["sliding_window_pattern"]
    return cfg["sliding_window"] if pat[i % len(pat)] == "L" else 0


def is_dense(cfg: Dict[str, Any], i: int) -> bool:
    return i < cfg.get("first_k_dense_replace", 0)


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int], dense: bool) -> Dict[str, tuple]:
    D, H, KV, hd = z["D"], z["H"], z["KV"], z["hd"]
    out = {"q": (D, H * hd), "k": (D, KV * hd), "v": (D, KV * hd),
           "o": (H * hd, D)}
    if dense:
        out.update({"mlp_gate": (D, z["F"]), "mlp_up": (D, z["F"]),
                    "mlp_down": (z["F"], D)})
        return out
    out.update({"router": (D, z["R"]), "router_bias": (z["R"],),
                "gate": (z["E"], D, z["Fe"]), "up": (z["E"], D, z["Fe"]),
                "down": (z["E"], z["Fe"], D)})
    if z["Fs"]:
        out.update({"shared_gate": (D, z["Fs"]), "shared_up": (D, z["Fs"]),
                    "shared_down": (z["Fs"], D)})
    return out


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype=jnp.bfloat16
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors by the program's recipe."""
    wk = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 38), 32)
    out = {}
    for name, shape in layer_shapes(sizes(cfg), is_dense(cfg, i)).items():
        t = jax.random.normal(
            jax.random.fold_in(wk[KEYS[name]], i), shape, F32) * 0.02
        out[name] = t if name == "router_bias" else t.astype(dtype)
    return out


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


def rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half of x [S, heads, dim] at positions pos [S]."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = pos.astype(F32)[:, None] * inv  # [S, dim / 2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
              window: int) -> jax.Array:
    """x: [S, D], the normed rows, positions 0 .. S - 1.  ``window`` 0:
    a full layer (causal, no rotary)."""
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    S, H, KV, hd = x.shape[0], z["H"], z["KV"], z["hd"]
    pos = jnp.arange(S)
    q = norm((x @ w["q"]).reshape(S, H, hd), eps, w.get("q_norm"))
    k = norm((x @ w["k"]).reshape(S, KV, hd), eps, w.get("k_norm"))
    v = (x @ w["v"]).reshape(S, KV, hd)
    if window:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rotate(q, pos, theta), rotate(k, pos, theta)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    out = []
    for lo in range(0, S, QUERY_ROWS):  # blocks of query rows
        rows = pos[lo:lo + QUERY_ROWS]
        scores = jnp.einsum("shd,thd->hst", q[lo:lo + QUERY_ROWS], k)
        scores = scores * hd ** -0.5
        seen = pos[None, :] <= rows[:, None]
        if window:
            seen &= rows[:, None] - pos[None, :] < window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(S, H * hd) @ w["o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(chosen experts [S, K] among the router's width, their weights)."""
    scores = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return np.asarray(idx), np.asarray(
        vals * cfg.get("routed_scaling_factor", 1.0))


def moe(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
        shared: bool = True, first: Optional[int] = None,
        count: Optional[int] = None) -> jax.Array:
    """x: [S, D].  The held experts' part of the routed sum (``count``
    experts from ``first``: the configuration's share by default) plus
    the shared expert (``shared`` False leaves it out: a test adds the
    shares of several chips and counts it once).  The experts' stacks
    may be in the served dtype: one expert is made float32 at a time."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["E"] if count is None else count
    idx, vals = route(x, w, cfg)
    out = jnp.zeros_like(x)
    for e in range(count):  # every held expert, its own tokens
        chose = idx == first + e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = (vals * chose).sum(axis=1)[rows]
        # to a power of two with rows of weight 0 (they add 0.0 to row
        # 0), so that a few shapes compile and not one an expert
        pad = (1 << int(rows.size - 1).bit_length()) - rows.size
        rows, weight = np.pad(rows, (0, pad)), np.pad(weight, (0, pad))
        y = swiglu(x[rows], *(w[n][e].astype(F32)
                              for n in ("gate", "up", "down")))
        out = out.at[rows].add(jnp.asarray(weight)[:, None] * y)
    if shared and z["Fs"]:
        out = out + swiglu(x, *(w[n].astype(F32) for n in (
            "shared_gate", "shared_up", "shared_down")))
    return out


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          i: int) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(norm(x, eps, w.get("input_norm")), w, cfg,
                      window_of(cfg, i))
    h = norm(x, eps, w.get("post_norm"))
    if is_dense(cfg, i):
        return x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
    return x + moe(h, w, cfg)


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
        xs = [layer(x, w, cfg, i) for x in xs]
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
