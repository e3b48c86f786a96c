"""Plain reference: the Mellum2 language model (``model_type``
``mellum``: window and full attention layers in one stack, each layer
KIND under a rotary of its own, every layer's feed-forward a softmax-
routed expert layer) in straightforward ``jax.numpy`` float32 -- no
kernels, no cache, no ring, no batching: every layer's attention is ONE
masked softmax over full scores (the window is a mask), every expert is
applied to the tokens that chose it.  It shares no code with
``vgate_tpu/`` and no mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.mellum CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config``; each point the config does not itself state is
listed under ``assumed`` in the configuration file.  eps =
``rms_norm_eps``; ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.  No biases.

* Every layer ``i`` of kind ``t = layer_types[i]``: ``h <- h + A(N(h;
  w_in))`` then ``h <- h + E(N(h; w_post))``; then ``N(h; w_f)`` and an
  untied head.  The multi-token-prediction head is left out (it feeds no
  logit of the main model).
* ``A``: ``q = x W_q`` -> heads x ``head_dim``, ``k = x W_k``, ``v = x
  W_v`` -> KV heads x ``head_dim`` (grouped: query head h reads KV head
  ``h // (heads / KV)``); a per-head RMSNorm on q and k; q and k rotated
  by ``R_t(pos)``, rotate-half over all ``head_dim`` dimensions, from
  ``rope_parameters[t]``: ``f_j = theta^(-2j / head_dim)``; ``rope_type
  default``: angles ``pos x f_j``, amplitude 1; ``rope_type yarn``:
  ``f'_j = (1 - r_j) f_j + r_j f_j / factor``, ``r_j = clip((j - low) /
  (high - low), 0, 1)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``, ``c(b) = head_dim ln(original / (2 pi b)) / (2
  ln theta)`` (18 and 35 at the published numbers), and cos AND sin
  times ``attention_factor`` (``0.1 ln factor + 1`` where the group has
  none), on q and on k, so a full layer's scores carry its square;
  scores ``head_dim^-0.5 q . k``; a query at position i sees key j where
  ``0 <= i - j``, and in a ``sliding_attention`` layer also ``i - j <
  sliding_window``; softmax in float32; ``out = [o_1 .. o_H] W_o``.
* ``E``: ``p = softmax(x W_r)`` over the ``num_experts`` experts in
  float32; the ``num_experts_per_tok`` largest; weights ``p_e / sum of
  the chosen`` (``norm_topk_prob``); ``y = sum_e w_e W_down,e (silu(
  W_gate,e x) * W_up,e x)`` of width ``moe_intermediate_size``; no shared
  expert, no bias, no scaling factor.  Every expert is held.

Departures from the published model: none known; what the config does
not state (pre-norm sub-blocks, the per-head norms) is the
configuration file's ``assumed``.

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_window_layers``): embedding and head from keys 8 and 9 of
``split(PRNGKey(seed), 16)``; the layers' tensors from
``split(fold_in(PRNGKey(seed), 38), 32)``, tensor ``j`` of layer ``i``
from ``fold_in(key j, i)``, normal x 0.02 cast to the served dtype; norm
weights at one.  The recipe, not the code, is shared.  Arithmetic is
float32 at highest precision on the served-dtype weights, one layer at a
time, one expert at a time and attention in blocks of query rows, so
that neither 3.8 B parameters in float32 nor a 4,227 x 4,227 x 32 score
tensor stand in memory at once.
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
KEYS = {"q": 0, "k": 1, "v": 2, "o": 3, "router": 7, "gate": 8, "up": 9,
        "down": 10}
EXPERT_STACKS = ("gate", "up", "down")
QUERY_ROWS = 256  # query rows a block of attention takes


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "E": cfg["num_experts"],
        "K": cfg["num_experts_per_tok"], "Fe": cfg["moe_intermediate_size"],
    }


def is_window(cfg: Dict[str, Any], i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int]) -> Dict[str, tuple]:
    D, H, KV, hd, E, Fe = (z[n] for n in ("D", "H", "KV", "hd", "E", "Fe"))
    return {"q": (D, H * hd), "k": (D, KV * hd), "v": (D, KV * hd),
            "o": (H * hd, D), "router": (D, E), "gate": (E, D, Fe),
            "up": (E, D, Fe), "down": (E, Fe, D)}


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype=jnp.bfloat16
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors by the program's recipe."""
    wk = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 38), 32)
    return {
        name: (jax.random.normal(
            jax.random.fold_in(wk[KEYS[name]], i), shape, F32) * 0.02
        ).astype(dtype)
        for name, shape in layer_shapes(sizes(cfg)).items()}


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


def yarn_ramp(group: Dict[str, Any], dim: int) -> tuple:
    """(low, high): the dimensions between which YaRN's ramp runs."""
    def c(rotations):
        return (dim * math.log(group["original_max_position_embeddings"]
                               / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(group["rope_theta"])))

    low = max(math.floor(c(group.get("beta_fast", 32))), 0)
    high = min(math.ceil(c(group.get("beta_slow", 1))), dim - 1)
    return low, high + 0.001 if low == high else high


def rotary_of(group: Dict[str, Any], dim: int) -> tuple:
    """(frequencies [dim / 2], amplitude) of one ``rope_parameters``
    group."""
    j = jnp.arange(dim // 2, dtype=F32)
    freq = float(group["rope_theta"]) ** (-2.0 * j / dim)
    if group["rope_type"] == "default":
        return freq, 1.0
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r}")
    low, high = yarn_ramp(group, dim)
    r = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    amplitude = group.get("attention_factor")
    if amplitude is None:
        amplitude = 0.1 * math.log(group["factor"]) + 1.0
    return (1.0 - r) * freq + r * freq / group["factor"], float(amplitude)


def rotate(x: jax.Array, pos: jax.Array, group: Dict[str, Any]
           ) -> jax.Array:
    """Rotate-half of x [S, heads, dim] at positions pos [S]."""
    dim = x.shape[-1]
    freq, amplitude = rotary_of(group, dim)
    angle = pos.astype(F32)[:, None] * freq  # [S, dim / 2]
    cos = amplitude * jnp.cos(angle)[:, None, :]
    sin = amplitude * jnp.sin(angle)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
              i: int) -> jax.Array:
    """x: [S, D], layer ``i``'s normed rows, positions 0 .. S - 1."""
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    S, H, KV, hd = x.shape[0], z["H"], z["KV"], z["hd"]
    window = cfg["sliding_window"] if is_window(cfg, i) else 0
    group = cfg["rope_parameters"][cfg["layer_types"][i]]
    pos = jnp.arange(S)
    q = norm((x @ w["q"]).reshape(S, H, hd), eps, w.get("q_norm"))
    k = norm((x @ w["k"]).reshape(S, KV, hd), eps, w.get("k_norm"))
    v = (x @ w["v"]).reshape(S, KV, hd)
    q, k = rotate(q, pos, group), rotate(k, pos, group)
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
    """(chosen experts [S, K], their weights [S, K])."""
    probs = jax.nn.softmax(x @ w["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return np.asarray(idx), np.asarray(vals)


def moe(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
        ) -> jax.Array:
    """x: [S, D].  The experts' stacks may be in the served dtype: one
    expert is made float32 at a time."""
    idx, vals = route(x, w, cfg)
    out = jnp.zeros_like(x)
    for e in range(sizes(cfg)["E"]):  # every expert, its own tokens
        chose = idx == e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = (vals * chose).sum(axis=1)[rows]
        # to a power of two with rows of weight 0 (they add 0.0 to row
        # 0), so that a few shapes compile and not one an expert
        pad = (1 << int(rows.size - 1).bit_length()) - rows.size
        rows, weight = np.pad(rows, (0, pad)), np.pad(weight, (0, pad))
        y = swiglu(x[rows], *(w[n][e].astype(F32) for n in EXPERT_STACKS))
        out = out.at[rows].add(jnp.asarray(weight)[:, None] * y)
    return out


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          i: int) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(norm(x, eps, w.get("input_norm")), w, cfg, i)
    return x + moe(norm(x, eps, w.get("post_norm")), w, cfg)


def hidden_states(cfg: Dict[str, Any], seed: int, dtype,
                  embed: jax.Array, sequences: List[List[int]]
                  ) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's weights drawn at a time."""
    xs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    for i in range(cfg["num_hidden_layers"]):
        # float32, the experts' stacks as they are
        w = {k: (v if k in EXPERT_STACKS else v.astype(F32))
             for k, v in draw_layer(cfg, seed, i, dtype).items()}
        xs = [layer(x, w, cfg, i) for x in xs]
    return xs


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int]
             ) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``."""
    with jax.default_matmul_precision("highest"):
        ends = draw_ends(cfg, seed, dtype)
        head = ends["lm_head"].astype(F32)
        out = []
        xs = hidden_states(cfg, seed, dtype, ends["embed"], sequences)
        for x, s, f in zip(xs, sequences, first):
            h = norm(x[f - 1: len(s) - 1], cfg["rms_norm_eps"])
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
