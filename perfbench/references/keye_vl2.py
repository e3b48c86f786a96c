"""Plain reference: the LANGUAGE model of Keye-VL-2.0-30B-A3B
(``model_type`` ``KeyeVL2``: grouped-query attention under a LEARNED
SPARSE selection in every layer, a softmax-routed expert layer without a
shared expert) in straightforward ``jax.numpy`` float32 -- no kernels, no
cache, no batching: in every layer the FULL index scores of every (query,
key) pair, an exact top-k, and attention as one masked softmax with
everything outside the pick masked; every held expert applied to the
tokens that chose it.  It shares no code with ``vgate_tpu/`` and no
mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.keye_vl2 CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config``; each point the config does not itself state is
ASSUMED and listed under ``assumed`` in the configuration file:

* Every layer: ``h <- h + A(N(h; w_in))`` then ``h <- h + M(N(h;
  w_post))``, ``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` with eps =
  ``rms_norm_eps``; then ``N(h; w_f)`` and an untied head.  No biases
  (``attention_bias`` false) but the index key's LayerNorm.
* ``A``: ``q = x W_q`` -> ``num_attention_heads`` x ``head_dim``, ``k = x
  W_k`` and ``v = x W_v`` -> ``num_key_value_heads`` x ``head_dim``; ``N``
  over the head's dimensions on q and on k, each with ONE weight of
  ``head_dim`` (ASSUMED: the Qwen3 family's q/k norms); rotate-half
  rotary ``R`` on all ``head_dim`` dimensions, theta ``rope_theta``, the
  ``head_dim / 2`` frequencies split by ``rope_scaling.mrope_section``
  over the position's components (frequency ``i`` turns by the component
  of its section; a text token's components are all its index, which is
  the plain rotary).  Head ``h`` attends with the K and V of head ``h //
  (heads / kv heads)``: the softmax over ``s in S_t`` of ``q_h . k_s x
  head_dim^-0.5``, the values alike, ``out = [o_1 .. o_H] W_o``.
* ``S_t``, in EVERY layer (``sa_config``): ``q^I = x W^I_q`` ->
  ``indexer_num_heads`` x ``indexer_head_dim`` (from the layer's normed
  input: no query latent); ``k^I_s = LN(x_s W^I_k)`` (ONE key a token;
  LayerNorm with weight and bias, eps 1e-6 ASSUMED); ``R`` on all
  ``indexer_head_dim`` dimensions of both at the position's FIRST
  (temporal) component (ASSUMED); ``w = x W^I_w x heads^-0.5 x
  head_dim^-0.5``; ``I(t, s) = sum_j w_j relu(q^I_j . k^I_s)``; ``S_t`` =
  the ``topk`` positions ``s <= t`` of largest ``I(t, .)``, all of them
  while ``t < topk``, ties to the lower position.  ``q_chunk_size`` and
  ``kv_chunk_size`` are a kernel's tile sizes and change no value.
* ``M``: ``p = softmax(x W_r)`` over the router's full width
  (``router_width``, default the held ``num_experts``); the top
  ``num_experts_per_tok``; their weights renormalised to sum 1
  (``norm_topk_prob``); ``out = sum_e p_e E_e(x)`` over the chosen
  experts that are HELD (``num_experts`` of them from ``first_expert``),
  ``E_e(x) = W_d(silu(x W_g) * x W_u)`` of width
  ``moe_intermediate_size``.  No shared expert.

Departures from the published model: the vision tower is left out (its
sizes are not in the catalog row), the indexer's orthogonal (Hadamard)
rotation of ``q^I`` and ``k^I`` is left out (it changes no dot product),
its float8 storage of the index keys is not taken, and the held share of
the experts and of the vocabulary.

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_kv_dsa_layers``): embedding and head from keys 8 and 9 of
``split(PRNGKey(seed), 16)``; the layers' tensors from
``split(fold_in(PRNGKey(seed), 53), 32)``, tensor ``j`` of layer ``i``
from ``fold_in(key j, i)``: N(0, 0.02) cast to the served type but the
indexer's ``W^I_q`` and ``W^I_w``, N(0, 1 / hidden); norm weights 1, the
LayerNorm's bias 0.  The recipe, not the code, is shared.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 256  # query rows whose scores stand at once
# tensor -> (its key among the 32, its scale; None: hidden^-0.5)
TENSORS = {"q": (0, 0.02), "k": (1, 0.02), "v": (2, 0.02), "o": (3, 0.02),
           "index_q": (4, None), "index_k": (5, 0.02), "index_w": (6, None),
           "router": (7, 0.02), "gate": (8, 0.02), "up": (9, 0.02),
           "down": (10, 0.02)}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    sa = cfg["sa_config"]
    E = cfg["num_experts"]
    return {
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "Hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
        "topk": sa["topk"], "E": E, "R": cfg.get("router_width") or E,
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"], "Fe": cfg["moe_intermediate_size"],
        "V": cfg["vocab_size"],
    }


def shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    z = sizes(cfg)
    D, H, KV, hd = z["D"], z["H"], z["KV"], z["hd"]
    return {
        "q": (D, H * hd), "k": (D, KV * hd), "v": (D, KV * hd),
        "o": (H * hd, D), "index_q": (D, z["Hi"] * z["di"]),
        "index_k": (D, z["di"]), "index_w": (D, z["Hi"]),
        "router": (D, z["R"]), "gate": (z["E"], D, z["Fe"]),
        "up": (z["E"], D, z["Fe"]), "down": (z["E"], z["Fe"], D),
    }


def picks(cfg: Dict[str, Any], i: int) -> bool:
    """Does layer ``i`` make a selection of its own?  Every layer."""
    return True


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s matrices in the served type (norm weights are 1 and
    the LayerNorm's bias 0: left out)."""
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 53), 32)
    out = {}
    for name, shape in shapes(cfg).items():
        j, scale = TENSORS[name]
        if scale is None:
            scale = cfg["hidden_size"] ** -0.5
        out[name] = (jax.random.normal(
            jax.random.fold_in(keys[j], i), shape, F32) * scale
        ).astype(dtype)
    return out


def draw_ends(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, jax.Array]:
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    V, D = cfg["vocab_size"], cfg["hidden_size"]
    draw = lambda k, shape: (
        jax.random.normal(k, shape, F32) * 0.02).astype(dtype)
    return {"embed": draw(keys[8], (V, D)), "lm_head": draw(keys[9], (D, V))}


def norm(x: jax.Array, eps: float, weight: Optional[jax.Array] = None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if weight is None else y * weight


def layer_norm(x: jax.Array, weight, bias, eps: float = 1e-6) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight
    return y if bias is None else y + bias


def components(pos) -> jax.Array:
    """Positions as [3, S]: a text token's three components are equal."""
    pos = jnp.asarray(pos)
    return jnp.stack([pos] * 3) if pos.ndim == 1 else pos


def rotate(x: jax.Array, pos, theta: float, sections=None) -> jax.Array:
    """Rotate-half on all of x's last dimension; x [S, ..., d], pos [3,
    S].  ``sections``: frequency ``i`` turns by the component of its
    section; None: by the first (temporal) component."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    pos = components(pos).astype(F32)
    if sections is None:
        at = jnp.broadcast_to(pos[0][:, None], (pos.shape[1], d // 2))
    else:
        comp = np.repeat(np.arange(len(sections)), sections)
        assert comp.size == d // 2, (sections, d)
        at = pos[comp].T  # [S, d / 2]
    ang = at * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
                 pos, rows: slice) -> jax.Array:
    """I(t, s) for the query rows ``rows`` against every key, [rows, S]
    float32, ``-inf`` above the diagonal."""
    z, theta = sizes(cfg), float(cfg["rope_theta"])
    S = x.shape[0]
    pos = components(pos)
    keys = rotate(layer_norm(x @ w["index_k"], w.get("index_k_norm"),
                             w.get("index_k_bias")), pos, theta)  # [S, di]
    q = (x[rows] @ w["index_q"]).reshape(-1, z["Hi"], z["di"])
    q = rotate(q, pos[:, rows], theta)
    weights = (x[rows] @ w["index_w"]) * (z["Hi"] ** -0.5 * z["di"] ** -0.5)
    dots = jnp.einsum("rjd,sd->rjs", q, keys)
    scores = jnp.sum(weights[:, :, None] * jnp.maximum(dots, 0.0), axis=1)
    t = jnp.arange(S)[rows][:, None]
    return jnp.where(jnp.arange(S)[None, :] <= t, scores, -jnp.inf)


def selection(scores, k: int) -> np.ndarray:
    """scores [R, S] (``-inf`` = not a candidate) -> bool [R, S]: each
    row's ``k`` largest candidates (all of them where there are at most
    ``k``), ties to the lower position: an exact, stable sort."""
    scores = np.asarray(scores, np.float32)
    live = scores > -np.inf
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    out = np.zeros(scores.shape, bool)
    np.put_along_axis(out, order, True, axis=-1)
    return out & live


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
              pos=None):
    """x: [S, D], the normed rows.  Returns (out [S, D], the layer's
    selection [S, S] bool)."""
    z, eps, theta = sizes(cfg), cfg["rms_norm_eps"], float(cfg["rope_theta"])
    S, H, KV, hd = x.shape[0], z["H"], z["KV"], z["hd"]
    pos = components(jnp.arange(S) if pos is None else pos)
    sections = cfg.get("rope_scaling", {}).get("mrope_section")
    q = norm((x @ w["q"]).reshape(S, H, hd), eps, w.get("q_norm"))
    k = norm((x @ w["k"]).reshape(S, KV, hd), eps, w.get("k_norm"))
    v = (x @ w["v"]).reshape(S, KV, hd)
    q, k = rotate(q, pos, theta, sections), rotate(k, pos, theta, sections)
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    out, picked = [], []
    for lo in range(0, S, ROW_BLOCK):  # blocks of query rows
        rows = slice(lo, min(lo + ROW_BLOCK, S))
        chosen = selection(index_scores(x, w, cfg, pos, rows), z["topk"])
        scores = jnp.einsum("shd,thd->hst", q[rows], k) * hd ** -0.5
        scores = jnp.where(jnp.asarray(chosen)[None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hst,thd->shd", jax.nn.softmax(scores, -1), v))
        picked.append(chosen)
    attn = jnp.concatenate(out).reshape(S, H * hd)
    return attn @ w["o"], np.concatenate(picked)


def route(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(chosen experts [S, K] among the router's width, their weights)."""
    p = jax.nn.softmax(x @ w["router"], axis=-1)
    vals, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return np.asarray(idx), np.asarray(vals)


def experts(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
            ) -> jax.Array:
    """x: [S, D].  The held experts' part of the routed sum.  The
    experts' stacks may be in the served type: one expert is made
    float32 at a time."""
    z = sizes(cfg)
    idx, vals = route(x, w, cfg)
    out = jnp.zeros_like(x)
    for e in range(z["E"]):  # every held expert, its own tokens
        chose = idx == z["first"] + e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = jnp.asarray((vals * chose).sum(axis=1)[rows])
        gate, up, down = (w[n][e].astype(F32) for n in ("gate", "up", "down"))
        y = (jax.nn.silu(x[rows] @ gate) * (x[rows] @ up)) @ down
        out = out.at[rows].add(weight[:, None] * y)
    return out


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          pos=None):
    """One layer on the residual rows x [S, D]: (x, its selection)."""
    eps = cfg["rms_norm_eps"]
    a, picked = attention(norm(x, eps, w.get("input_norm")), w, cfg, pos)
    x = x + a
    return x + experts(norm(x, eps, w.get("post_norm")), w, cfg), picked


def f32_but_experts(lw: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """A layer's tensors in float32, the experts' stacks as they are."""
    return {k: (v if k in ("gate", "up", "down") else v.astype(F32))
            for k, v in lw.items()}


def hidden_states(cfg: Dict[str, Any], seed: int, dtype,
                  embed: jax.Array, sequences: List[List[int]],
                  layers: Optional[List[Dict[str, jax.Array]]] = None,
                  selections: Optional[list] = None,
                  positions: Optional[list] = None) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's weights drawn (or taken from ``layers``) at a time.
    ``selections`` (a list) receives, per layer, every sequence's
    selection there; ``positions``: a sequence's [3, S] where they are
    not its tokens' indices."""
    xs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    positions = positions or [None] * len(xs)
    for i in range(cfg["num_hidden_layers"]):
        lw = draw_layer(cfg, seed, i, dtype) if layers is None else layers[i]
        w = f32_but_experts(lw)
        done = [layer(x, w, cfg, p) for x, p in zip(xs, positions)]
        xs = [d[0] for d in done]
        if selections is not None:
            selections.append([d[1] for d in done])
    return xs


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int],
             weights: Optional[Dict[str, Any]] = None,
             selections: Optional[list] = None,
             positions: Optional[list] = None) -> List[np.ndarray]:
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
                           None if weights is None else weights["layers"],
                           selections, positions)
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
