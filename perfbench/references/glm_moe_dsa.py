"""Plain reference: the GLM-5.2 language model (``model_type``
``glm_moe_dsa``: multi-head latent attention under a LEARNED SPARSE
selection, leading dense layers, a sigmoid-routed expert layer with an
ungated shared expert) in straightforward ``jax.numpy`` float32 -- no
kernels, no cache, no batching: in a layer that picks, the FULL index
scores of every (query, key) pair, an exact top-k, and attention in its
NON-absorbed form as one masked softmax with everything outside the pick
masked; every held expert applied to the tokens that chose it.  It shares
no code with ``vgate_tpu/`` and no mathematics with another family's
reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.glm_moe_dsa CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config``; each point the config does not itself state is
listed under ``assumed`` in the configuration file.  eps =
``rms_norm_eps``; ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.  No biases
but the index key's LayerNorm.

* Every layer: ``h <- h + A(N(h; w_in))`` then ``h <- h + F(N(h;
  w_post))``; then ``N(h; w_f)`` and an untied head.
* ``A``: ``c_q = N(x W_qa)``; ``q = c_q W_qb`` -> heads x ``[q_nope |
  q_rope]``; ``x W_kva`` -> ``[c | k_r]``, ``c_kv = N(c)``, ``k_rope =
  R(k_r)``, ONE rotary key for all heads; per head ``[k_nope | v] = c_kv
  W_kvb``; ``R`` rotate-half on all ``qk_rope_head_dim`` dimensions,
  theta from ``rope_parameters``, type ``default``; scores ``(q . k) x
  (nope + rope)^-0.5``; softmax over the SELECTED keys ``S_t`` only; ``out
  = [o_1 .. o_H] W_o``.
* The stack held is ``num_hidden_layers`` layers from ``first_layer``
  (default 0) of the per-layer lists ``indexer_types`` and
  ``mlp_layer_types``.
* ``S_t`` in a layer whose ``indexer_types`` entry is ``full``: ``q^I =
  c_q W^I_q`` -> ``index_n_heads`` x ``index_head_dim``; ``k^I_s =
  LN(x_s W^I_k)`` (LayerNorm, weight and bias, eps 1e-6); ``R`` on the
  FIRST ``qk_rope_head_dim`` dimensions of both; ``w = x W^I_w x
  index_n_heads^-0.5 x index_head_dim^-0.5``; ``I(t, s) = sum_j w_j
  relu(q^I_j . k^I_s)``; ``S_t`` = the ``index_topk`` positions ``s <= t``
  of largest ``I(t, .)``, all of them while ``t < index_topk``, ties to
  the lower position.  In a ``shared`` layer: the ``S_t`` of the nearest
  ``full`` layer below it.
* ``F``, a layer whose ``mlp_layer_types`` entry is ``dense``: ``W_d(silu(x
  W_g) * x W_u)`` of width ``intermediate_size``.  ``sparse``: ``s =
  sigmoid(x W_r)`` over the router's full width; the top
  ``num_experts_per_tok`` of ``s + b`` (a selection bias, for the CHOICE
  only); weights ``s_i / (sum s + 1e-20)`` over the chosen
  (``norm_topk_prob``) ``x routed_scaling_factor``; ``out = sum_e w_e
  E_e(x)`` over the chosen experts that are HELD (``n_routed_experts`` of
  them from ``first_expert``) ``+ S(x)``, ``S`` one ungated SwiGLU of
  width ``n_shared_experts x moe_intermediate_size``.

Departures from the published model: the indexer's orthogonal (Hadamard)
rotation of ``q^I`` and ``k^I`` is left out (it changes no dot product),
its float8 storage of the index keys is not taken, the multi-token-
prediction module is left out (it feeds no logit of the main model), the
selection bias is drawn N(0, 0.02) and not trained, and the held share.

Weights.  ``draw_layer`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py _init_dsa_layers``):
embedding and head from keys 8 and 9 of ``split(PRNGKey(seed), 16)``; the
layers' tensors from ``split(fold_in(PRNGKey(seed), 40), 32)``, tensor
``j`` of layer ``i`` (its index in the whole stack) from ``fold_in(key j,
i)``, normal x 0.02 cast to the served dtype but ``q_b`` and ``kv_b`` x
0.05, the selection bias float32; norm weights at one, the LayerNorm's
bias at zero.  The recipe, not the code, is shared.  Arithmetic is
float32 at highest precision on the served-dtype weights, one layer at a
time, one expert at a time, the index scores a head at a time and
attention in blocks of query rows, so that neither 3.9 B parameters in
float32 nor a 6,000 x 6,000 x 64 score tensor stand in memory at once.
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
KEYS = {"q_a": 0, "q_b": 1, "kv_a": 2, "kv_b": 3, "o": 4,
        "mlp_gate": 5, "mlp_up": 6, "mlp_down": 7, "router": 8,
        "gate": 9, "up": 10, "down": 11, "router_bias": 12,
        "shared_gate": 13, "shared_up": 14, "shared_down": 15,
        "index_q": 16, "index_k": 17, "index_w": 18}
WIDE = ("q_b", "kv_b")  # drawn x 0.05
ROW_BLOCK = 256  # query rows attention takes at once
LN_EPS = 1e-6  # the index key's LayerNorm


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "ql": cfg["q_lora_rank"],
        "kl": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "F": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
        "R": cfg.get("router_width") or cfg["n_routed_experts"],
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"], "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg.get("n_shared_experts", 0) * cfg["moe_intermediate_size"],
        "Hi": cfg["index_n_heads"], "di": cfg["index_head_dim"],
        "topk": cfg["index_topk"],
    }


def picks(cfg: Dict[str, Any], i: int) -> bool:
    """Layer ``i`` of the stack held here is layer ``first_layer + i`` of
    the lists (a cut states the published lists whole)."""
    return cfg["indexer_types"][cfg.get("first_layer", 0) + i] == "full"


def is_dense(cfg: Dict[str, Any], i: int) -> bool:
    return cfg["mlp_layer_types"][cfg.get("first_layer", 0) + i] == "dense"


# ----------------------------------------------------------- the weights

def layer_shapes(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    z = sizes(cfg)
    D, H = z["D"], z["H"]
    out = {
        "q_a": (D, z["ql"]), "q_b": (z["ql"], H * (z["nope"] + z["rope"])),
        "kv_a": (D, z["kl"] + z["rope"]),
        "kv_b": (z["kl"], H, z["nope"] + z["vd"]), "o": (H * z["vd"], D),
    }
    if picks(cfg, i):
        out.update({"index_q": (z["ql"], z["Hi"] * z["di"]),
                    "index_k": (D, z["di"]), "index_w": (D, z["Hi"])})
    if is_dense(cfg, i):
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
    dk = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 40), 32)
    out = {}
    for name, shape in layer_shapes(cfg, i).items():
        t = jax.random.normal(jax.random.fold_in(dk[KEYS[name]], i),
                              shape, F32) * (0.05 if name in WIDE else 0.02)
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


def layer_norm(x: jax.Array, w: Optional[jax.Array],
               b: Optional[jax.Array]) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(
        jnp.mean((x - mu) ** 2, axis=-1, keepdims=True) + LN_EPS)
    return y * (1.0 if w is None else w) + (0.0 if b is None else b)


def rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half of x [S, ..., dim] at positions pos [S], on all of
    ``dim``."""
    dim = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = pos.astype(F32)[:, None] * freq  # [S, dim / 2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(x: jax.Array, cq: jax.Array, w: Dict[str, jax.Array],
                 cfg: Dict[str, Any], pos: jax.Array) -> jax.Array:
    """I(t, s) [S, S] float32 for every pair, ``-inf`` where s > t."""
    z, theta = sizes(cfg), float(cfg["rope_parameters"]["rope_theta"])
    S, Hi, di, r = x.shape[0], z["Hi"], z["di"], z["rope"]
    first = lambda t: jnp.concatenate(
        [rotate(t[..., :r], pos, theta), t[..., r:]], axis=-1)
    qi = first((cq @ w["index_q"]).reshape(S, Hi, di))
    ki = first(layer_norm(x @ w["index_k"], w.get("index_k_norm"),
                          w.get("index_k_bias")))
    wt = (x @ w["index_w"]) * (Hi ** -0.5 * di ** -0.5)  # [S, Hi]
    scores = jnp.zeros((S, S), F32)
    for j in range(Hi):  # a head at a time
        scores = scores + wt[:, j:j + 1] * jax.nn.relu(qi[:, j] @ ki.T)
    return jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)


def selection(scores: jax.Array, topk: int) -> np.ndarray:
    """The pick as a mask [S, S]: per row the ``topk`` largest scores
    (``jax.lax.top_k``: of equal scores the lower position first), and
    nothing above the diagonal."""
    S = scores.shape[0]
    mask = np.zeros((S, S), bool)
    if topk >= S:
        mask[:] = True
    else:
        idx = np.asarray(jax.lax.top_k(scores, topk)[1])
        mask[np.arange(S)[:, None], idx] = True
    return mask & np.tri(S, dtype=bool)


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
              picked: Optional[np.ndarray]):
    """x: [S, D], the normed rows; the NON-absorbed form.  ``picked``: the
    selection to attend under (a layer that reuses one), None where the
    layer has an indexer and makes its own.  Returns (out, selection)."""
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    S, H, nope, kl = x.shape[0], z["H"], z["nope"], z["kl"]
    pos = jnp.arange(S)
    cq = norm(x @ w["q_a"], eps, w.get("q_a_norm"))
    if "index_q" in w:
        picked = selection(index_scores(x, cq, w, cfg, pos), z["topk"])
    q = (cq @ w["q_b"]).reshape(S, H, nope + z["rope"])
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], pos, theta)], -1)
    kv = x @ w["kv_a"]
    c_kv = norm(kv[:, :kl], eps, w.get("kv_a_norm"))
    k_rope = rotate(kv[:, kl:], pos, theta)  # [S, rope], one for all heads
    kv_b = w["kv_b"].reshape(kl, H, nope + z["vd"])
    k_nope = jnp.einsum("tk,khn->thn", c_kv, kv_b[..., :nope])
    v = jnp.einsum("tk,khv->thv", c_kv, kv_b[..., nope:])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (S, H, z["rope"]))], -1)
    sigma = (nope + z["rope"]) ** -0.5
    out = []
    for lo in range(0, S, ROW_BLOCK):  # blocks of query rows
        rows = slice(lo, min(lo + ROW_BLOCK, S))
        scores = jnp.einsum("shd,thd->hst", q[rows], k) * sigma
        scores = jnp.where(jnp.asarray(picked[rows])[None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hst,thv->shv", jax.nn.softmax(scores, -1), v))
    attn = jnp.concatenate(out).reshape(S, H * z["vd"])
    return attn @ w["o"], picked


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(chosen experts [S, K] among the router's width, their weights)."""
    s = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return np.asarray(idx), np.asarray(
        vals * cfg.get("routed_scaling_factor", 1))


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
          picked: Optional[np.ndarray]):
    """One layer on the residual rows x [S, D] under the selection
    ``picked`` of the layers below: (x, the selection now in force)."""
    eps = cfg["rms_norm_eps"]
    a, picked = attention(norm(x, eps, w.get("input_norm")), w, cfg, picked)
    x = x + a
    h = norm(x, eps, w.get("post_norm"))
    if "mlp_gate" in w:
        return x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"]), picked
    return x + moe(h, w, cfg), picked


def f32_but_experts(lw: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """A layer's tensors in float32, the experts' stacks as they are."""
    return {k: (v if k in ("gate", "up", "down") else v.astype(F32))
            for k, v in lw.items()}


def hidden_states(cfg: Dict[str, Any], seed: int, dtype,
                  embed: jax.Array, sequences: List[List[int]],
                  layers: Optional[List[Dict[str, jax.Array]]] = None,
                  selections: Optional[list] = None) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's weights drawn (or taken from ``layers``) at a time.
    ``selections`` (a list) receives, per layer, every sequence's
    selection in force there."""
    xs = [embed[jnp.asarray(s)].astype(F32) for s in sequences]
    picked: List[Optional[np.ndarray]] = [None] * len(xs)
    for i in range(cfg["num_hidden_layers"]):
        lw = draw_layer(cfg, seed, i, dtype) if layers is None else layers[i]
        w = f32_but_experts(lw)
        done = [layer(x, w, cfg, p) for x, p in zip(xs, picked)]
        xs, picked = [d[0] for d in done], [d[1] for d in done]
        if selections is not None:
            selections.append(list(picked))
    return xs


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int],
             weights: Optional[Dict[str, Any]] = None,
             selections: Optional[list] = None) -> List[np.ndarray]:
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
                           selections)
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
