"""Plain reference: the Qwen3-Next decoder in straightforward
``jax.numpy`` float32 -- no kernels, no cache, no batching, the linear
layers' recurrence token by token, attention as one masked softmax,
every held expert applied to the tokens that chose it.  It shares no
code with ``vgate_tpu/`` and no mathematics with ``perfbench/reference.py``.

    JAX_PLATFORMS=cpu python -m perfbench.references.qwen3_next CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
published ``config.json`` and the module structure of ``transformers``'
``modeling_qwen3_next.py`` as the writer knows it (no network here; each
point the config does not itself state is listed under ``assumed`` in
the configuration file).  eps = ``rms_norm_eps`` everywhere;
``N1p(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``.

* Layer ``l`` (0-based) is FULL if ``(l + 1) % full_attention_interval
  == 0``, else LINEAR.  ``h = x + Mixer(N1p(x))``; ``y = h +
  MoE(N1p(h))``.  Final ``N1p``, untied head.
* FULL (gated attention): ``x W_q`` -> heads x ``2 * head_dim``, per head
  the first half the query and the second the gate; ``k``, ``v`` on
  ``num_key_value_heads``; ``q``, ``k`` through ``N1p`` per head;
  rotate-half RoPE on the first ``partial_rotary_factor * head_dim``
  dimensions; causal softmax attention, scale ``head_dim ** -0.5``;
  ``out = (attn * sigmoid(gate)) W_o``.
* LINEAR (Gated DeltaNet): ``x W_qkvz`` -> q, k (``linear_num_key_heads``
  x ``linear_key_head_dim``), v, z (``linear_num_value_heads`` x
  ``linear_value_head_dim``), laid out ``[q | k | v | z]``; ``x W_ba``
  -> ``[b | a]``.  The (q, k, v) channels pass a causal depth-wise
  convolution of ``linear_conv_kernel_dim`` taps (no bias), then SiLU.
  q, k L2-normalised per head, ``q *= dk ** -0.5``, key heads repeated
  to the value heads.  ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)``.  Per value head, ``S = 0``:
  ``S <- S e^g; d = beta (v - S^T k); S <- S + k d^T; o = S^T q``.
  ``o <- RMSNorm(o; w) * SiLU(z)`` with the PLAIN weight, ``out = o W_out``.
* MoE: ``p = softmax(x W_r)`` over the router's full width; top
  ``num_experts_per_tok``, renormalised to sum 1; ``routed = sum w_e
  E_e(x)`` over the chosen experts that are HELD (``num_experts`` of
  them from ``first_expert``: what the absent ones would add is left
  out, model-configs guide section 4); ``shared = sigmoid(x w_sg)
  E_s(x)``; ``out = routed + shared``.
* Left out: the multi-token-prediction module.

Weights.  ``draw_weights`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py init_layers``):
embedding and head from keys 8 and 9 of ``split(PRNGKey(seed), 16)``,
the layers from ``split(fold_in(PRNGKey(seed), 27), 32)``, normal x 0.02
cast to the served dtype (the convolution x 0.5), norm weights at their
identity, ``A_log`` normal x 0.02 in float32, ``dt_bias`` the inverse
softplus of a rate drawn log-uniformly in [0.001, 0.693].  The recipe,
not the code, is shared.  Arithmetic is float32 at highest precision on
the served-dtype weights, one layer at a time, so that 3.7 B parameters
in float32 never stand in memory at once.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ------------------------------------------------------------- the sizes

def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    period = cfg["full_attention_interval"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {
        "D": D, "L": L, "period": period, "P": L // period, "n": period - 1,
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "V": cfg["vocab_size"],
        "E": cfg["num_experts"],
        "R": cfg.get("router_width") or cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg["shared_expert_intermediate_size"],
        "Hk": Hk, "Hv": Hv, "dk": dk, "dv": dv,
        "kd": Hk * dk, "vd": Hv * dv, "C": 2 * Hk * dk + Hv * dv,
        "taps": cfg["linear_conv_kernel_dim"],
        "rot": int(cfg["head_dim"] * cfg.get("partial_rotary_factor", 1.0)),
    }


def is_full(cfg: Dict[str, Any], layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


# ----------------------------------------------------------- the weights

def _moe_shapes(z: Dict[str, int], lead: tuple) -> Dict[str, tuple]:
    D, E, R, Fe, Fs = z["D"], z["E"], z["R"], z["Fe"], z["Fs"]
    return {
        "router": lead + (D, R), "gate": lead + (E, D, Fe),
        "up": lead + (E, D, Fe), "down": lead + (E, Fe, D),
        "shared_gate": lead + (D, Fs), "shared_up": lead + (D, Fs),
        "shared_down": lead + (Fs, D), "shared_router": lead + (D,),
    }


MOE_ORDER = ("router", "gate", "up", "down", "shared_gate", "shared_up",
             "shared_down", "shared_router")


def draw_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, Any]:
    """{"embed", "lm_head", "full": {name: [P, ...]}, "linear": {name:
    [P, n, ...]}} by the program's recipe."""
    z = sizes(cfg)
    root = jax.random.PRNGKey(seed)
    keys = jax.random.split(root, 16)
    hk = jax.random.split(jax.random.fold_in(root, 27), 32)

    def normal(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, F32) * scale).astype(dtype)

    D, P, n, H, KV, hd = z["D"], z["P"], z["n"], z["H"], z["KV"], z["hd"]
    full = {
        "q": normal(hk[0], (P, D, 2 * H * hd)),
        "k": normal(hk[1], (P, D, KV * hd)),
        "v": normal(hk[2], (P, D, KV * hd)),
        "o": normal(hk[3], (P, H * hd, D)),
    }
    for i, name in enumerate(MOE_ORDER):
        full[name] = normal(hk[4 + i], _moe_shapes(z, (P,))[name])
    lead = (P, n)
    rate = jnp.exp(
        jnp.log(1e-3) + jax.random.uniform(hk[16], lead + (z["Hv"],))
        * (jnp.log(0.693) - jnp.log(1e-3))
    )
    linear = {
        "in_qkvz": normal(hk[12], lead + (D, z["C"] + z["vd"])),
        "in_ba": normal(hk[13], lead + (D, 2 * z["Hv"])),
        "conv": normal(hk[14], lead + (z["C"], z["taps"]), scale=0.5),
        "a_log": jax.random.normal(hk[15], lead + (z["Hv"],), F32) * 0.02,
        "dt_bias": jnp.log(jnp.expm1(rate)).astype(F32),
        "out": normal(hk[17], lead + (z["vd"], D)),
    }
    for i, name in enumerate(MOE_ORDER):
        linear[name] = normal(hk[18 + i], _moe_shapes(z, lead)[name])
    return {
        "embed": normal(keys[8], (z["V"], D)),
        "lm_head": normal(keys[9], (D, z["V"])),
        "full": full, "linear": linear,
    }


def layer_weights(cfg: Dict[str, Any], weights: Dict[str, Any], layer: int
                  ) -> Dict[str, jax.Array]:
    """One layer's tensors in float32 (norm weights, where the weights
    carry none, are the identity and left out)."""
    period = cfg["full_attention_interval"]
    p, j = divmod(layer, period)
    if is_full(cfg, layer):
        return {k: v[p].astype(F32) for k, v in weights["full"].items()}
    return {k: v[p, j].astype(F32) for k, v in weights["linear"].items()}


# ------------------------------------------------------ the mathematics

def norm1p(x: jax.Array, eps: float, w: Optional[jax.Array] = None
           ) -> jax.Array:
    """x / rms(x) * (1 + w); w = 0 (identity) when the weights have none."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * (1.0 + w)


def partial_rope(x: jax.Array, theta: float, rot: int) -> jax.Array:
    """x: [S, heads, hd], positions 0..S-1; rotate-half on the first
    ``rot`` dimensions, the others pass through."""
    S = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned, x[..., rot:]], -1)


def gated_attention(x: jax.Array, w: Dict[str, jax.Array],
                    cfg: Dict[str, Any]) -> jax.Array:
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    H, KV, hd, S = z["H"], z["KV"], z["hd"], x.shape[0]
    qg = (x @ w["q"]).reshape(S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ w["k"]).reshape(S, KV, hd)
    v = (x @ w["v"]).reshape(S, KV, hd)
    q = partial_rope(norm1p(q, eps, w.get("q_norm")), cfg["rope_theta"],
                     z["rot"])
    k = partial_rope(norm1p(k, eps, w.get("k_norm")), cfg["rope_theta"],
                     z["rot"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return (attn * jax.nn.sigmoid(gate)).reshape(S, H * hd) @ w["o"]


def causal_conv_silu(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: [S, C], w: [C, taps]; y_t = sum_j w[:, j] x_{t - (taps-1) + j}
    with zeros before the sequence's start, then SiLU."""
    S, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    y = sum(padded[j: j + S] * w[:, j] for j in range(taps))
    return jax.nn.silu(y)


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, state: Optional[jax.Array] = None):
    """The recurrence, token by token.  q, k: [S, Hv, dk]; v: [S, Hv,
    dv]; g, beta: [S, Hv].  Returns (o [S, Hv, dv], final S [Hv, dk, dv])."""
    Hv, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    S_ = jnp.zeros((Hv, dk, dv), F32) if state is None else state
    out = []
    for t in range(q.shape[0]):
        S_ = S_ * jnp.exp(g[t])[:, None, None]
        d = beta[t][:, None] * (v[t] - jnp.einsum("hkv,hk->hv", S_, k[t]))
        S_ = S_ + k[t][:, :, None] * d[:, None, :]
        out.append(jnp.einsum("hkv,hk->hv", S_, q[t]))
    return jnp.stack(out), S_


def linear_attention(x: jax.Array, w: Dict[str, jax.Array],
                     cfg: Dict[str, Any]) -> jax.Array:
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    S, Hk, Hv, dk, dv = x.shape[0], z["Hk"], z["Hv"], z["dk"], z["dv"]
    qkvz, ba = x @ w["in_qkvz"], x @ w["in_ba"]
    y = causal_conv_silu(qkvz[:, : z["C"]], w["conv"])
    zg = qkvz[:, z["C"]:].reshape(S, Hv, dv)
    q = y[:, : z["kd"]].reshape(S, Hk, dk)
    k = y[:, z["kd"]: 2 * z["kd"]].reshape(S, Hk, dk)
    v = y[:, 2 * z["kd"]:].reshape(S, Hv, dv)
    l2 = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q) * dk ** -0.5, Hv // Hk, axis=1)
    k = jnp.repeat(l2(k), Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])
    o, _ = gated_delta(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    if "gdn_norm" in w:  # the plain weight (ones-centred)
        o = o * w["gdn_norm"]
    return (o * jax.nn.silu(zg)).reshape(S, Hv * dv) @ w["out"]


def expert(x: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array
           ) -> jax.Array:
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
        shared: bool = True) -> jax.Array:
    """x: [S, D].  The held experts' part of the routed sum, plus the
    shared expert (``shared`` False leaves it out: a test adds the
    shares of several chips and counts it once)."""
    z = sizes(cfg)
    probs = jax.nn.softmax(x @ w["router"], axis=-1)  # over all R
    vals, idx = jax.lax.top_k(probs, z["K"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals, idx = np.asarray(vals), np.asarray(idx)
    out = jnp.zeros_like(x)
    for e in range(z["E"]):  # every held expert, its own tokens
        chose = idx == z["first"] + e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = jnp.asarray((vals * chose).sum(axis=1)[rows])
        y = expert(x[rows], w["gate"][e], w["up"][e], w["down"][e])
        out = out.at[rows].add(weight[:, None] * y)
    if shared and z["Fs"]:
        s = expert(x, w["shared_gate"], w["shared_up"], w["shared_down"])
        out = out + jax.nn.sigmoid(x @ w["shared_router"])[:, None] * s
    return out


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          full: bool) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    mixer = gated_attention if full else linear_attention
    h = x + mixer(norm1p(x, eps, w.get("input_norm")), w, cfg)
    return h + moe(norm1p(h, eps, w.get("post_norm")), w, cfg)


def hidden_states(cfg: Dict[str, Any], weights: Dict[str, Any],
                  sequences: List[List[int]]) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's float32 weights at a time."""
    xs = [weights["embed"][jnp.asarray(s)].astype(F32) for s in sequences]
    for l in range(cfg["num_hidden_layers"]):
        w = layer_weights(cfg, weights, l)
        xs = [layer(x, w, cfg, is_full(cfg, l)) for x in xs]
    return xs


def logits(cfg: Dict[str, Any], weights: Dict[str, Any],
           sequences: List[List[int]]) -> List[np.ndarray]:
    """Logits [S, V] at every position of every sequence."""
    with jax.default_matmul_precision("highest"):
        head = weights["lm_head"].astype(F32)
        fw = weights.get("final_norm")
        return [
            np.asarray(norm1p(x, cfg["rms_norm_eps"],
                              None if fw is None else fw.astype(F32)) @ head)
            for x in hidden_states(cfg, weights, sequences)
        ]


def logprobs(cfg: Dict[str, Any], weights: Dict[str, Any],
             sequences: List[List[int]], first: List[int]
             ) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``."""
    with jax.default_matmul_precision("highest"):
        head = weights["lm_head"].astype(F32)
        out = []
        for x, s, f in zip(hidden_states(cfg, weights, sequences),
                           sequences, first):
            fw = weights.get("final_norm")
            h = norm1p(x[f - 1: len(s) - 1], cfg["rms_norm_eps"],
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
    weights = draw_weights(cfg, int(job["weights_seed"]), dtype)
    lps = logprobs(cfg, weights, job["sequences"], job["first"])
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
