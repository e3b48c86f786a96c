"""Plain reference: the Granite 4.0-H decoder (``granitemoehybrid`` with
``num_local_experts`` 0) in straightforward ``jax.numpy`` float32 -- no
kernels, no cache, no batching, the Mamba-2 recurrence one token after
another, attention as one masked softmax.  It shares no code with
``vgate_tpu/`` and no mathematics with another family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.granite_hybrid CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config`` and the module structure of ``transformers``'
``modeling_granitemoehybrid.py`` (its Mamba-2 mixer is Bamba's) as the
writer knows them; what the config does not itself state is listed under
``assumed`` in the configuration file.  ``eps = rms_norm_eps``; ``N(x; w)
= x / sqrt(mean(x^2) + eps) * w``, the PLAIN weight; no bias anywhere but
the convolution's.

* ``h0 = embedding_multiplier * embed[ids]``.
* ``layer_types[i]`` gives layer ``i`` its mixer.  Both kinds: ``h <- h +
  residual_multiplier * Mix(N(h; w_in))``, then ``h <- h +
  residual_multiplier * (SiLU(a) * b) W_out`` with ``[a | b] = N(h;
  w_post) W_in`` (``shared_intermediate_size`` each).
* ``mamba``: ``[z | xBC | dt] = u W`` of widths ``d_inner | d_inner + 2 G
  N | heads`` (``d_inner = mamba_n_heads * mamba_d_head``, ``G =
  mamba_n_groups``, ``N = mamba_d_state``); ``xBC <- SiLU(conv(xBC) +
  b)``, a causal depth-wise convolution of ``mamba_d_conv`` taps; ``x``
  [heads, d_head], ``B`` and ``C`` [G, N], head ``h`` reading group ``h
  // (heads / G)``; ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; from ``S = 0``: ``S <- exp(delta A) S + delta x
  B^T``, ``y = S C + D x``; then the gate FIRST, ``y * SiLU(z)``, then
  RMSNorm over each group's ``d_inner / G`` channels; ``out_proj``.
* ``attention``: q ``num_attention_heads x hd``, k and v
  ``num_key_value_heads x hd`` (``hd = hidden_size /
  num_attention_heads``), NO rotary embedding
  (``position_embedding_type`` "nope"); causal softmax of
  ``attention_multiplier * q k^T``; ``o_proj``.
* ``logits = (N(h; w_final) embed^T) / logits_scaling``
  (``tie_word_embeddings``).

Weights.  ``layer_weights`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_mamba_mlp_layers``): the embedding from key 8 of
``split(PRNGKey(seed), 16)``; the layers' tensors from
``split(fold_in(PRNGKey(seed), 51), 32)``, tensor ``j`` of layer ``i``
(its index in the whole stack) from ``fold_in(key j, i)``, normal x 0.02
cast to the served dtype (the taps x 0.5); norm weights and ``D`` at one;
``A_log = log(U[1, 16])``; ``dt_bias`` the inverse softplus of a step
drawn log-uniformly in [0.001, 0.1] floored at 1e-4.  The recipe, not
the code, is shared.  A layer's weights are drawn when the layer runs
and dropped after it: 3.19 B parameters never stand in float32 at once.
``round_to`` (a test's and the tolerance measurement's): the drawn
weights rounded once more, to a narrower type, before the arithmetic.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# which of the 32 split keys draws which tensor
KEYS = {"in_proj": 0, "conv": 1, "conv_bias": 2, "a_log": 3, "dt_bias": 4,
        "out": 5, "q": 8, "k": 9, "v": 10, "o": 11, "gate": 12, "up": 13,
        "down": 14}
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    Hm, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    H = cfg["num_attention_heads"]
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"], "H": H,
        "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
        "Hm": Hm, "P": P, "G": G, "N": N, "di": Hm * P,
        "C": Hm * P + 2 * G * N, "taps": cfg["mamba_d_conv"],
        "F": cfg["shared_intermediate_size"],
    }


def kinds(cfg: Dict[str, Any]) -> List[str]:
    types = cfg["layer_types"]
    assert len(types) == cfg["num_hidden_layers"], types
    assert set(types) <= {"mamba", "attention"}, types
    return list(types)


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int], kind: str) -> Dict[str, tuple]:
    D, F = z["D"], z["F"]
    ff = {"gate": (D, F), "up": (D, F), "down": (F, D)}
    if kind == "mamba":
        return {"in_proj": (D, z["di"] + z["C"] + z["Hm"]),
                "conv": (z["C"], z["taps"]), "conv_bias": (z["C"],),
                "out": (z["di"], D), **ff}
    return {"q": (D, z["H"] * z["hd"]), "k": (D, z["KV"] * z["hd"]),
            "v": (D, z["KV"] * z["hd"]), "o": (z["H"] * z["hd"], D), **ff}


def _rounded(w: jax.Array, dtype, round_to) -> jax.Array:
    w = w.astype(dtype)
    return w if round_to is None else w.astype(round_to).astype(dtype)


def embedding(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16,
              round_to=None) -> jax.Array:
    z = sizes(cfg)
    key = jax.random.split(jax.random.PRNGKey(seed), 16)[8]
    return _rounded(jax.random.normal(key, (z["V"], z["D"]), F32) * 0.02,
                    dtype, round_to)


def layer_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16,
                  round_to=None) -> Iterator[Dict[str, jax.Array]]:
    """One dict a layer, in the stack's order, drawn as it is asked for."""
    z = sizes(cfg)
    gk = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 51), 32)
    for i, kind in enumerate(kinds(cfg)):
        key = lambda name: jax.random.fold_in(gk[KEYS[name]], i)
        w = {name: _rounded(
            jax.random.normal(key(name), shape, F32)
            * (0.5 if name == "conv" else 0.02), dtype, round_to)
             for name, shape in layer_shapes(z, kind).items()}
        if kind == "mamba":
            w["a_log"] = jnp.log(jax.random.uniform(
                key("a_log"), (z["Hm"],), F32, 1.0, 16.0))
            step = jnp.maximum(1e-4, jnp.exp(
                jnp.log(1e-3) + jax.random.uniform(key("dt_bias"), (z["Hm"],))
                * (jnp.log(0.1) - jnp.log(1e-3))))
            w["dt_bias"] = jnp.log(jnp.expm1(step)).astype(F32)
            if not cfg.get("mamba_conv_bias", True):
                del w["conv_bias"]
        yield w


# ------------------------------------------------------ the mathematics

def norm(x: jax.Array, eps: float) -> jax.Array:
    """x / rms(x); every norm's weight is drawn at one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def conv_silu(x: jax.Array, taps: jax.Array, bias: Optional[jax.Array]
              ) -> jax.Array:
    """x: [S, C], taps: [C, K]: y_t = b + sum_j taps[:, j] x_{t-K+1+j},
    nothing before the sequence's start; then SiLU."""
    S, K = x.shape[0], taps.shape[1]
    past = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    y = sum(past[j: j + S] * taps[:, j] for j in range(K))
    return jax.nn.silu(y if bias is None else y + bias)


def selective_scan(x, delta, A, B, C, D):
    """x: [S, H, P]; delta: [S, H] (after the softplus); A, D: [H]; B, C:
    [S, G, N].  One token after another from a state of zeros: y [S, H,
    P]."""
    H, P = x.shape[1:]
    per = H // B.shape[1]

    def token(state, t):
        x_t, d_t, B_t, C_t = t
        B_h, C_h = jnp.repeat(B_t, per, axis=0), jnp.repeat(C_t, per, axis=0)
        state = (jnp.exp(d_t * A)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * B_h[:, None, :])
        y = jnp.sum(state * C_h[:, None, :], axis=-1) + D[:, None] * x_t
        return state, y

    zeros = jnp.zeros((H, P, B.shape[2]), F32)
    return jax.lax.scan(token, zeros, (x, delta, B, C))[1]


def mamba(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
          ) -> jax.Array:
    z = sizes(cfg)
    S, di, G, N = u.shape[0], z["di"], z["G"], z["N"]
    proj = u @ w["in_proj"]
    gate, xbc, dt = (proj[:, :di], proj[:, di: di + z["C"]],
                     proj[:, di + z["C"]:])
    xbc = conv_silu(xbc, w["conv"], w.get("conv_bias"))
    x = xbc[:, :di].reshape(S, z["Hm"], z["P"])
    B = xbc[:, di: di + G * N].reshape(S, G, N)
    C = xbc[:, di + G * N:].reshape(S, G, N)
    delta = jax.nn.softplus(dt + w["dt_bias"])
    skip = jnp.ones((z["Hm"],), F32)  # D, drawn at one
    y = selective_scan(x, delta, -jnp.exp(w["a_log"]), B, C, skip)
    y = y.reshape(S, di) * jax.nn.silu(gate)  # the gate FIRST
    y = norm(y.reshape(S, G, di // G), cfg["rms_norm_eps"]).reshape(S, di)
    return y @ w["out"]  # the norm's weight is drawn at one


def attention(u: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
              ) -> jax.Array:
    z = sizes(cfg)
    H, KV, hd, S = z["H"], z["KV"], z["hd"], u.shape[0]
    q = (u @ w["q"]).reshape(S, H, hd)
    k = jnp.repeat((u @ w["k"]).reshape(S, KV, hd), H // KV, axis=1)
    v = jnp.repeat((u @ w["v"]).reshape(S, KV, hd), H // KV, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * cfg["attention_multiplier"]
    seen = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    return jnp.einsum("hst,thd->shd", probs, v).reshape(S, H * hd) @ w["o"]


def swiglu(u: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def layer(h: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          kind: str) -> jax.Array:
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mix = mamba if kind == "mamba" else attention
    h = h + r * mix(norm(h, eps), w, cfg)  # the norms' weights: one
    return h + r * swiglu(norm(h, eps), w)


def logprobs(cfg: Dict[str, Any], seed: int, dtype,
             sequences: List[List[int]], first: List[int], round_to=None
             ) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``."""
    with jax.default_matmul_precision("highest"):
        embed = embedding(cfg, seed, dtype, round_to).astype(F32)
        hs = [cfg["embedding_multiplier"] * embed[jnp.asarray(s)]
              for s in sequences]
        for kind, lw in zip(kinds(cfg),
                            layer_weights(cfg, seed, dtype, round_to)):
            w = {k: v.astype(F32) for k, v in lw.items()}
            hs = [layer(h, w, cfg, kind) for h in hs]
        out = []
        for h, s, f in zip(hs, sequences, first):
            rows = norm(h[f - 1: len(s) - 1], cfg["rms_norm_eps"])
            logits = (rows @ embed.T) / cfg["logits_scaling"]
            out.append(np.asarray(jax.nn.log_softmax(logits, axis=-1)))
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
