"""Plain reference: the Qwen2 decoder block in straightforward
``jax.numpy`` float32 -- no kernels, no cache, no batching -- following
the published description (Qwen2ForCausalLM): pre-norm residual blocks,
RMSNorm, grouped-query attention with q/k/v biases and rotate-half RoPE,
SwiGLU MLP, final norm, (tied) output head.  Independent of
``vgate_tpu/models/decoder.py``: it shares no code with it.

Runs in a process of its own pinned to the CPU backend (the server child
holds the chip):

    JAX_PLATFORMS=cpu python -m perfbench.reference CONFIG TOKENS OUT

Weights.  The program serves random weights and has no way to export
them, so the reference draws the same ones: ``draw_weights`` repeats the
recipe of ``models/decoder.py init_params`` (PRNGKey(seed) split 16
ways, normal * 0.02 cast to bf16, norms 1, biases 0; threefry is
identical on every backend).  The recipe, not the code, is shared; a
program change that draws other weights fails the comparison, and that
is the intent.  Arithmetic here is float32 at highest precision on the
bf16-rounded weights, one layer at a time.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# which of the 16 split keys draws which tensor (init_params' order)
KEY_INDEX = {"q": 0, "k": 1, "v": 2, "o": 3, "gate": 5, "up": 6,
             "down": 7, "embed": 8, "lm_head": 9}


def shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "q": (L, D, H * hd), "k": (L, D, KV * hd), "v": (L, D, KV * hd),
        "o": (L, H * hd, D), "gate": (L, D, F), "up": (L, D, F),
        "down": (L, F, D), "embed": (V, D),
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def draw_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    return {
        name: (jax.random.normal(keys[KEY_INDEX[name]], shape, jnp.float32)
               * 0.02).astype(dtype)
        for name, shape in shapes(cfg).items()
    }


def rms_norm(x: jax.Array, eps: float) -> jax.Array:
    # weight is all ones in these weights
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, hd]; rotate-half, positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
          ) -> jax.Array:
    """One decoder block on one sequence, x: [S, D] float32.  q/k/v
    biases are zero in these weights and left out."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = x.shape[0]
    h = rms_norm(x, eps)
    q = rope((h @ w["q"]).reshape(S, H, hd), theta)
    k = rope((h @ w["k"]).reshape(S, KV, hd), theta)
    v = (h @ w["v"]).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)  # grouped-query: share KV heads
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(S, H * hd) @ w["o"]
    h = rms_norm(x, eps)
    return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


def logprobs(cfg: Dict[str, Any], weights: Dict[str, jax.Array],
             sequences: List[List[int]], first: List[int]
             ) -> List[np.ndarray]:
    """Log-softmax over the vocabulary at positions ``first[i]-1 ..
    len-2`` of sequence i: the distributions that predicted tokens
    ``first[i] .. len-1``.  Full forward, no cache."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][jnp.asarray(s)].astype(f32) for s in sequences]
        for l in range(cfg["num_hidden_layers"]):
            w = {n: weights[n][l].astype(f32)
                 for n in ("q", "k", "v", "o", "gate", "up", "down")}
            xs = [layer(x, w, cfg) for x in xs]
        head = (weights["embed"].T if cfg["tie_word_embeddings"]
                else weights["lm_head"]).astype(f32)
        out = []
        for x, s, f in zip(xs, sequences, first):
            h = rms_norm(x[f - 1: len(s) - 1], cfg["rms_norm_eps"])
            out.append(np.asarray(jax.nn.log_softmax(h @ head, axis=-1)))
    return out


def main(argv: List[str]) -> int:
    config_path, tokens_path, out_path = argv
    with open(config_path) as fh:
        cfg = json.load(fh)
    with open(tokens_path) as fh:
        job = json.load(fh)
    dtype = (jnp.float32 if cfg.get("torch_dtype") == "float32"
             else jnp.bfloat16)  # the type the server holds them in
    weights = draw_weights(cfg, int(job["weights_seed"]), dtype)
    lps = logprobs(cfg, weights, job["sequences"], job["first"])
    # keep only what the comparison reads: the served top-k ids' values
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
