"""Plain reference: the Nemotron-H decoder (Nemotron 3 Super) in
straightforward ``jax.numpy`` float32 -- no kernels, no cache, no
batching, the Mamba-2 recurrence token by token, attention as one
masked softmax, every held expert applied to the tokens that chose it.
It shares no code with ``vgate_tpu/`` and no mathematics with another
family's reference.

    JAX_PLATFORMS=cpu python -m perfbench.references.nemotron_h CONFIG JOB OUT

(``perfbench/README.md`` has the protocol.)  The mathematics, from the
catalog row's ``config`` and the module structure of ``transformers``'
``modeling_nemotron_h.py`` and ``mamba_ssm``'s Mamba-2 as the writer
knows them (no network here; each point the config does not itself state
is listed under ``assumed`` in the configuration file).  eps =
``norm_eps`` everywhere; ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``,
the PLAIN weight.

* ``hybrid_override_pattern`` gives each layer ONE kind: ``M`` Mamba-2,
  ``E`` expert layer, ``*`` attention.  Every layer is ``h <- h +
  F_kind(N(h; w_l))``; then ``N(h; w_f)`` and an untied head.
* ``M``: ``x W_in`` -> ``[z (d_inner) | xBC (d_inner + 2 G N) | dt
  (heads)]``, ``d_inner = mamba_num_heads * mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``.  ``xBC <- SiLU(conv(xBC) + b)``:
  causal depth-wise convolution of ``conv_kernel`` taps, with bias.
  Split ``x`` [heads, head_dim], ``B``, ``C`` [G, N]; head ``h`` reads
  group ``h // (heads / G)``.  ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a scalar a head.  Per head, ``S = 0`` in ``R^{P x N}``:
  ``S <- exp(dt A) S + dt x B^T``; ``y = S C + D x``.  Then ``y <-
  N_grouped(y * SiLU(z); w_n)``: the gate FIRST, then RMSNorm over each
  group's ``d_inner / G`` channels; ``out = y W_out``.
* ``*``: q ``num_attention_heads x head_dim``, k and v
  ``num_key_value_heads x head_dim``, no biases, no q/k norm, no gate,
  NO rotary embedding (``assumed.rotary``); causal softmax attention,
  scale ``head_dim ** -0.5``; ``out = attn W_o``.
* ``E`` (LatentMoE): ``s = sigmoid(x W_r)`` over the router's full
  width; the top ``num_experts_per_tok`` of ``s + b``
  (``e_score_correction_bias``); weights ``s`` of the chosen (WITHOUT
  ``b``), ``w <- w / (sum w + 1e-20)``, ``w <- routed_scaling_factor
  w``.  ``u = x W_lin`` (hidden -> ``moe_latent_size``); ``r = sum_e w_e
  E_e(u)`` over the chosen experts that are HELD (``n_routed_experts``
  of them from ``first_expert``: what the absent ones would add is left
  out, model-configs guide section 4), ``E(u) = relu(u W_up)^2 W_down``;
  ``routed = r W_lout``; ``shared = relu(x V_up)^2 V_down``; ``out =
  routed + shared``.
* Left out: the multi-token-prediction module (``assumed.mtp``).

Weights.  ``draw_weights`` repeats the recipe of the program's
``init_params`` for this family (``models/hybrid.py
_init_pattern_layers``): embedding and head from keys 8 and 9 of
``split(PRNGKey(seed), 16)``; the layers' tensors from
``split(fold_in(PRNGKey(seed), 31), 32)``, tensor ``j`` of the i-th
layer of its kind from ``fold_in(key j, i)``, normal x 0.02 cast to the
served dtype (the convolution's taps x 0.5); norm weights and ``D`` at
one; ``A_log = log(U[1, 16])``; ``dt_bias`` the inverse softplus of a
step drawn log-uniformly in [``time_step_min``, ``time_step_max``],
floored at ``time_step_floor``; the router's bias normal x 0.02 in
float32.  The recipe, not the code, is shared.  Arithmetic is float32
at highest precision on the served-dtype weights, one layer's float32
weights at a time, so that 4.6 B parameters in float32 never stand in
memory at once.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
# which of the 32 split keys draws which tensor
KEYS = {"in_proj": 0, "conv": 1, "conv_bias": 2, "a_log": 3, "dt_bias": 4,
        "out": 5, "q": 8, "k": 9, "v": 10, "o": 11, "router": 16,
        "router_bias": 17, "latent_in": 18, "latent_out": 19, "up": 20,
        "down": 21, "shared_up": 22, "shared_down": 23}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    Hm, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "Hm": Hm, "P": P, "G": G, "N": N,
        "di": Hm * P, "C": Hm * P + 2 * G * N, "taps": cfg["conv_kernel"],
        "E": cfg["n_routed_experts"],
        "R": cfg.get("router_width") or cfg["n_routed_experts"],
        "first": cfg.get("first_expert", 0),
        "K": cfg["num_experts_per_tok"], "W": cfg["moe_latent_size"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg["moe_shared_expert_intermediate_size"],
    }


def kinds(cfg: Dict[str, Any]) -> List[str]:
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"], pattern
    return [KINDS[c] for c in pattern]


# ----------------------------------------------------------- the weights

def layer_shapes(z: Dict[str, int], kind: str) -> Dict[str, tuple]:
    D = z["D"]
    if kind == "mamba":
        return {"in_proj": (D, z["di"] + z["C"] + z["Hm"]),
                "conv": (z["C"], z["taps"]), "conv_bias": (z["C"],),
                "out": (z["di"], D)}
    if kind == "attn":
        return {"q": (D, z["H"] * z["hd"]), "k": (D, z["KV"] * z["hd"]),
                "v": (D, z["KV"] * z["hd"]), "o": (z["H"] * z["hd"], D)}
    return {"router": (D, z["R"]), "latent_in": (D, z["W"]),
            "latent_out": (z["W"], D), "up": (z["E"], z["W"], z["Fe"]),
            "down": (z["E"], z["Fe"], z["W"]), "shared_up": (D, z["Fs"]),
            "shared_down": (z["Fs"], D)}


def draw_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, Any]:
    """{"embed", "lm_head", "layers": [one dict a layer]} by the
    program's recipe."""
    z = sizes(cfg)
    root = jax.random.PRNGKey(seed)
    keys = jax.random.split(root, 16)
    nk = jax.random.split(jax.random.fold_in(root, 31), 32)

    def normal(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, F32) * scale).astype(dtype)

    layers, seen = [], {}
    for kind in kinds(cfg):
        i = seen.get(kind, 0)  # the layer's index among those of its kind
        seen[kind] = i + 1
        key = lambda name: jax.random.fold_in(nk[KEYS[name]], i)
        w = {name: normal(key(name), shape,
                          0.5 if name == "conv" else 0.02)
             for name, shape in layer_shapes(z, kind).items()}
        if kind == "mamba":
            w["a_log"] = jnp.log(jax.random.uniform(
                key("a_log"), (z["Hm"],), F32, 1.0, 16.0))
            lo, hi = cfg["time_step_min"], cfg["time_step_max"]
            step = jnp.maximum(cfg["time_step_floor"], jnp.exp(
                jnp.log(lo) + jax.random.uniform(key("dt_bias"), (z["Hm"],))
                * (jnp.log(hi) - jnp.log(lo))))
            w["dt_bias"] = jnp.log(jnp.expm1(step)).astype(F32)
            if not cfg.get("use_conv_bias", True):
                del w["conv_bias"]
        if kind == "moe":
            w["router_bias"] = jax.random.normal(
                key("router_bias"), (z["R"],), F32) * 0.02
        layers.append(w)
    return {
        "embed": normal(keys[8], (z["V"], z["D"])),
        "lm_head": normal(keys[9], (z["D"], z["V"])),
        "layers": layers,
    }


# ------------------------------------------------------ the mathematics

def norm(x: jax.Array, eps: float, w: Optional[jax.Array] = None
         ) -> jax.Array:
    """x / rms(x) * w; w = 1 (identity) when the weights have none."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * w


def causal_conv_silu(x: jax.Array, w: jax.Array,
                     bias: Optional[jax.Array]) -> jax.Array:
    """x: [S, C], w: [C, taps]; y_t = b + sum_j w[:, j] x_{t-(taps-1)+j}
    with zeros before the sequence's start, then SiLU."""
    S, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    y = sum(padded[j: j + S] * w[:, j] for j in range(taps))
    return jax.nn.silu(y if bias is None else y + bias)


def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array,
             state: Optional[jax.Array] = None):
    """The recurrence, token by token.  x: [S, H, P]; dt: [S, H] (after
    the softplus); A, D: [H]; B, C: [S, G, N].  Returns (y [S, H, P],
    final S [H, P, N])."""
    H, P = x.shape[1:]
    R = H // B.shape[1]
    S_ = jnp.zeros((H, P, B.shape[2]), F32) if state is None else state
    out = []
    for t in range(x.shape[0]):
        B_t, C_t = jnp.repeat(B[t], R, axis=0), jnp.repeat(C[t], R, axis=0)
        S_ = (S_ * jnp.exp(dt[t] * A)[:, None, None]
              + (dt[t][:, None] * x[t])[:, :, None] * B_t[:, None, :])
        out.append(jnp.einsum("hpn,hn->hp", S_, C_t) + D[:, None] * x[t])
    return jnp.stack(out), S_


def gated_group_norm(y: jax.Array, z: jax.Array, w: Optional[jax.Array],
                     groups: int, eps: float) -> jax.Array:
    """y, z: [S, d_inner].  The gate FIRST, then RMSNorm over each of
    the ``groups`` groups of channels, then the plain weight."""
    y = y * jax.nn.silu(z)
    S, di = y.shape
    y = norm(y.reshape(S, groups, di // groups), eps).reshape(S, di)
    return y if w is None else y * w


def mamba(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
          ) -> jax.Array:
    z = sizes(cfg)
    S, di, G, N = x.shape[0], z["di"], z["G"], z["N"]
    proj = x @ w["in_proj"]
    gate, xbc, dt = (proj[:, :di], proj[:, di: di + z["C"]],
                     proj[:, di + z["C"]:])
    xbc = causal_conv_silu(xbc, w["conv"], w.get("conv_bias"))
    xs = xbc[:, :di].reshape(S, z["Hm"], z["P"])
    B = xbc[:, di: di + G * N].reshape(S, G, N)
    C = xbc[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    D = w["d"] if "d" in w else jnp.ones((z["Hm"],), F32)
    y, _ = ssm_scan(xs, dt, -jnp.exp(w["a_log"]), B, C, D)
    y = gated_group_norm(y.reshape(S, di), gate, w.get("ssm_norm"), G,
                         cfg["norm_eps"])
    return y @ w["out"]


def attention(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]
              ) -> jax.Array:
    z = sizes(cfg)
    H, KV, hd, S = z["H"], z["KV"], z["hd"], x.shape[0]
    q = (x @ w["q"]).reshape(S, H, hd)
    k = jnp.repeat((x @ w["k"]).reshape(S, KV, hd), H // KV, axis=1)
    v = jnp.repeat((x @ w["v"]).reshape(S, KV, hd), H // KV, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return attn.reshape(S, H * hd) @ w["o"]


def route(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any]):
    """(chosen experts [S, K] among the router's width, their weights
    [S, K]): chosen by ``s + b``, weighted by ``s`` alone."""
    scores = jax.nn.sigmoid(x @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return np.asarray(idx), np.asarray(vals * cfg["routed_scaling_factor"])


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def moe(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
        shared: bool = True) -> jax.Array:
    """x: [S, D].  The held experts' part of the routed sum through the
    latent, plus the shared expert (``shared`` False leaves it out: a
    test adds the shares of several chips and counts it once)."""
    z = sizes(cfg)
    idx, vals = route(x, w, cfg)
    u = x @ w["latent_in"]
    r = jnp.zeros_like(u)
    for e in range(z["E"]):  # every held expert, its own tokens
        chose = idx == z["first"] + e  # [S, K]
        rows = np.nonzero(chose.any(axis=1))[0]
        if rows.size == 0:
            continue
        weight = jnp.asarray((vals * chose).sum(axis=1)[rows])
        y = relu2(u[rows] @ w["up"][e]) @ w["down"][e]
        r = r.at[rows].add(weight[:, None] * y)
    out = r @ w["latent_out"]
    if shared and z["Fs"]:
        out = out + relu2(x @ w["shared_up"]) @ w["shared_down"]
    return out


MIXERS = {"mamba": mamba, "attn": attention, "moe": moe}


def layer(x: jax.Array, w: Dict[str, jax.Array], cfg: Dict[str, Any],
          kind: str) -> jax.Array:
    return x + MIXERS[kind](norm(x, cfg["norm_eps"], w.get("norm")), w, cfg)


def hidden_states(cfg: Dict[str, Any], weights: Dict[str, Any],
                  sequences: List[List[int]]) -> List[jax.Array]:
    """Final-norm inputs [S, D] of every sequence: the whole stack, one
    layer's float32 weights at a time."""
    xs = [weights["embed"][jnp.asarray(s)].astype(F32) for s in sequences]
    for kind, lw in zip(kinds(cfg), weights["layers"]):
        w = {k: v.astype(F32) for k, v in lw.items()}
        xs = [layer(x, w, cfg, kind) for x in xs]
    return xs


def logprobs(cfg: Dict[str, Any], weights: Dict[str, Any],
             sequences: List[List[int]], first: List[int]
             ) -> List[np.ndarray]:
    """Log-softmax at positions ``first[i]-1 .. len-2`` of sequence i:
    the distributions that predicted tokens ``first[i] .. len-1``."""
    with jax.default_matmul_precision("highest"):
        head = weights["lm_head"].astype(F32)
        fw = weights.get("final_norm")
        out = []
        for x, s, f in zip(hidden_states(cfg, weights, sequences),
                           sequences, first):
            h = norm(x[f - 1: len(s) - 1], cfg["norm_eps"],
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
