"""The layer stack of two kinds (Qwen3-Next): Gated DeltaNet linear
attention in three layers of four, gated softmax attention in the
fourth, an expert layer behind each.

``models/decoder.py``'s forwards hand a hybrid spec's work here after
they have chosen the attention implementation, so the step programs,
their cache threading and the kernels are the dense models' own.  What
differs:

* the layer scan walks PERIODS (``spec.full_attention_interval`` layers:
  the linear ones unrolled, then the full one), with the K/V pools of
  the full layers only (``[periods, KV, P, ps, hd]``) and the recurrent
  state of the linear ones riding the carry, all updated in place;
* the recurrent state (``{"S": [Lr, slots, Hv, dk, dv] float32, "conv":
  [Lr, slots, K-1, C]}``) is indexed by decode SLOT.  A prompt pass
  starts from zeros (or, for a later chunk of a chunked prefill, from
  the slot's row), runs the chunk-wise recurrence and overwrites the
  row whole; a decode step updates the rows of active slots in place and
  leaves idle rows alone.  Padded prompt positions get ``g = 0, beta =
  0`` and the convolution tail is taken at the row's real length, so a
  bucket's padding never moves the state;
* the experts' matrices never ride the scan's per-period slices: the
  grouped product's kernel takes the full stack and a layer index.

Parameters (``init_params``): ``layers = {"linear": {... [P, n, ...]},
"full": {... [P, ...]}}`` with ``P`` periods and ``n`` linear layers a
period.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.ops import gated_delta as gd
from vgate_tpu.ops.kv_quant import kv_write_pages
from vgate_tpu.ops.moe import STAT_NAMES, combine_stats, expert_layer
from vgate_tpu.ops.norms import rms_norm
from vgate_tpu.ops.rope import apply_rope

EXPERT_STACKS = ("gate", "up", "down")


def init_layers(spec: ModelSpec, key, dtype, normal, norm_init
                ) -> Dict[str, Any]:
    """Random draw of the hybrid family's layer tensors, from keys of
    its own (``fold_in(key, 27)`` split 32 ways: the dense and Mixtral
    draws stay what they were).  ``dt_bias`` is drawn so that a head's
    per-step decay ``exp(g)`` lies log-uniformly between about 0.5 and
    0.999 (at ``a = 0``): with every head forgetting in a token or two,
    neither a wrong state nor a stale one could show in a comparison."""
    hk = jax.random.split(jax.random.fold_in(key, 27), 32)
    D, P, n = spec.hidden_size, spec.num_periods, spec.linear_per_period
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    Fs = spec.shared_expert_intermediate_size
    Hv, dv = spec.linear_num_value_heads, spec.linear_value_head_dim
    kd, vd, C = spec.linear_key_dim, spec.linear_value_dim, spec.linear_conv_dim
    K = spec.linear_conv_kernel_dim

    def moe(lead, ks):
        out = {
            "router": normal(ks[0], lead + (D, R)),
            "gate": {"w": normal(ks[1], lead + (E, D, Fe))},
            "up": {"w": normal(ks[2], lead + (E, D, Fe))},
            "down": {"w": normal(ks[3], lead + (E, Fe, D))},
        }
        if Fs:
            out.update({
                "shared_gate": {"w": normal(ks[4], lead + (D, Fs))},
                "shared_up": {"w": normal(ks[5], lead + (D, Fs))},
                "shared_down": {"w": normal(ks[6], lead + (Fs, D))},
                "shared_router": normal(ks[7], lead + (D,)),
            })
        return out

    q_width = H * hd * (2 if spec.attn_output_gate else 1)
    full = {
        "input_norm": norm_init((P, D), dtype),
        "post_norm": norm_init((P, D), dtype),
        "q": {"w": normal(hk[0], (P, D, q_width))},
        "k": {"w": normal(hk[1], (P, D, KV * hd))},
        "v": {"w": normal(hk[2], (P, D, KV * hd))},
        "o": {"w": normal(hk[3], (P, H * hd, D))},
        **moe((P,), hk[4:12]),
    }
    if spec.qk_norm:
        full["q_norm"] = norm_init((P, hd), dtype)
        full["k_norm"] = norm_init((P, hd), dtype)
    lead = (P, n)
    rate = jnp.exp(
        jnp.log(1e-3) + jax.random.uniform(hk[16], lead + (Hv,))
        * (jnp.log(0.693) - jnp.log(1e-3))
    )  # -log(decay) a step at a = 0
    linear = {
        "input_norm": norm_init(lead + (D,), dtype),
        "post_norm": norm_init(lead + (D,), dtype),
        "in_qkvz": {"w": normal(hk[12], lead + (D, C + vd))},
        "in_ba": {"w": normal(hk[13], lead + (D, 2 * Hv))},
        "conv": normal(hk[14], lead + (C, K), scale=0.5),
        "a_log": jax.random.normal(hk[15], lead + (Hv,), jnp.float32) * 0.02,
        "dt_bias": jnp.log(jnp.expm1(rate)).astype(jnp.float32),
        "gdn_norm": jnp.ones(lead + (dv,), dtype),
        "out": {"w": normal(hk[17], lead + (vd, D))},
        **moe(lead, hk[18:26]),
    }
    return {"linear": linear, "full": full}


def make_state(spec: ModelSpec, slots: int, dtype) -> Dict[str, jax.Array]:
    """The recurrent state of every linear layer, zeros, one row a slot."""
    Lr = spec.linear_layers
    return {
        "S": jnp.zeros(
            (Lr, slots, spec.linear_num_value_heads,
             spec.linear_key_head_dim, spec.linear_value_head_dim),
            jnp.float32,
        ),
        "conv": jnp.zeros(
            (Lr, slots, spec.linear_conv_kernel_dim - 1,
             spec.linear_conv_dim), dtype,
        ),
    }


def state_bytes_per_slot(spec: ModelSpec, dtype_bytes: int) -> int:
    """Bytes one slot's row holds over all linear layers."""
    tile = (spec.linear_num_value_heads * spec.linear_key_head_dim
            * spec.linear_value_head_dim * 4)
    tail = ((spec.linear_conv_kernel_dim - 1) * spec.linear_conv_dim
            * dtype_bytes)
    return spec.linear_layers * (tile + tail)


def _rope(x, positions, spec: ModelSpec):
    return apply_rope(x, positions, spec.rope_theta, spec.rope_scaling,
                      rotary_dim=spec.rotary_dim)


@jax.named_scope("qkv")
def _gated_qkv(h, lp, spec: ModelSpec, positions):
    """Full-attention front half: norm, q (with its gate beside it, per
    head ``[query | gate]``), k, v, per-head norms on q and k, partial
    rope.  h: [..., S, D] with positions [..., S]."""
    eps, uo = spec.rms_eps, spec.unit_offset_norm
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    normed = rms_norm(h, lp["input_norm"], eps, uo)
    q = jnp.einsum("...d,dh->...h", normed, lp["q"]["w"])
    k = jnp.einsum("...d,dh->...h", normed, lp["k"]["w"])
    v = jnp.einsum("...d,dh->...h", normed, lp["v"]["w"])
    gate = None
    if spec.attn_output_gate:
        q = q.reshape(*q.shape[:-1], H, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
    else:
        q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], KV, hd)
    v = v.reshape(*v.shape[:-1], KV, hd)
    if spec.qk_norm:
        q = rms_norm(q, lp["q_norm"], eps, uo)
        k = rms_norm(k, lp["k_norm"], eps, uo)
    return _rope(q, positions, spec), _rope(k, positions, spec), v, gate


@jax.named_scope("o_proj")
def _gated_out(attn, gate, lp, dtype):
    if gate is not None:
        attn = (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    attn = attn.reshape(*attn.shape[:-2], -1)
    return jnp.einsum("...h,hd->...d", attn, lp["o"]["w"])


def _finish(h, mixer_out, lp, spec: ModelSpec, row_mask, use_pallas,
            layer, stack):
    h = h + mixer_out.astype(h.dtype)
    normed = rms_norm(h, lp["post_norm"], spec.rms_eps,
                      spec.unit_offset_norm)
    out, stats = expert_layer(
        normed, lp, spec, jax.nn.silu, row_mask=row_mask, use_pallas=use_pallas,
        layer=layer, stack=stack,
    )
    return h + out, stats


def _linear_inputs(h, lp, spec: ModelSpec):
    """Norm and the two input projections of a linear layer: the
    pre-convolution (q, k, v) channels, z, b and a."""
    C, Hv = spec.linear_conv_dim, spec.linear_num_value_heads
    normed = rms_norm(h, lp["input_norm"], spec.rms_eps,
                      spec.unit_offset_norm)
    qkvz = jnp.einsum("...d,dc->...c", normed, lp["in_qkvz"]["w"])
    ba = jnp.einsum("...d,dc->...c", normed, lp["in_ba"]["w"])
    return qkvz[..., :C], qkvz[..., C:], ba[..., :Hv], ba[..., Hv:]


def _linear_heads(y, spec: ModelSpec):
    """Post-convolution channels -> q, k ([..., Hv, dk], normalised, q
    scaled, key heads repeated to the value heads) and v [..., Hv, dv]."""
    Hk, Hv = spec.linear_num_key_heads, spec.linear_num_value_heads
    dk, dv, kd = (spec.linear_key_head_dim, spec.linear_value_head_dim,
                  spec.linear_key_dim)
    lead = y.shape[:-1]
    q = gd.l2_normalize(y[..., :kd].reshape(*lead, Hk, dk)) * dk ** -0.5
    k = gd.l2_normalize(y[..., kd:2 * kd].reshape(*lead, Hk, dk))
    v = y[..., 2 * kd:].reshape(*lead, Hv, dv)
    rep = Hv // Hk
    return (jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2),
            v.astype(jnp.float32))


def _linear_out(o, z, lp, spec: ModelSpec, dtype):
    """Per-head RMSNorm with the PLAIN weight, gated by SiLU(z), then
    the output projection."""
    Hv, dv = spec.linear_num_value_heads, spec.linear_value_head_dim
    z = z.reshape(*z.shape[:-1], Hv, dv).astype(jnp.float32)
    o = rms_norm(o, lp["gdn_norm"], spec.rms_eps, False) * jax.nn.silu(z)
    o = o.reshape(*o.shape[:-2], Hv * dv).astype(dtype)
    return jnp.einsum("...v,vd->...d", o, lp["out"]["w"])


# prompt tokens a linear layer's mixer takes at once: its float32
# temporaries are ~12 x tokens x value heads x head size x 4 bytes
PROMPT_BLOCK_TOKENS = 4096


def _linear_rows(h, lp, spec: ModelSpec, lens, tail, S0):
    """The mixer over rows h [B, S, D] from (tail, S0): returns (out,
    final state, final tail).  Padded positions move nothing."""
    S = h.shape[1]
    qkv, z, b, a = _linear_inputs(h, lp, spec)
    with jax.named_scope("conv"):
        y, new_tail = gd.causal_conv(qkv, tail, lp["conv"], lens)
    q, k, v = _linear_heads(y, spec)
    g, beta = gd.gates(a, b, lp["a_log"], lp["dt_bias"])
    valid = (jnp.arange(S)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    o, S1 = gd.gated_delta_chunked(q, k, v, g, beta, S0)
    return _linear_out(o, z, lp, spec, h.dtype), S1, new_tail


def _linear_prompt(h, lp, st, li, spec: ModelSpec, lens, slots, fresh):
    """A linear layer's mixer over prompt rows h [B, S, D]: starts from
    the slot's row (zeros where ``fresh``), ends with the row
    overwritten whole.  A wide wave goes through in groups of rows."""
    with jax.named_scope("linear_attn"):
        B, S = h.shape[:2]
        keep = jnp.logical_not(fresh)
        tail = jnp.where(keep[:, None, None], st["conv"][li][slots], 0)
        S0 = jnp.where(keep[:, None, None, None], st["S"][li][slots], 0.0)
        rows = max(1, PROMPT_BLOCK_TOKENS // S)
        # groups of rows unrolled (see ops/moe.py expert_layer)
        parts = [
            _linear_rows(h[lo:lo + rows], lp, spec, lens[lo:lo + rows],
                         tail[lo:lo + rows], S0[lo:lo + rows])
            for lo in range(0, B, rows)
        ]
        out, S1, new_tail = (
            jnp.concatenate([p[i] for p in parts]) for i in range(3)
        )
        st = {
            "S": st["S"].at[li, slots].set(S1, mode="drop"),
            "conv": st["conv"].at[li, slots].set(
                new_tail.astype(st["conv"].dtype), mode="drop"),
        }
    return out, st


def _linear_step(h, lp, st, li, spec: ModelSpec, active, use_pallas):
    """A linear layer's mixer for one decode step, h [B, D], row = slot."""
    with jax.named_scope("linear_attn"):
        qkv, z, b, a = _linear_inputs(h, lp, spec)
        with jax.named_scope("conv"):
            tail = st["conv"][li]  # [B, K-1, C]
            cat = jnp.concatenate([tail, qkv[:, None].astype(tail.dtype)], 1)
            w32 = lp["conv"].astype(jnp.float32)
            y = jax.nn.silu(jnp.einsum(
                "bkc,ck->bc", cat.astype(jnp.float32), w32)).astype(h.dtype)
            new_tail = jnp.where(active[:, None, None], cat[:, 1:], tail)
            conv = st["conv"].at[li].set(new_tail)
        q, k, v = _linear_heads(y, spec)
        g, beta = gd.gates(a, b, lp["a_log"], lp["dt_bias"])
        g = jnp.where(active[:, None], g, 0.0)
        beta = jnp.where(active[:, None], beta, 0.0)
        o, S = gd.gated_delta_step(q, k, v, g, beta, st["S"], li,
                                   use_pallas=use_pallas)
        out = _linear_out(o, z, lp, spec, h.dtype)
    return out, {"S": S, "conv": conv}


def _period_scan(params, spec: ModelSpec, x0, k_pages, v_pages, state,
                 linear_fn, full_fn):
    """Scan over periods.  ``linear_fn(h, lp, st, li, stack)`` ->
    ``(h, st, stats)`` runs one linear layer (``li`` its index among the
    linear layers); ``full_fn(h, lp, kp, vp, p, stack)`` -> ``(h, kp,
    vp, stats)`` the period's full-attention layer against the FULL
    pools.  The experts' stacks stay outside the scanned slices.
    Returns (x, k_pages, v_pages, state, stats [4])."""
    layers = params["layers"]
    n = spec.linear_per_period
    light = lambda d: {k: v for k, v in d.items() if k not in EXPERT_STACKS}
    flat = lambda w: jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), w)
    lin_stack = {k: flat(layers["linear"][k]) for k in EXPERT_STACKS}
    full_stack = {k: layers["full"][k] for k in EXPERT_STACKS}

    def fn(carry, xs):
        h, kp, vp, st = carry
        lin_p, full_p, p = xs

        # the period's linear layers as an inner scan: one body traced,
        # lowered and compiled for the three of them
        def lin_fn(c, per_layer):
            lp, j = per_layer
            h_, st_, s = linear_fn(c[0], lp, c[1], p * n + j, lin_stack)
            return (h_, st_), s

        (h, st), lin_stats = jax.lax.scan(
            lin_fn, (h, st), (lin_p, jnp.arange(n, dtype=jnp.int32)))
        h, kp, vp, s = full_fn(h, full_p, kp, vp, p, full_stack)
        return (h, kp, vp, st), jnp.concatenate([lin_stats, s[None]])

    (x, k_pages, v_pages, state), stats = jax.lax.scan(
        fn, (x0, k_pages, v_pages, state),
        (light(layers["linear"]), light(layers["full"]),
         jnp.arange(spec.num_periods, dtype=jnp.int32)),
    )
    stats = combine_stats(stats.reshape(-1, len(STAT_NAMES)))
    return x, k_pages, v_pages, state, stats


def prompt_forward(params, spec: ModelSpec, x, lens, positions, k_pages,
                   v_pages, state, slots, fresh, write_tables, attend,
                   use_pallas: bool):
    """The prompt pass over embedded rows x [B, S, D] (a whole prompt,
    or the suffix / one chunk of one).  ``write_tables`` are the pages
    the rows' K/V go to (whole pages from the rows' first position);
    ``attend(q, k, v, kp, vp, layer)`` is the attention the caller
    chose.  Returns (x, k_pages, v_pages, state)."""
    B, S = x.shape[:2]
    ps = k_pages.shape[-2]
    KV, hd = spec.num_kv_heads, spec.head_dim
    n_pages = S // ps
    row_mask = jnp.arange(S)[None, :] < lens[:, None]
    to_pages = lambda t: jnp.transpose(
        t.reshape(B, n_pages, ps, KV, hd), (0, 1, 3, 2, 4))

    def linear_fn(h, lp, st, li, stack):
        out, st = _linear_prompt(h, lp, st, li, spec, lens, slots, fresh)
        h, stats = _finish(h, out, lp, spec, row_mask, use_pallas, li, stack)
        return h, st, stats

    def full_fn(h, lp, kp, vp, p, stack):
        with jax.named_scope("gated_attn"):
            q, k, v, gate = _gated_qkv(h, lp, spec, positions)
            pt = write_tables[:, :n_pages]
            kp = kv_write_pages(kp, pt, to_pages(k), layer=p)
            vp = kv_write_pages(vp, pt, to_pages(v), layer=p)
            with jax.named_scope("attention"):
                attn = attend(q, k, v, kp, vp, p)
            out = _gated_out(attn, gate, lp, h.dtype)
        h, stats = _finish(h, out, lp, spec, row_mask, use_pallas, p, stack)
        return h, kp, vp, stats

    x, k_pages, v_pages, state, _stats = _period_scan(
        params, spec, x, k_pages, v_pages, state, linear_fn, full_fn)
    return x, k_pages, v_pages, state


def decode_forward(params, spec: ModelSpec, x, positions, k_pages, v_pages,
                   state, active, write_attend, use_pallas: bool):
    """One decode step over embedded rows x [B, D], row = slot.
    ``write_attend(q, k, v, kp, vp, layer)`` is the caller's cache step:
    the token's K and V into the pool and its attention over it.
    Returns (x, k_pages, v_pages, state, stats [4])."""
    if active is None:
        active = jnp.ones(x.shape[:1], bool)

    def linear_fn(h, lp, st, li, stack):
        out, st = _linear_step(h, lp, st, li, spec, active, use_pallas)
        h, stats = _finish(h, out, lp, spec, active, use_pallas, li, stack)
        return h, st, stats

    def full_fn(h, lp, kp, vp, p, stack):
        with jax.named_scope("gated_attn"):
            q, k, v, gate = _gated_qkv(
                h[:, None], lp, spec, positions[:, None])
            attn, kp, vp = write_attend(
                q[:, 0], k[:, 0], v[:, 0], kp, vp, p)
            out = _gated_out(attn, None if gate is None else gate[:, 0],
                             lp, h.dtype)
        h, stats = _finish(h, out, lp, spec, active, use_pallas, p, stack)
        return h, kp, vp, stats

    return _period_scan(
        params, spec, x, k_pages, v_pages, state, linear_fn, full_fn)
