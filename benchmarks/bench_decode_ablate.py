"""Decode-step ablation: where does the time go? (run on real TPU)

Times each component of the serving decode step with amortized in-jit
loops (one dispatch per measurement, N iterations inside), so dispatch
and readback cost does not pollute per-step numbers.

Components:
  chunk-pallas   full _decode_chunk (the serving program), Pallas attention
  chunk-jnp      full _decode_chunk, jnp gather-twin attention
  fwd-pallas     decode_forward only (argmax feedback, no sampler)
  fwd-jnp        same, jnp twin
  sample         sample_tokens alone on random logits (top-k path)
  argmax         plain argmax on the same logits (greedy floor)
  lmhead         final-norm + lm_head einsum alone
  attn-pallas    28x paged_decode_attention_pallas per iteration
  attn-jnp       28x jnp twin per iteration

Prints one JSON line per component: {"component", "ms_per_step", ...}.
Timed decode rows also carry the roofline columns (benchmarks/_roofline.py):
``kv_bytes_per_token`` (the resident-KV read cost this row's KV config
implies), ``achieved_hbm_gbps`` over the step's modeled traffic
(weights + live-context KV reads) and ``pct_of_hbm_roofline`` against
the device's HBM peak — so KV-quant and future roofline PRs carry a
roofline number automatically instead of a bare tok/s.

``VGT_ABLATE_KV=int8`` runs the KV-heavy rows (chunk/fwd/attn) on an
int8 QuantPages pool (kv_cache.dtype: int8 — ops/kv_quant.py): halved
KV read bytes per step is the capacity/roofline lever this ablation is
meant to price on hardware.  It has not run on the current toolchain
(ROADMAP S2).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

_sync = jax.block_until_ready


def timed(fn, *args, iters_inside: int, reps: int = 3) -> float:
    """ms per inner iteration: best of ``reps`` timed dispatches."""
    _sync(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / iters_inside * 1e3


def main() -> None:
    from vgate_tpu.models.decoder import decode_forward, init_params
    from vgate_tpu.models.specs import spec_for_model_id
    from vgate_tpu.ops.sampling import sample_tokens
    from vgate_tpu.runtime.engine_core import _decode_chunk

    from benchmarks._roofline import (
        decode_step_bytes,
        kv_bytes_per_token,
        roofline_row,
    )
    from vgate_tpu.ops.kv_quant import SCALE_BYTES, QuantPages

    model_id = os.environ.get("VGT_BENCH_MODEL", "Qwen/Qwen2.5-1.5B-Instruct")
    only = set(sys.argv[1:])  # optional component filter
    spec = spec_for_model_id(model_id)
    dtype = jnp.bfloat16
    B = int(os.environ.get("VGT_ABLATE_SLOTS", 128))
    ctx = int(os.environ.get("VGT_ABLATE_CTX", 512))
    ps = 16
    pages_per_seq = ctx // ps
    P = B * pages_per_seq + 1
    STEPS = 32
    # KV storage format for the KV-heavy rows: bf16 (default) or int8
    # (kv_cache.dtype: int8 — halved KV read bytes, the roofline lever)
    kv_mode = os.environ.get("VGT_ABLATE_KV", "bf16")
    kv_quant = kv_mode == "int8"
    kv_tok_bytes = kv_bytes_per_token(
        spec.num_layers, spec.num_kv_heads, spec.head_dim,
        dtype_bytes=1 if kv_quant else jnp.dtype(dtype).itemsize,
        scale_bytes=SCALE_BYTES if kv_quant else 0,
    )

    platform = jax.devices()[0].platform
    device_kind = getattr(jax.devices()[0], "device_kind", "unknown")
    base = {
        "model": spec.name, "B": B, "ctx": ctx, "platform": platform,
        "kv_dtype": "int8" if kv_quant else "bf16",
        "kv_bytes_per_token": kv_tok_bytes,
    }
    print(json.dumps({**base, "event": "start"}), flush=True)

    params = init_params(spec, jax.random.PRNGKey(0), dtype)
    weight_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    # live context the decode rows actually read per slot (positions
    # start at ctx/2 and advance STEPS; midpoint of the sweep)
    ctx_live = ctx // 2 + STEPS // 2
    kv_shape = (spec.num_layers, spec.num_kv_heads, P, ps, spec.head_dim)

    def fresh_kv():
        if kv_quant:
            return QuantPages(
                jnp.zeros(kv_shape, jnp.int8),
                jnp.ones(kv_shape[:-1], jnp.bfloat16),
            )
        return jnp.zeros(kv_shape, dtype)

    k_pages = fresh_kv()
    v_pages = fresh_kv()
    page_tables = jnp.asarray(
        (np.arange(B * pages_per_seq, dtype=np.int32) % (P - 1) + 1)
        .reshape(B, pages_per_seq)
    )
    tokens = jnp.zeros((B,), jnp.int32)
    positions = jnp.full((B,), ctx // 2, jnp.int32)
    active = jnp.ones((B,), bool)
    temps = jnp.zeros((B,), jnp.float32)
    top_ps = jnp.ones((B,), jnp.float32)
    top_ks = jnp.zeros((B,), jnp.int32)
    seeds = jnp.full((B,), -1, jnp.int32)
    steps0 = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(0)
    counter = jnp.asarray(0, jnp.uint32)

    def step_bytes_for(component):
        """Modeled HBM traffic per step by component family: decode
        rows stream the weights once + read every slot's live KV
        window; attention-only rows read just the KV (their L layer
        calls compose to the same all-layer total).  Host-RTT and
        sampler rows have no meaningful HBM story — no columns."""
        if component.startswith(("chunk-", "fwd-")):
            return decode_step_bytes(weight_bytes, B, ctx_live, kv_tok_bytes)
        if component.startswith("attn-"):
            return B * ctx_live * kv_tok_bytes
        return None

    def report(component, ms):
        row = {**base, "component": component, "ms_per_step": round(ms, 3)}
        sb = step_bytes_for(component)
        if sb:
            row.update(roofline_row(ms, sb, device_kind))
        print(json.dumps(row), flush=True)

    # bare dispatch + host-readback round-trip (NOT divided by STEPS):
    # subtract this from `* 32` totals when comparing absolute floors
    if not only or "rtt" in only:
        @jax.jit
        def rtt_fn(t):
            return t + 1

        report("rtt", timed(rtt_fn, tokens, iters_inside=1))

    # --- full serving chunk (pallas / jnp) ---
    for name, use_pallas in (("chunk-pallas", True), ("chunk-jnp", False)):
        if only and name not in only:
            continue
        if use_pallas and platform != "tpu":
            continue

        def run(k_pages, v_pages, up=use_pallas):
            return _decode_chunk(
                params, spec, tokens, positions, k_pages, v_pages,
                page_tables, active, temps, top_ps, top_ks, key, counter,
                num_steps=STEPS, use_pallas=up, max_position=ctx - 1,
                seeds=seeds, steps=steps0,
            )[0]

        # donation consumes the caches: rebuild fresh copies per rep
        kp = fresh_kv()
        vp = fresh_kv()
        _sync(run(kp, vp))  # compile + warm
        best = float("inf")
        for _ in range(3):
            kp = fresh_kv()
            vp = fresh_kv()
            jax.block_until_ready((kp, vp))
            t0 = time.perf_counter()
            _sync(run(kp, vp))
            best = min(best, time.perf_counter() - t0)
        report(name, best / STEPS * 1e3)

    # --- model forward only (argmax feedback, no sampler) -----------------
    for name, use_pallas in (("fwd-pallas", True), ("fwd-jnp", False)):
        if only and name not in only:
            continue
        if use_pallas and platform != "tpu":
            continue

        # params passed explicitly: closing over them captures multi-GB
        # constants into the lowered program
        @functools.partial(jax.jit, donate_argnums=(1, 2),
                           static_argnums=(3,))
        def fwd_loop(params, k_pages, v_pages, up):
            def body(carry, _):
                toks, pos, kp, vp = carry
                logits, kp, vp = decode_forward(
                    params, spec, toks, pos, kp, vp, page_tables,
                    active=active, use_pallas=up,
                )
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                pos = jnp.minimum(pos + 1, ctx - 1)
                return (toks, pos, kp, vp), toks

            (_, _, kp, vp), ys = jax.lax.scan(
                body, (tokens, positions, k_pages, v_pages), None,
                length=STEPS,
            )
            return ys

        kp = fresh_kv()
        vp = fresh_kv()
        _sync(fwd_loop(params, kp, vp, use_pallas))
        best = float("inf")
        for _ in range(3):
            kp = fresh_kv()
            vp = fresh_kv()
            jax.block_until_ready((kp, vp))
            t0 = time.perf_counter()
            _sync(fwd_loop(params, kp, vp, use_pallas))
            best = min(best, time.perf_counter() - t0)
        report(name, best / STEPS * 1e3)

    # --- prefill ----------------------------------------------------------
    PB, PS_LEN = 32, 128  # the bench serving prefill shape
    if B >= PB and pages_per_seq >= PS_LEN // ps and (
        not only or "prefill" in only
    ):
        from vgate_tpu.models.decoder import prefill_forward

        ptokens = jnp.asarray(
            (np.arange(PB * PS_LEN, dtype=np.int32) % 199 + 3).reshape(
                PB, PS_LEN
            )
        )
        plens = jnp.full((PB,), PS_LEN - 5, jnp.int32)
        ppt = page_tables[:PB, : PS_LEN // ps]

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def prefill_loop(params, kp, vp):
            def body(c, _):
                kp, vp = c
                logits, kp, vp = prefill_forward(
                    params, spec, ptokens, plens, kp, vp, ppt,
                )
                return (kp, vp), logits[0, 0]

            (kp, vp), ys = jax.lax.scan(body, (kp, vp), None, length=4)
            return ys

        _sync(prefill_loop(params, fresh_kv(), fresh_kv()))
        best = float("inf")
        for _ in range(3):
            kp = fresh_kv()
            vp = fresh_kv()
            jax.block_until_ready((kp, vp))
            t0 = time.perf_counter()
            _sync(prefill_loop(params, kp, vp))
            best = min(best, time.perf_counter() - t0)
        # ms per prefill DISPATCH (B=32 x 128-token bucket)
        report("prefill", best / 4 * 1e3)

    # --- sampling / lm_head in isolation ----------------------------------
    V = spec.vocab_size
    logits = jax.random.normal(jax.random.PRNGKey(1), (B, V), jnp.float32)

    if not only or "sample" in only:
        @jax.jit
        def sample_loop(logits):
            def body(c, i):
                k = jax.random.fold_in(key, i)
                t = sample_tokens(logits + c[:, None].astype(jnp.float32),
                                  temps, top_ps, top_ks, k,
                                  seeds=seeds, steps=steps0)
                return t, ()
            out, _ = jax.lax.scan(body, tokens, jnp.arange(STEPS))
            return out

        report("sample", timed(sample_loop, logits, iters_inside=STEPS))

    if not only or "argmax" in only:
        @jax.jit
        def argmax_loop(logits):
            def body(c, _):
                t = jnp.argmax(
                    logits + c[:, None].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return t, ()
            out, _ = jax.lax.scan(body, tokens, None, length=STEPS)
            return out

        report("argmax", timed(argmax_loop, logits, iters_inside=STEPS))

    if not only or "lmhead" in only:
        from vgate_tpu.models.decoder import _logits as logits_fn

        x = jax.random.normal(
            jax.random.PRNGKey(2), (B, spec.hidden_size), dtype
        )

        @jax.jit
        def lmhead_loop(params, x):
            def body(c, _):
                lg = logits_fn(params, spec, x + c)
                return lg[:, 0].astype(dtype)[:, None] * 0 + c, ()
            out, _ = jax.lax.scan(
                body, jnp.zeros((B, 1), dtype), None, length=STEPS
            )
            return out

        report("lmhead", timed(lmhead_loop, params, x, iters_inside=STEPS))

    # --- attention only (28 layer calls per iteration) --------------------
    from vgate_tpu.ops.kv_quant import quantize

    q = jax.random.normal(
        jax.random.PRNGKey(3), (B, spec.num_heads, spec.head_dim), dtype
    )

    def attn_pool(seed):
        vals = jax.random.normal(
            jax.random.PRNGKey(seed),
            (spec.num_kv_heads, P, ps, spec.head_dim), dtype,
        ) * 0.1
        if kv_quant:
            return QuantPages(*quantize(vals))
        return vals

    kp1 = attn_pool(4)
    # independent V buffer: aliasing K/V would let XLA CSE the twin's two
    # page gathers and halve its apparent memory traffic
    vp1 = attn_pool(5)
    seq_lens = positions + 1
    L = spec.num_layers

    for name in ("attn-pallas", "attn-jnp"):
        if only and name not in only:
            continue
        if name == "attn-pallas":
            if platform != "tpu":
                continue
            from vgate_tpu.ops.pallas.paged_attention import (
                paged_decode_attention_pallas as attn,
            )
        else:
            from vgate_tpu.ops.attention import (
                paged_decode_attention as attn,
            )

        @jax.jit
        def attn_loop(q, kp1, vp1):
            # outer scan amortizes the dispatch round-trip over STEPS
            # decode-steps; each step runs all L layer calls
            def step(c, _):
                def body(h, _):
                    o = attn(h, kp1, vp1, page_tables, seq_lens)
                    return o.astype(h.dtype), ()
                h, _ = jax.lax.scan(body, c, None, length=L)
                return h, ()
            out, _ = jax.lax.scan(step, q, None, length=STEPS)
            return out

        report(name, timed(attn_loop, q, kp1, vp1, iters_inside=STEPS))

    print(json.dumps({**base, "event": "done"}), flush=True)


if __name__ == "__main__":
    main()
