"""Microbenchmark: Pallas kernels vs their jnp twins on real TPU.

Quantifies the memory-path claim in ops/pallas/paged_attention.py (the
kernel DMAs only live pages; the twin gathers the full page window) and
ops/pallas/flash_prefill.py (blockwise online softmax vs the jnp
blockwise twin).  Run on hardware:

    python benchmarks/bench_kernels.py

Prints one JSON line per (kernel, shape) with median step times and the
speedup.  CPU-safe fallback: refuses to run (the kernels need a TPU).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


LOOP = 8  # op invocations fused into one program


def _looped(op):
    """Scan the op LOOP times inside one jit so per-dispatch cost
    amortizes away; the q input depends on the previous output, which
    stops XLA hoisting the op out of the loop."""

    @jax.jit
    def run(q, *rest):
        def body(carry, _):
            out = op(q + 0 * carry.astype(q.dtype), *rest)
            return out.astype(jnp.float32), None

        out, _ = jax.lax.scan(
            body, jnp.zeros(q.shape, jnp.float32), None, length=LOOP
        )
        return out

    return run


_sync = jax.block_until_ready


def _median_time(fn, *args, iters: int = 10, warmup: int = 2) -> float:
    """Median per-op time of the looped program."""
    for _ in range(warmup):
        _sync(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / LOOP


def bench_paged_decode(B=128, H=12, KV=2, hd=128, ps=16, ctx=512):
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    pages_per_seq = ctx // ps
    P = 1 + B * pages_per_seq
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    k_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    v_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    page_tables = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    # realistic mixed occupancy: sequence lengths spread over [ps, ctx]
    seq_lens = jnp.asarray(
        (np.arange(B) % pages_per_seq + 1) * ps, np.int32
    )

    np.testing.assert_allclose(
        np.asarray(
            jax.jit(paged_decode_attention)(
                q, k_pages, v_pages, page_tables, seq_lens
            ),
            np.float32,
        ),
        np.asarray(
            jax.jit(paged_decode_attention_pallas)(
                q, k_pages, v_pages, page_tables, seq_lens
            ),
            np.float32,
        ),
        rtol=2e-2, atol=2e-2,
    )
    twin = _looped(paged_decode_attention)
    kern = _looped(paged_decode_attention_pallas)
    t_twin = _median_time(twin, q, k_pages, v_pages, page_tables, seq_lens)
    t_kern = _median_time(kern, q, k_pages, v_pages, page_tables, seq_lens)
    return {
        "kernel": "paged_decode_attention",
        "shape": f"B{B} H{H} KV{KV} hd{hd} ps{ps} ctx{ctx}",
        "jnp_us": round(t_twin * 1e6, 1),
        "pallas_us": round(t_kern * 1e6, 1),
        "speedup": round(t_twin / t_kern, 2),
    }


def bench_flash_prefill(B=8, S=1024, H=12, KV=2, hd=128):
    from vgate_tpu.ops.attention import flash_prefill_attention
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    seq_lens = jnp.asarray(
        np.linspace(S // 4, S, B).astype(np.int32)
    )

    np.testing.assert_allclose(
        np.asarray(
            jax.jit(flash_prefill_attention)(q, k, v, seq_lens), np.float32
        ),
        np.asarray(
            jax.jit(flash_prefill_attention_pallas)(q, k, v, seq_lens),
            np.float32,
        ),
        rtol=3e-2, atol=3e-2,
    )
    twin = _looped(flash_prefill_attention)
    kern = _looped(flash_prefill_attention_pallas)
    t_twin = _median_time(twin, q, k, v, seq_lens)
    t_kern = _median_time(kern, q, k, v, seq_lens)
    return {
        "kernel": "flash_prefill_attention",
        "shape": f"B{B} S{S} H{H} KV{KV} hd{hd}",
        "jnp_us": round(t_twin * 1e6, 1),
        "pallas_us": round(t_kern * 1e6, 1),
        "speedup": round(t_twin / t_kern, 2),
    }


def bench_decode_window(B=128, H=8, KV=4, hd=256, ps=16, ctx=4096,
                        window=1024):
    """Sliding-window decode (Gemma-2 local layers): the kernel skips DMA
    below the window, so its time should track O(window) while the jnp
    twin still gathers O(ctx)."""
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    pages_per_seq = ctx // ps
    P = 1 + B * pages_per_seq
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    k_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    v_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    page_tables = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    seq_lens = jnp.full((B,), ctx, jnp.int32)  # worst case: full context
    w = jnp.asarray(window, jnp.int32)

    twin = _looped(
        functools.partial(paged_decode_attention, window=w)
    )
    kern = _looped(
        functools.partial(paged_decode_attention_pallas, window=w)
    )
    t_twin = _median_time(twin, q, k_pages, v_pages, page_tables, seq_lens)
    t_kern = _median_time(kern, q, k_pages, v_pages, page_tables, seq_lens)
    return {
        "kernel": "paged_decode_attention[window]",
        "shape": f"B{B} H{H} KV{KV} hd{hd} ctx{ctx} win{window}",
        "jnp_us": round(t_twin * 1e6, 1),
        "pallas_us": round(t_kern * 1e6, 1),
        "speedup": round(t_twin / t_kern, 2),
    }


def bench_multitok_verify(B=64, S=4, H=12, KV=2, hd=128, ps=16, ctx=512):
    """Speculative-verify attention: S candidate rows vs the jnp suffix
    gather path."""
    from vgate_tpu.ops.attention import paged_suffix_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    pages_per_seq = ctx // ps
    P = 1 + B * pages_per_seq
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    k_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    v_pages = jax.random.normal(key, (KV, P, ps, hd), jnp.bfloat16)
    page_tables = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    positions0 = jnp.asarray(
        (np.arange(B) % (pages_per_seq - 1) + 1) * ps, np.int32
    )
    input_lens = jnp.full((B,), S, jnp.int32)

    twin = _looped(
        lambda q_, kp, vp, pt, p0: paged_suffix_attention(
            q_, kp, vp, pt, p0, p0 + S
        )
    )
    kern = _looped(
        lambda q_, kp, vp, pt, p0: paged_multitok_attention_pallas(
            q_, kp, vp, pt, p0, input_lens
        )
    )
    t_twin = _median_time(twin, q, k_pages, v_pages, page_tables, positions0)
    t_kern = _median_time(kern, q, k_pages, v_pages, page_tables, positions0)
    return {
        "kernel": "spec_verify_attention",
        "shape": f"B{B} S{S} H{H} KV{KV} hd{hd} ctx{ctx}",
        "jnp_us": round(t_twin * 1e6, 1),
        "pallas_us": round(t_kern * 1e6, 1),
        "speedup": round(t_twin / t_kern, 2),
    }


def main() -> None:
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            "bench_kernels needs a real TPU (Pallas kernels don't run on "
            f"{device.platform}); CPU CI covers parity in interpret mode"
        )
    print(json.dumps(bench_paged_decode()))
    print(json.dumps(bench_paged_decode(ctx=2048)))
    print(json.dumps(bench_flash_prefill()))
    print(json.dumps(bench_flash_prefill(S=2048)))
    print(json.dumps(bench_decode_window()))
    print(json.dumps(bench_multitok_verify()))


if __name__ == "__main__":
    main()
