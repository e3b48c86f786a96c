"""Static cost analysis of the decode-chunk program (no TPU needed).

Lowers ``_decode_chunk`` at the bench serving shape on the CPU backend and
prints XLA's bytes-accessed / FLOP estimates per decode step, next to the
analytic roofline (weights + live KV).  This is the CPU backend's
program, not the chip's: for what XLA:TPU actually builds, compile ahead
of time for the v5e as tests/test_tpu_aot.py does.

Usage: python scripts/diag_decode_cost.py [--steps 8] [--pages 4097]
"""

from __future__ import annotations

import argparse
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--pages", type=int, default=4097)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--model", default="Qwen/Qwen2.5-1.5B-Instruct")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=512)
    ap.add_argument("--greedy", action="store_true",
                    help="all-greedy sampling variant (argmax fast path)")
    args = ap.parse_args()

    from vgate_tpu.models.decoder import init_params
    from vgate_tpu.models.specs import spec_for_model_id
    from vgate_tpu.runtime.step_programs import _decode_chunk

    spec = spec_for_model_id(args.model)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    params = init_params(spec, jax.random.PRNGKey(0), dtype)

    B = args.slots
    ps = args.page_size
    pages_per_seq = args.ctx // ps
    P = args.pages
    geom_kv = (spec.num_layers, spec.num_kv_heads, P, ps, spec.head_dim)
    k_pages = jnp.zeros(geom_kv, dtype)
    v_pages = jnp.zeros(geom_kv, dtype)
    page_tables = jnp.asarray(
        (np.arange(B * pages_per_seq, dtype=np.int32) % (P - 1) + 1)
        .reshape(B, pages_per_seq)
    )
    tokens = jnp.zeros((B,), jnp.int32)
    positions = jnp.full((B,), args.ctx // 2, jnp.int32)
    active = jnp.ones((B,), bool)
    temps = jnp.zeros((B,), jnp.float32)
    top_ps = jnp.ones((B,), jnp.float32)
    top_ks = jnp.zeros((B,), jnp.int32)
    seeds = jnp.full((B,), -1, jnp.int32)
    steps_arr = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(0)
    counter = jnp.asarray(0, jnp.uint32)

    lowered = _decode_chunk.lower(
        params, spec, tokens, positions, k_pages, v_pages, page_tables,
        active, temps, top_ps, top_ks, key, counter,
        num_steps=args.steps, use_pallas=False,
        max_position=args.ctx - 1, seeds=seeds, steps=steps_arr,
        all_greedy=args.greedy,
    )
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    bytes_total = ca.get("bytes accessed", float("nan"))
    flops = ca.get("flops", float("nan"))

    nbytes = jnp.dtype(dtype).itemsize
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    live_kv = (
        2 * spec.num_layers * spec.num_kv_heads * B * args.ctx
        * spec.head_dim * nbytes
    )
    kv_buf = 2 * int(np.prod(geom_kv)) * nbytes
    per_step = bytes_total / args.steps
    print(f"model={spec.name} B={B} ctx={args.ctx} pages={P} steps={args.steps}")
    print(f"param bytes            : {param_bytes/1e9:8.2f} GB")
    print(f"live KV (all layers)   : {live_kv/1e9:8.2f} GB")
    print(f"KV pool buffers        : {kv_buf/1e9:8.2f} GB")
    print(f"roofline bytes/step    : {(param_bytes+live_kv)/1e9:8.2f} GB")
    print(f"XLA bytes accessed/step: {per_step/1e9:8.2f} GB "
          f"({per_step/(param_bytes+live_kv):.1f}x roofline)")
    print(f"XLA flops/step         : {flops/args.steps/1e9:8.1f} GFLOP")
    print(f"v5e est ms/step @819GBps HBM: {per_step/819e9*1e3:6.1f} ms")


if __name__ == "__main__":
    main()
