"""Standalone compile+execute probe for the blocked decode kernel.

Run in its own process with a wall-clock budget enforced by the caller
(the chip tool's ``--timeout``): a Mosaic compile that hangs must cost
one bounded call, not the A/B grid that would follow it.

Compiles the Qwen2.5-1.5B serving decode shape (B=128, H=12, KV=2,
hd=128, page 32) at each block_slots the session grid would use, and
executes one call with a host readback.  Prints one JSON line:
``{"probe": "blocked_kernel", "ok": true, "seconds": ..., "per_bs":
{...}}``.
"""

import json
import time

import numpy as np

import jax
import jax.numpy as jnp


def main() -> int:
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas_blocked,
    )

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(json.dumps({"probe": "blocked_kernel", "ok": False,
                          "error": f"not a tpu: {d.platform}"}))
        return 1

    B, H, KV, hd, ps = 128, 12, 2, 128, 32
    pages_per_seq, P = 16, 1 + 128 * 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.bfloat16)
    k_pages = jnp.asarray(
        rng.normal(size=(KV, P, ps, hd)) * 0.1, jnp.bfloat16
    )
    v_pages = jnp.asarray(
        rng.normal(size=(KV, P, ps, hd)) * 0.1, jnp.bfloat16
    )
    page_tables = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    seq_lens = jnp.full((B,), 500, jnp.int32)

    t0 = time.time()
    per_bs = {}
    for bs in (4, 8, 16):
        t = time.time()
        out = paged_decode_attention_pallas_blocked(
            q, k_pages, v_pages, page_tables, seq_lens, block_slots=bs
        )
        np.asarray(out)  # host readback = the only reliable sync here
        per_bs[str(bs)] = round(time.time() - t, 1)
    print(json.dumps({
        "probe": "blocked_kernel", "ok": True,
        "seconds": round(time.time() - t0, 1), "per_bs": per_bs,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
