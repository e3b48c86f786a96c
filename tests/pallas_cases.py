"""What the files of Pallas kernel tests share (``test_pallas_kernels``,
``test_pallas_decode_kernel``, ``test_pallas_decode_trips``,
``test_pallas_decode_pipeline``; the int8 and tp files read
``live_rows_match``): a paged decode case, the rule for its rows, and
the decode kernel's work-list cases.  The four files are apart by the
kernel they trace, none past 300 s alone
(``tests/conftest.py LONGEST_FIRST``)."""

import jax.numpy as jnp
import numpy as np


def make_case(B=4, H=8, KV=2, hd=128, ps=16, pages_per_seq=16, seed=0,
              lens=None):
    rng = np.random.default_rng(seed)
    P = 1 + B * pages_per_seq
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    page_tables = jnp.asarray(
        rng.permutation(np.arange(1, P))[: B * pages_per_seq].reshape(
            B, pages_per_seq
        ),
        jnp.int32,
    )
    if lens is None:
        lens = rng.integers(1, pages_per_seq * ps, size=B)
    seq_lens = jnp.asarray(lens, jnp.int32)
    return q, k_pages, v_pages, page_tables, seq_lens


def live_rows_match(got, expect, seq_lens, tol=2e-5):
    """Live rows are held to the twin; a row of length 0 is held to
    ZEROS (the twin averages garbage there, and the logits' integrity
    guard reduces over every row)."""
    got, lens = np.asarray(got), np.asarray(seq_lens)
    np.testing.assert_allclose(
        got[lens > 0], np.asarray(expect)[lens > 0], rtol=tol, atol=tol
    )
    assert not got[lens == 0].any(), "a row of length 0 must come out zero"


# slot -> length; a chunk (one item of a program's work list) is 256
# tokens and a program serves 64 slots at this geometry
TRIP_CASES = {
    # (s0c0 s0c1) (s0c2 s5c0) (s9c0 s9c1) (s20c0): seven items, the last
    # a trip of its own; the other program has nothing to do
    "odd-item-count": ({0: 700, 5: 100, 9: 300, 20: 50}, {}),
    # one program with one item, one with none
    "one-item-program": ({40: 77}, {}),
    # live slots among dead ones in both programs, chunk edges (256, 257)
    "dead-slots-between-live": (
        {0: 5, 3: 256, 4: 1, 9: 257, 31: 33, 63: 64, 65: 17, 69: 90}, {}),
    # (s2c0 s2c1) (s2c2 s3c0) (s3c1 s4c0): a slot ends in a trip's first
    # item and another starts in its second, twice
    "pair-spans-two-slots": ({2: 600, 3: 300, 4: 10}, {}),
    # twelve one-chunk slots: every trip ends two slots (two staged
    # pages, two stores), twelve writes over four staging pages
    "both-items-last-chunks": ({b: 1 + 3 * b for b in range(12)}, {}),
    # first chunks 2, 1, 0 and 2: the chunks below a window are no items
    "window-first-chunk-not-0": (
        {0: 700, 1: 513, 2: 40, 7: 768}, {"window": 100}),
    "layer-pools": ({0: 700, 5: 100, 9: 300, 20: 50, 66: 257}, {"layer": 1}),
    "float32-pools": ({1: 300, 2: 0, 3: 31, 64: 513}, {"dtype": jnp.float32}),
}


def trip_case(case):
    """(arguments, keyword arguments, slot -> length, new K rows) of a
    decode-kernel call that writes, for a case of TRIP_CASES."""
    from vgate_tpu.ops.pallas.paged_attention import _decode_sizes

    pattern, kw = TRIP_CASES[case]
    kw = dict(kw)
    dtype, layer = kw.pop("dtype", jnp.bfloat16), kw.pop("layer", None)
    B, KV, G, hd, ps, pages_per_seq = 70, 2, 4, 128, 32, 24
    assert _decode_sizes(
        B, KV, G, hd, ps, pages_per_seq, dtype, dtype
    )[:2] == (8, 64)
    q, k_pages, v_pages, page_tables, seq_lens = (
        x.astype(dtype) if x.dtype == jnp.float32 else x
        for x in make_case(
            B=B, H=KV * G, KV=KV, hd=hd, ps=ps, pages_per_seq=pages_per_seq,
            lens=[pattern.get(b, 0) for b in range(B)], seed=31,
        )
    )
    if layer is not None:
        k_pages, v_pages = (
            jnp.stack([pool * 0.5, pool, pool * 2.0])
            for pool in (k_pages, v_pages)
        )
        kw["layer"] = jnp.asarray(layer)
    if "window" in kw:
        kw["window"] = jnp.asarray(kw["window"], jnp.int32)
    rng = np.random.default_rng(32)
    for name in ("k_new", "v_new"):
        kw[name] = jnp.asarray(rng.normal(size=(B, KV, hd)), dtype)
    return (q, k_pages, v_pages, page_tables, seq_lens), kw, pattern
