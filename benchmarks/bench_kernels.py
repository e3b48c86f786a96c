"""Microbenchmark: Pallas kernels vs their jnp twins on real TPU.

Quantifies the memory-path claim in ops/pallas/paged_attention.py (the
kernel DMAs only live pages; the twin gathers the full page window) and
ops/pallas/flash_prefill.py (blockwise online softmax vs the jnp
blockwise twin).  Run on hardware:

    python benchmarks/bench_kernels.py
    python benchmarks/bench_kernels.py sample_edits   # that probe alone
    python benchmarks/bench_kernels.py greedy_head    # a decode step's tail
    python benchmarks/bench_kernels.py decode_cells [CELL ...] [buffers=N ...]
    python benchmarks/bench_kernels.py expert_layer [CONFIG ...]
    python benchmarks/bench_kernels.py dsa_index dsa_select dsa_attend
    python benchmarks/bench_kernels.py dsa_attend dsa_attend_64k
    python benchmarks/bench_kernels.py eva_decode [PAGESxITEMS ...]
    python benchmarks/bench_kernels.py dense_prompt [CONFIG ...]
    python benchmarks/bench_kernels.py ssd_step [HEADS ...]
    python benchmarks/bench_kernels.py flash_prefill_cells [CELL ...]
    python benchmarks/bench_kernels.py flash_prefill_hollow

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

from vgate_tpu.utils.math import cdiv  # noqa: E402


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


def _median_time(
    fn, *args, iters: int = 10, warmup: int = 2, loop: int = LOOP
) -> float:
    """Median per-op time of a program that runs the op `loop` times."""
    for _ in range(warmup):
        _sync(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / loop


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


# The cells' decode shapes (BENCHMARK.json), as the traced runs hold them
# (PERF.md section 5): (KV, G, hd), slots, context, live slots, and the
# live streams' lengths.  A dead slot has length 0 (models/decoder.py
# hands the kernel that for an inactive row).  `latent` > 0: ONE pool of
# hd-wide rows whose first `latent` lanes are the value (mistral's).
DECODE_CELLS = {
    "qwen2.5-1.5b.decode-heavy": ((2, 6, 128), 256, 2048, 251, (80, 720), 0),
    "qwen2.5-1.5b.chat": ((2, 6, 128), 256, 2048, 55, (70, 690), 0),
    "qwen2.5-7b-l14.prefill-heavy": (
        (4, 7, 128), 256, 2048, 54, (1040, 1880), 0),
    "qwen3-next-80b-a3b-l8e128.decode-heavy": (
        (2, 8, 256), 256, 2048, 251, (80, 720), 0),
    "nemotron-3-super-120b-a12b-l11e128.decode-heavy": (
        (2, 16, 128), 192, 2048, 188, (80, 720), 0),
    "mistral-small-4-119b-l4e32.long-prompt": (
        (1, 32, 384), 256, 8192, 256, (4200, 7900), 256),
    # PR 56: every slot live at 1,025-1,536 prompt tokens and up to 319
    # answered (perfbench/traffic/long-context.json): five to eight items
    # a slot, all but the last a full chunk
    "qwen2.5-1.5b.long-context": (
        (2, 6, 128), 256, 2048, 256, (1030, 1850), 0),
}
with open(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "perfbench", "peaks.json",
)) as _fh:
    _peaks = json.load(_fh)["TPU v5 lite"]
HBM_BYTES_PER_S, BF16_FLOPS_PER_S = (
    _peaks["hbm_bytes_per_s"], _peaks["bf16_flops"])


def decode_cell_case(name, ps=32, layers=2, dead_len=0, seed=0):
    """Inputs of one decode-kernel launch at a cell's shape, `(q, pools,
    page_tables, seq_lens)`: stacked bf16 pools (K and V, or the one
    latent pool), scattered pages, `live` streams at random slots with
    lengths uniform over the cell's range."""
    (KV, G, hd), B, ctx, live, (lo, hi), latent = DECODE_CELLS[name]
    rng = np.random.default_rng(seed)
    pages_per_seq = ctx // ps
    P = 1 + B * pages_per_seq
    kq, *kp = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, KV * G, hd), jnp.bfloat16)
    pools = tuple(
        jax.random.normal(k, (layers, KV, P, ps, hd), jnp.bfloat16)
        for k in kp[: 1 if latent else 2]
    )
    page_tables = jnp.asarray(
        (rng.permutation(P - 1)[: B * pages_per_seq] + 1).reshape(B, -1),
        jnp.int32,
    )
    lens = np.full((B,), dead_len, np.int64)
    lens[rng.permutation(B)[:live]] = rng.integers(lo, hi + 1, size=live)
    return q, pools, page_tables, jnp.asarray(lens, jnp.int32)


def decode_cell_kernel(name, module=None, **kw):
    """The cell's decode kernel as one layer's cache work, `(q, pools,
    page_tables, seq_lens, news, layer) -> (attention, pools)`: it
    writes the slots' new rows (`news`, one a pool) and attends to them.
    `kw` goes to the kernel (`items`, `buffers`, `hollow`); `module` is
    the `paged_attention` to take it from (the parent commit's)."""
    from vgate_tpu.ops.pallas import paged_attention

    module = module or paged_attention
    mla_decode_attention_pallas = module.mla_decode_attention_pallas
    paged_decode_attention_pallas = module.paged_decode_attention_pallas
    latent = DECODE_CELLS[name][-1]

    def plain(q, pools, pt, sl, news, layer):
        out, *pools = paged_decode_attention_pallas(
            q, *pools, pt, sl, layer=layer, k_new=news[0], v_new=news[1],
            **kw
        )
        return out, tuple(pools)

    def absorbed(q, pools, pt, sl, news, layer):
        out, pool = mla_decode_attention_pallas(
            q, pools[0], pt, sl, layer, news[0][:, 0], v_width=latent,
            scale=q.shape[-1] ** -0.5, **kw
        )
        # the value's width back to the query's: the probe chains layers
        return jnp.pad(out, ((0, 0), (0, 0), (0, q.shape[-1] - latent))), (
            pool,)

    return absorbed if latent else plain


def decode_cell_news(case, seed=7):
    """A new row a slot a pool, [B, KV, hd] each."""
    q, pools = case[:2]
    KV, hd = pools[0].shape[1], pools[0].shape[-1]
    return tuple(jax.random.normal(
        jax.random.PRNGKey(seed), (len(pools), q.shape[0], KV, hd),
        pools[0].dtype,
    ))


def time_decode_layers(layer_fns, case, loop=28, rounds=10):
    """Median seconds of ONE layer's cache work for each of `layer_fns`
    (name -> `decode_cell_kernel`'s signature): `loop` of them chained in
    one program (a decode step's layers) with the pools on the carry,
    layer index alternating.  The programs are timed in turn, round by
    round, so that what drifts between rounds falls on all alike, and
    they hand ONE copy of the pools on (each donates it)."""
    q, pools, page_tables, seq_lens = case
    # the runs donate their pools: the case keeps its own
    pools = tuple(jnp.copy(pool) for pool in pools)
    news = decode_cell_news(case)

    def program(layer_fn):
        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(q, pools, page_tables, seq_lens, news):
            def body(carry, i):
                out, pools = carry
                out, pools = layer_fn(
                    q + 0 * out.astype(q.dtype), pools, page_tables,
                    seq_lens, news, i % pools[0].shape[0],
                )
                return (out.astype(jnp.float32), pools), None

            carry, _ = jax.lax.scan(
                body, (jnp.zeros(q.shape, jnp.float32), pools),
                jnp.arange(loop, dtype=jnp.int32),
            )
            return carry

        return run

    programs = {form: program(fn) for form, fn in layer_fns.items()}
    times = {form: [] for form in programs}
    for _ in range(2 + rounds):  # the first two compile and warm
        for form, run in programs.items():
            t0 = time.perf_counter()
            _, pools = _sync(run(q, pools, page_tables, seq_lens, news))
            times[form].append(time.perf_counter() - t0)
    return {
        form: float(np.median(seconds[2:])) / loop
        for form, seconds in times.items()
    }


def decode_trips(seq_lens, chunk_tokens, block_slots, items):
    """(work-list items, loop trips, trips that hold `items` items,
    `full_chunk_share`: the share of items whose chunk has no dead page)
    of one launch: a function of the lengths alone.  A program's list is the live chunks of its block of
    slots, a trip takes `items` of them, and an odd last item is a trip
    of its own."""
    lens = np.asarray(seq_lens, np.int64)
    chunks = cdiv(lens, chunk_tokens)
    per_program = [
        int(chunks[i:i + block_slots].sum())
        for i in range(0, len(chunks), block_slots)
    ]
    work = sum(per_program)
    return (
        work, sum(cdiv(n, items) for n in per_program),
        sum(n // items for n in per_program),
        float((lens // chunk_tokens).sum()) / max(work, 1),
    )


def _parent_paged_attention(
    path="_parent/vgate_tpu/ops/pallas/paged_attention.py",
):
    """ops/pallas/paged_attention.py of the parent commit, or None."""
    return _parent_module(path, "parent_paged_attention")


def bench_decode_cells(cells=None, forced=()):
    """A decode layer's cache work (the kernel that writes the token's
    page itself) at the cells' shapes: µs a launch, the share of the HBM
    roofline over the LIVE tokens' bytes read (what
    `kernel.decode_attn_roofline_live` counts in a traced run), the
    launch's items and trips with the share of trips that hold two and
    the share of items that are full chunks, and the hollow kernel's time
    (the trips' bookkeeping alone: no copy, no product).  `rule_items`
    and `rule_buffers` are what `_decode_sizes` picks for the shape.

    The forms stand side by side, each with its hollow twin, timed in
    turn round by round: `rule` (the kernel as served), `bN` for each of
    `forced` (the rule's items at N chunk buffers: N / items trips, one
    computed and the others in flight), `items_N` (the other items count
    at its rule's buffers) and `parent` (the parent commit's kernel where
    `_parent/` holds a `git archive` of it, at its own rule's buffers:
    `"buffers": 0`).  Every form must give the
    first form's bits (the parent's where it is there), and on plain
    pools one item's must be the scatter's (what a step did until PR
    30)."""
    from vgate_tpu.models.decoder import decode_attn_inputs
    from vgate_tpu.ops.kv_quant import kv_write_tokens
    from vgate_tpu.ops.pallas.paged_attention import (
        _decode_sizes, paged_decode_attention_pallas,
    )

    parent = _parent_paged_attention()

    def parent_kernel(name, buffers, **kw):
        """The parent commit's kernel: its buffers are its own rule's."""
        return decode_cell_kernel(name, module=parent, **kw)

    def scatter_then_kernel(q, pools, pt, sl, news, layer):
        # a dead slot's token goes to trash page 0, as in a decode step
        _, page_ids, page_off = decode_attn_inputs(
            jnp.maximum(sl - 1, 0), pt, sl > 0, pools[0].shape[-2]
        )
        pools = tuple(
            kv_write_tokens(pool, page_ids, page_off, new, layer=layer)
            for pool, new in zip(pools, news)
        )
        return paged_decode_attention_pallas(
            q, *pools, pt, sl, layer=layer, items=1
        ), pools

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def same_bits(got_fn, want_fn, case, news):
        """One layer, attention and every page but the trash page, bit
        for bit (a dead slot's token goes there only by scatter)."""
        got, want = got_fn(*case, news, 1), want_fn(*case, news, 1)
        return [
            jnp.array_equal(got[0], want[0]),
            *(jnp.array_equal(g[:, :, 1:], w[:, :, 1:])
              for g, w in zip(got[1], want[1])),
        ]

    for name in cells or DECODE_CELLS:
        (KV, G, hd), B, ctx, live, _, latent = DECODE_CELLS[name]
        case = decode_cell_case(name)
        news = decode_cell_news(case, seed=11)
        lens, ps = np.asarray(case[3]), case[1][0].shape[-2]
        sizes = dict(
            B=B, KV=KV, G=G, hd=hd, page_size=ps, pages_per_seq=ctx // ps,
            kv_dtype=jnp.bfloat16, q_dtype=jnp.bfloat16, pools=len(case[1]),
        )
        CP, BS, rule, rule_buffers = _decode_sizes(**sizes)
        other = 3 - rule
        work, _, _, full_chunks = decode_trips(lens, CP * ps, BS, 1)
        # form -> (items, chunk buffers, what makes the kernel); a form
        # that another already is (a forced count the rule gives) goes
        forms = {}
        if parent is not None:
            forms["parent"] = (rule, 0, parent_kernel)
        forms["rule"] = (rule, rule_buffers, decode_cell_kernel)
        for n in forced:
            forms[f"b{n}"] = (rule, n, decode_cell_kernel)
        forms[f"items_{other}"] = (
            other, _decode_sizes(**sizes, items=other)[3],
            decode_cell_kernel,
        )
        kernels = {}
        for form, key in forms.items():
            if key not in kernels.values():
                kernels[form] = key
        first = next(iter(kernels))
        made = {
            form: make(name, items=n, buffers=buffers)
            for form, (n, buffers, make) in kernels.items()
        }
        for form in list(made)[1:]:
            if not all(map(bool, same_bits(
                made[form], made[first], case, news
            ))):
                raise SystemExit(f"{name}: {form} differs from {first}")
        one = made["rule"] if rule == 1 else made[f"items_{other}"]
        if not latent and not all(
            map(bool, same_bits(one, scatter_then_kernel, case, news))
        ):
            raise SystemExit(f"{name}: the kernel's write differs from "
                             "the scatter's")
        live_bytes = int(lens.sum()) * len(case[1]) * KV * hd * 2
        line = {
            "kernel": "mla_decode_attention" if latent
            else "paged_decode_attention",
            "cell": name,
            "shape": f"B{B} KV{KV} G{G} hd{hd} ps{ps} ctx{ctx}, {live} live",
            "live_tokens": int(lens.sum()),
            "chunk_tokens": CP * ps, "block_slots": BS, "rule_items": rule,
            "rule_buffers": rule_buffers, "work_items": work,
            "full_chunk_share": round(full_chunks, 3),
            "same_bits_as": first,
        }
        seconds = time_decode_layers({
            **made,
            **{
                (form, "hollow"): make(
                    name, items=n, buffers=buffers, hollow=True
                )
                for form, (n, buffers, make) in kernels.items()
            },
        }, case)
        for form, (n, buffers, _) in kernels.items():
            _, trips, full, _ = decode_trips(lens, CP * ps, BS, n)
            took, hollow = seconds[form], seconds[form, "hollow"]
            line[form] = {
                "items": n, "buffers": buffers, "trips": trips,
                "full_trip_pct": round(100 * full / trips, 1),
                "us": round(took * 1e6, 1),
                "hbm_roofline_pct": round(
                    100 * live_bytes / HBM_BYTES_PER_S / took, 1
                ),
                "hollow_us": round(hollow * 1e6, 1),
                "us_an_item": round(took * 1e6 / work, 4),
                "hollow_us_an_item": round(hollow * 1e6 / work, 4),
            }
        yield line


# (vocabulary, rows): the `sample` scope's shapes in the benchmark's
# configurations (Qwen2.5's vocabulary; qwen3-next's quarter of it; the
# quarter the nemotron and mistral cells hold, at nemotron's 192 slots)
SAMPLE_SHAPES = ((151936, 256), (37984, 256), (32768, 192))
SAMPLE_WIDTHS = (1, 16, 32, 64, 128, 256)


def _edits_by_parent_scatter(logits, bias_ids, bias_vals, steps, min_toks,
                             stop_ids):
    """What a step did until PR 34: a scatter-add for `logit_bias`, then
    a scatter into a copy and a select between copy and original for the
    `min_tokens` floor."""
    rows = jnp.arange(logits.shape[0])[:, None]
    logits = logits.astype(jnp.float32).at[
        jnp.broadcast_to(rows, bias_ids.shape), bias_ids
    ].add(bias_vals, mode="drop")
    masked = logits.at[
        jnp.broadcast_to(rows, stop_ids.shape), stop_ids
    ].set(-1e30, mode="drop")
    return jnp.where((steps < min_toks)[:, None], masked, logits)


def _edits_by(bias, floor):
    """The program's own two edits (ops/sampling.py), one form forced."""

    def edits(logits, bias_ids, bias_vals, steps, min_toks, stop_ids):
        logits = bias(logits.astype(jnp.float32), bias_ids, bias_vals)
        return floor(logits, steps, min_toks, stop_ids)

    return edits


def sample_edit_forms():
    from vgate_tpu.ops import sampling

    return {
        "parent_scatter": _edits_by_parent_scatter,
        "scatter": _edits_by(
            sampling._bias_by_scatter, sampling._floor_by_scatter
        ),
        "compare": _edits_by(
            sampling._bias_by_compare, sampling._floor_by_compare
        ),
    }


def sample_edit_case(V, B, width, stop_width=2, seed=0):
    """A decode step's sampling inputs at a cell's shape: float32 logits,
    `width` distinct bias ids a row at +-100 (the benchmark's requests
    carry 16), every row below its `min_tokens` floor with `stop_width`
    stop ids (the cells': eos and one extra)."""
    rng = np.random.default_rng(seed)
    logits = jax.random.normal(jax.random.PRNGKey(seed), (B, V), jnp.float32)
    ids = np.stack([rng.permutation(V)[:width] for _ in range(B)])
    vals = rng.choice([100.0, -100.0], size=(B, width))
    stops = np.stack([rng.permutation(V)[:stop_width] for _ in range(B)])
    return (
        logits, jnp.asarray(ids, jnp.int32), jnp.asarray(vals, jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.full((B,), 1024, jnp.int32),
        jnp.asarray(stops, jnp.int32),
    )


def time_sample_scope(edits, sampler, case, loop=LOOP):
    """Median seconds of one `sample` scope (the edits, or none, then
    the sampler) over `loop` of them in one program; the logits depend
    on the previous step's tokens, so nothing is hoisted."""
    from vgate_tpu.ops.sampling import sample_tokens

    logits, bias_ids, bias_vals, steps, min_toks, stop_ids = case
    B = logits.shape[0]
    temps = jnp.full((B,), 0.0 if sampler == "argmax" else 0.8)
    top_ps = jnp.full((B,), 0.95)
    top_ks = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(3)

    @jax.jit
    def run(logits):
        def body(carry, i):
            x = logits + carry
            if edits is not None:
                x = edits(x, bias_ids, bias_vals, steps + i, min_toks,
                          stop_ids)
            tokens = sample_tokens(
                x, temps, top_ps, top_ks, jax.random.fold_in(key, i),
                steps=steps + i, all_greedy=sampler == "argmax",
            )
            return tokens[0].astype(jnp.float32) * 0.0, tokens

        return jax.lax.scan(
            body, jnp.float32(0), jnp.arange(loop, dtype=jnp.int32)
        )[1]

    return _median_time(run, logits, loop=loop)


def bench_sample_edits(shapes=SAMPLE_SHAPES, widths=SAMPLE_WIDTHS,
                       forms=None):
    """The decode step's `sample` scope alone, at the cells' shapes: the
    sampler with no edit, then `logit_bias` and the `min_tokens` floor
    ahead of it in each form, over the bias widths the API allows.  Every
    form has to give the parent's bits."""
    forms = forms or sample_edit_forms()
    for V, B in shapes:
        # top-k sampling at the widest vocabulary only (no cell samples)
        for sampler in ("argmax", "topk") if V == shapes[0][0] else ("argmax",):
            alone = time_sample_scope(None, sampler, sample_edit_case(V, B, 1))
            for width in widths:
                case = sample_edit_case(V, B, width)
                want = jax.jit(forms["parent_scatter"])(*case)
                line = {
                    "scope": "sample", "vocab": V, "rows": B,
                    "sampler": sampler, "bias_width": width,
                    "stop_width": int(case[5].shape[1]),
                    "sampler_alone_us": round(alone * 1e6, 1),
                }
                for label, edits in forms.items():
                    if label != "parent_scatter" and not bool(
                        jnp.array_equal(jax.jit(edits)(*case), want)
                    ):
                        raise SystemExit(
                            f"{label} at width {width}: not the parent's bits"
                        )
                    seconds = time_sample_scope(edits, sampler, case)
                    line[f"{label}_us"] = round(seconds * 1e6, 1)
                yield line


# the decode chunk's tail at the cells' shapes: (rows, width, vocabulary,
# tied): the 1.5B; the LFM2 cut; the 7B-l14; nemotron's / mistral's
# quarter vocabulary at nemotron's 192 slots; qwen3-next's; K-EXAONE's;
# and GLM's 48 slots, where the kernel loses and the shape rule
# (ops/pallas/greedy_head.py worth_fusing) keeps the three passes
HEAD_SHAPES = (
    (256, 1536, 151936, True), (256, 2048, 65536, True),
    (256, 3584, 152064, False), (192, 4096, 32768, False),
    (256, 2048, 37984, True), (192, 6144, 19200, False),
    (48, 6144, 19360, False),
)
HEAD_TILES = (512, 1024)  # beside the rule's; 2,048 outgrow VMEM
HEAD_THRESHOLD = 1.0e4


def greedy_head_case(B, D, V, tied, ids="shared", seed=0):
    """A greedy decode step's tail inputs: the head (bf16, drawn as
    `init_params` draws it), a 16-wide `logit_bias` at +-100 and two
    stop ids a row, every row below its floor.  ``shared``: every row
    names the same ids, low in the vocabulary and at its end (the
    benchmark's requests: 16 printable bytes and the model's stops);
    ``random``: each row its own, anywhere (the edits' worst case)."""
    rng = np.random.default_rng(seed)
    head = (0.02 * jax.random.normal(
        jax.random.PRNGKey(seed), (V, D) if tied else (D, V), jnp.float32
    )).astype(jnp.bfloat16)
    if ids == "shared":
        bias = np.tile(3 + np.arange(65, 81), (B, 1))
        stops = np.tile([V - 1, V - 2], (B, 1))
    else:
        bias = np.stack([rng.permutation(V)[:16] for _ in range(B)])
        stops = np.stack([rng.permutation(V)[:2] for _ in range(B)])
    vals = rng.choice([100.0, -100.0], size=(B, 16))
    return (
        head, jnp.asarray(bias, jnp.int32), jnp.asarray(vals, jnp.float32),
        jnp.full((B,), 1 << 20, jnp.int32), jnp.asarray(stops, jnp.int32),
    )


def _head_product(x, head, tied):
    return jnp.einsum(
        "bd,vd->bv" if tied else "bd,dv->bv", x, head,
        preferred_element_type=jnp.float32)


def head_by_three_passes(x, head, bias_ids, bias_vals, steps, min_toks,
                         stop_ids, tied):
    """The present path, as `_decode_chunk` traces it: the head's
    product written as `f32[B, V]`, the guard, the edits, the argmax."""
    from vgate_tpu import integrity
    from vgate_tpu.ops.sampling import (
        apply_logit_bias, sample_tokens, suppress_stop_tokens)

    logits = _head_product(x, head, tied)
    flags = integrity.logit_guard(logits, HEAD_THRESHOLD)
    logits = suppress_stop_tokens(
        apply_logit_bias(logits, bias_ids, bias_vals), steps, min_toks,
        stop_ids)
    return sample_tokens(logits, None, None, None, None,
                         all_greedy=True), flags


def head_by_one_reduce(x, head, bias_ids, bias_vals, steps, min_toks,
                       stop_ids, tied):
    """Form (i): the same consumers as ONE variadic reduce whose only
    producer is the product (value and first index of the edited
    maximum, and the guard's maximum of |raw| as bits)."""
    from vgate_tpu import integrity
    from vgate_tpu.ops.sampling import apply_logit_bias, suppress_stop_tokens

    logits = _head_product(x, head, tied)
    edited = suppress_stop_tokens(
        apply_logit_bias(logits, bias_ids, bias_vals), steps, min_toks,
        stop_ids)
    bits = jax.lax.bitcast_convert_type(logits, jnp.int32) & 0x7FFFFFFF
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)

    def fold(a, b):
        (va, ia, ba), (vb, ib, bb) = a, b
        take = (vb > va) | ((vb == va) & (ib < ia))
        return (jnp.where(take, vb, va), jnp.where(take, ib, ia),
                jnp.maximum(ba, bb))

    _, tokens, most = jax.lax.reduce(
        (edited, iota, bits),
        (jnp.float32(-jnp.inf), jnp.int32(0x7FFFFFFF), jnp.int32(0)),
        fold, (1,))
    flags = (
        jnp.where(most >= 0x7F800000, integrity.FLAG_NONFINITE, 0)
        | jnp.where(most == 0, integrity.FLAG_ZERO, 0)
        | jnp.where(
            jax.lax.bitcast_convert_type(most, jnp.float32)
            >= HEAD_THRESHOLD, integrity.FLAG_SATURATED, 0))
    return tokens, flags.astype(jnp.uint8)


def head_by_kernel(tile):
    """Form (ii): ops/pallas/greedy_head.py at a forced tile (0: the
    rule's)."""
    from vgate_tpu.ops.pallas.greedy_head import greedy_head_pallas
    from vgate_tpu.ops.sampling import live_stop_ids

    def run(x, head, bias_ids, bias_vals, steps, min_toks, stop_ids, tied):
        V = head.shape[0] if tied else head.shape[1]
        live = live_stop_ids(V, steps, min_toks, stop_ids)
        return greedy_head_pallas(
            x, head, bias_ids, bias_vals, live, tied=tied, guard=True,
            threshold=HEAD_THRESHOLD, tile=tile)

    return run


def head_forms(tiles=HEAD_TILES):
    return {
        "three_passes": head_by_three_passes,
        "one_reduce": head_by_one_reduce,
        "kernel": head_by_kernel(0),
        **{f"kernel_{t}": head_by_kernel(t) for t in tiles},
    }


def head_program(form, B, D, tied, loop):
    """`loop` steps of one form in one program: each step's rows are
    drawn from the step's index and lean on the last step's tokens, so
    nothing is hoisted and no two steps see the same rows.  Returns
    ([loop, B] tokens, [loop, B] flags)."""

    @jax.jit
    def run(head, bias_ids, bias_vals, min_toks, stop_ids):
        def body(carry, i):
            x = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(11), i), (B, D),
                jnp.float32) + carry
            tokens, flags = form(
                x.astype(jnp.bfloat16), head, bias_ids, bias_vals,
                jnp.zeros((B,), jnp.int32) + i, min_toks, stop_ids, tied)
            return tokens[0].astype(jnp.float32) * 0.0, (tokens, flags)

        return jax.lax.scan(
            body, jnp.float32(0), jnp.arange(loop, dtype=jnp.int32))[1]

    return run


def bench_greedy_head(shapes=HEAD_SHAPES, loop=64):
    """A greedy decode step's tail (the head's product, the guard, the
    edits, the argmax), us a step of the present three passes and of
    each form tried, beside the two floors (the head's bytes at the
    chip's bandwidth, the product at 197 TFLOP/s); each form's tokens
    and flags against the present path's over `loop` steps of random
    rows.  The drawing of a step's rows (B x D normals) is in every
    form's time alike."""
    for B, D, V, tied in shapes:
        for ids in ("shared", "random"):
            case = greedy_head_case(B, D, V, tied, ids)
            line = {
                "scope": "greedy_head", "rows": B, "width": D, "vocab": V,
                "tied": tied, "ids": ids,
                "bytes_floor_us": round(
                    1e6 * V * D * 2 / HBM_BYTES_PER_S, 1),
                "product_floor_us": round(1e6 * 2 * B * D * V / 197e12, 1),
            }
            want = None
            for label, form in head_forms().items():
                run = head_program(form, B, D, tied, loop)
                try:
                    got = jax.tree.map(np.asarray, run(*case))
                except Exception as exc:  # noqa: BLE001: a form the chip refuses
                    line[f"{label}_error"] = str(exc)[:200]
                    continue
                seconds = _median_time(run, *case, iters=5, loop=loop)
                line[f"{label}_us"] = round(seconds * 1e6, 1)
                if want is None:
                    want = got
                    continue
                line[f"{label}_token_diff_pct"] = round(
                    100 * float(np.mean(got[0] != want[0])), 4)
                line[f"{label}_flag_diff_pct"] = round(
                    100 * float(np.mean(got[1] != want[1])), 4)
            yield line


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


def bench_swa_prefill(S=8192, H=64, KV=8, hd=128, window=128,
                      blocks=((128, 128), (256, 128), (512, 128),
                              (1024, 128), (256, 256), (512, 256))):
    """A window layer's prompt attention at the K-EXAONE cut's shape
    (one 8,192-row prompt, 64 query heads on 8 KV heads, window 128):
    the flash kernel over a BAND of key blocks at several block sizes,
    against the same kernel over every block under the diagonal with the
    window as a mask (1,024-row blocks: what a full layer's launch
    costs), each checked against the first."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
        swa_prefill_attention_pallas,
    )

    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, KV, hd),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, KV, hd),
                          jnp.bfloat16)
    lens = jnp.asarray([S - 37], jnp.int32)
    masked = lambda q, k, v, lens: flash_prefill_attention_pallas(
        q, k, v, lens, block_q=1024, block_k=1024, window=window)
    want = np.asarray(jax.jit(masked)(q, k, v, lens), np.float32)
    yield {"kernel": "flash_prefill_attention (window as a mask)",
           "shape": f"S{S} H{H} KV{KV} hd{hd} w{window}", "blocks": [1024] * 2,
           "us": round(_median_time(_looped(masked), q, k, v, lens) * 1e6, 1)}
    from vgate_tpu.ops.pallas.flash_prefill import swa_blocks

    for bq, bk in blocks:
        band = lambda q, k, v, lens, bq=bq, bk=bk: (
            swa_prefill_attention_pallas(q, k, v, lens, window,
                                         block_q=bq, block_k=bk))
        got = np.asarray(jax.jit(band)(q, k, v, lens), np.float32)
        yield {"kernel": "swa_prefill_attention (band)",
               "blocks": [bq, bk], "rule": (bq, bk) == swa_blocks(window),
               "max_abs_diff": float(np.abs(got - want)[0, :S - 37].max()),
               "us": round(
                   _median_time(_looped(band), q, k, v, lens) * 1e6, 1)}


# a window layer's prompt launch at the two cells that have one: the
# arguments of ``bench_swa_prefill`` (K-EXAONE's are its defaults)
SWA_PREFILL_CASES = {
    "k-exaone-236b-a23b-l5e16": {},
    "mellum2-12b-a2.5b-l8": dict(
        S=16384, H=32, KV=4, window=1024,
        blocks=((256, 128), (256, 256), (512, 256), (512, 512),
                (1024, 256), (1024, 512), (1024, 1024), (2048, 512))),
}


def bench_mellum_decode(B=80, H=32, KV=4, hd=128, ps=32, window=1024,
                        lens=(9700, 16000)):
    """A decode step's two attention launches at the Mellum2 cut's shape
    (80 slots, 32 query heads on 4 KV heads of 128, contexts uniform
    over the long-agent traffic's live range): a WINDOW layer's over the
    slots' rings of 33 pages (``swa_decode_attention_pallas``: five
    work-list items a slot) and a FULL layer's over the pool
    (``paged_decode_attention_pallas``: 38-63 items a slot), each us a
    launch and its share of the HBM roofline over the rows it has to
    read (min(context, window) and context)."""
    from vgate_tpu.models import hybrid
    from vgate_tpu.ops.pallas.paged_attention import (
        _decode_sizes, paged_decode_attention_pallas,
        swa_decode_attention_pallas,
    )

    R = -(-window // ps) + 1
    rng = np.random.default_rng(57)
    seq_lens = jnp.asarray(rng.integers(*lens, size=B), jnp.int32)
    n_pages = -(-lens[1] // ps)
    key = jax.random.PRNGKey(57)
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    draw = lambda i, pages: jax.random.normal(
        jax.random.fold_in(key, i), (KV, pages, ps, hd), jnp.bfloat16)
    ring_tables = hybrid.ring_tables(jnp.arange(B), n_pages, B, R)
    pool_tables = jnp.asarray(
        1 + rng.permutation(B * n_pages).reshape(B, n_pages), jnp.int32)
    row_bytes = 2 * KV * hd * 2
    sizes = _decode_sizes(B, KV, H // KV, hd, ps, n_pages, jnp.bfloat16,
                          jnp.bfloat16)
    # attention alone: a launch that also writes the step's row aliases
    # its pool, and a probe that throws the written pool away makes XLA
    # copy 1.3 GB a launch (the first reading of this probe, PR 57)
    cases = {
        "swa_decode_attention (33-page rings)": (
            lambda q, k, v, t: swa_decode_attention_pallas(
                q, k, v, t, seq_lens, window),
            draw(2, 1 + B * R), draw(3, 1 + B * R), ring_tables,
            int(jnp.minimum(seq_lens, window).sum())),
        "paged_decode_attention (the full layers' pool)": (
            lambda q, k, v, t: paged_decode_attention_pallas(
                q, k, v, t, seq_lens),
            draw(4, 1 + B * n_pages), draw(5, 1 + B * n_pages), pool_tables,
            int(seq_lens.sum())),
    }
    for name, (fn, k, v, tables, rows) in cases.items():
        took = _median_time(_looped(fn), q, k, v, tables)
        yield {"kernel": name,
               "shape": f"B{B} H{H} KV{KV} hd{hd} ps{ps} w{window}",
               "chunk_pages": sizes[0], "block_slots": sizes[1],
               "items_a_trip": sizes[2], "buffers": sizes[3],
               "rows_read": rows, "us": round(took * 1e6, 1),
               "hbm_roofline_pct": round(
                   100 * rows * row_bytes / HBM_BYTES_PER_S / took, 1)}


def bench_mellum_grouped(E=64, D=2304, F=896, pairs=(640, 32768)):
    """The grouped expert product at the Mellum2 cut's two shapes, (2,304
    -> 896) and (896 -> 2,304), over 64 experts' uniform groups at a
    decode step's 640 pairs and a prompt block's 32,768: us a launch at
    the rule's column tile (ops/moe.py ``_column_tile``: 896 and 768)
    and at the other multiples of 128 that divide the columns, beside
    the larger of the weights' bytes over the HBM rate and the products'
    operations over the bf16 rate."""
    from vgate_tpu.ops.moe import _column_tile, _row_tile
    from vgate_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

    key = jax.random.PRNGKey(57)
    for K, N in ((D, F), (F, D)):
        w = (0.02 * jax.random.normal(key, (1, E, K, N), jnp.float32)
             ).astype(jnp.bfloat16)
        rule = _column_tile(K, N, 2)
        tiles = [rule] + [t for t in range(N, 0, -128)
                          if N % t == 0 and t != rule
                          and K * t * 2 <= (4 << 20)]
        for M in pairs:
            tm = _row_tile(M)
            rows = jax.random.normal(
                jax.random.fold_in(key, M), (M, K), jnp.bfloat16)
            sizes = jnp.full((E,), M // E, jnp.int32)
            least = max(E * K * N * 2 / HBM_BYTES_PER_S,
                        2 * M * K * N / BF16_FLOPS_PER_S)
            # the loop's carry is the lhs's shape: the result cut or padded
            fit = (lambda out: out[:, :K]) if N >= K else (
                lambda out: jnp.pad(out, ((0, 0), (0, K - N))))
            for tn in tiles:
                fn = _looped(lambda r, w, sizes, tn=tn: fit(
                    grouped_matmul_pallas(
                        r, w, sizes, jnp.int32(0), tm=tm, tn=tn)))
                took = _median_time(fn, rows, w, sizes)
                yield {"kernel": "grouped_matmul", "shape": f"E{E} {K}->{N}",
                       "pairs": M, "row_tile": tm, "column_tile": tn,
                       "rule": tn == rule, "us": round(took * 1e6, 1),
                       "roofline_pct": round(100 * least / took, 1)}


# the prompt attention launch at the cells' shapes: (B, bucket rows, of
# them real, H, KV, head width, block rows, keys a row picks under a
# selection or 0, ``skip_padding``).  GLM's is ONE of its eight groups
# of eight heads (models/hybrid.py ``_head_groups``); the dense bucket
# of 2,048 rows in 256-row blocks is the control: it is bound by grid
# steps, and two bodies under ``pl.when`` must not cost it one
FLASH_PREFILL_CELLS = {
    "glm-5.2-l5e16": (1, 16384, 11000, 8, 8, 256, 1024, 2048, True),
    "keye-vl-2.0-30b-a3b-l12e32": (
        1, 16384, 12288, 32, 4, 128, 1024, 2048, True),
    "mistral-small-4-119b-l4e32": (
        1, 8192, 5550, 32, 32, 128, 1024, 0, True),
    "qwen2.5-7b-l14": (8, 2048, 1280, 28, 4, 128, 256, 0, False),
    # no cell's: a score cap and a window that cuts some tiles (Gemma-2's
    # form), for the bits (interpret mode on a CPU cannot hold them: XLA
    # fuses the cap's product into the subtraction behind it)
    "softcap-window": (1, 4096, 3000, 8, 4, 256, 1024, 0, False,
                       {"softcap": 50.0, "window": 2500}),
}


def _selection_mask(key, rows, picks, block=2048):
    """[1, rows, rows] int8: row i keeps each of its i + 1 causal keys
    with probability ``picks / (i + 1)`` (all of them while it has no
    more): the density a selection of ``picks`` keys a row has, spread
    uniformly as seed-made index weights spread it."""
    def some(first):
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(rows)[None, :]
        u = jax.random.uniform(jax.random.fold_in(key, first), (block, rows))
        return ((j <= i) & (u * (i + 1) < picks)).astype(jnp.int8)

    return jnp.concatenate(
        [jax.jit(some)(first) for first in range(0, rows, block)])[None]


def bench_flash_prefill_cells(cells=None, forced=()):
    """The prompt attention launch (ops/pallas/flash_prefill.py) at the
    shapes of ``FLASH_PREFILL_CELLS``: us a launch and us a live tile a
    head for the parent's kernel (where ``_parent`` holds a checkout),
    for this tree's with every tile through the edge body
    (``_all_edge``: under a selection that is the bias shared by a block
    of heads ALONE) and for this tree's as served, each result
    ``array_equal`` to the first's on the chip; ``forced``: heads a
    program of the masked form beside the rule's (the probe's alone)."""
    from vgate_tpu.ops.pallas import flash_prefill as tree

    parent = _parent_module(
        "_parent/vgate_tpu/ops/pallas/flash_prefill.py", "parent_flash")
    for name in cells or FLASH_PREFILL_CELLS:
        B, S, real, H, KV, hd, block, picks, skip, *extra = (
            FLASH_PREFILL_CELLS[name])
        key = jax.random.PRNGKey(54)
        q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(
            jax.random.fold_in(key, 1), (B, S, KV, hd), jnp.bfloat16)
        v = jax.random.normal(
            jax.random.fold_in(key, 2), (B, S, KV, hd), jnp.bfloat16)
        lens = jnp.full((B,), real, jnp.int32)
        kw = dict(block_q=block, block_k=block, skip_padding=skip,
                  **(extra[0] if extra else {}))
        rest = (k, v, lens)
        if picks:
            kw["name"] = "dsa_prefill_attention_pallas"
            rest += (_selection_mask(jax.random.fold_in(key, 3), S, picks),)
        tiles, interior = tree.tile_counts(
            [real] * B, S, S, block, block, skip_padding=skip,
            window=kw.get("window", 0))
        rule = tree.head_block(H, H // KV, block, block, hd, 2)
        forms = [("parent", parent, {}, None)] if parent else []
        forms += [("all_edge", tree, {"_all_edge": True}, None),
                  ("change", tree, {}, None)]
        forms += [(f"change_hb{hb}", tree, {}, hb) for hb in forced
                  if picks and hb != rule and H % hb == 0
                  and hb % (H // KV) == 0]
        want = None
        for form, module, more, hb in forms:
            def op(q, k, v, lens, *mask, module=module, more=more):
                return module.flash_prefill_attention_pallas(
                    q, k, v, lens, **({"mask": mask[0]} if mask else {}),
                    **kw, **more)

            rule_fn = tree.head_block
            if hb:  # the probe's alone: no caller forces it (the
                # kernel's own jit does not know the rule changed)
                jax.clear_caches()
                tree.head_block = lambda *a, hb=hb: hb
            try:
                got = np.asarray(jax.jit(op)(q, *rest), np.float32)
                t = _median_time(_looped(op), q, *rest, iters=6)
            except Exception as e:  # a forced size that does not fit
                yield {"probe": "flash_prefill_cells", "cell": name,
                       "form": form, "error": str(e)[:200]}
                continue
            finally:
                if hb:
                    tree.head_block = rule_fn
                    jax.clear_caches()
            want = got if want is None else want
            yield {
                "probe": "flash_prefill_cells", "cell": name, "form": form,
                "shape": f"B{B} S{S} real{real} H{H} KV{KV} hd{hd}",
                "blocks": block, "picks": picks,
                "heads_a_program": (hb or rule) if picks and module is tree
                else 1,
                "tiles_a_head": tiles, "interior_tiles_a_head": interior,
                "us_a_launch": round(t * 1e6, 1),
                "us_a_tile": round(t * 1e6 / (tiles * H), 3),
                "equal_to_first": bool(np.array_equal(got, want)),
            }


def bench_flash_prefill_hollow(widths=(128, 256), block=1024, tiles=64):
    """Where a prompt attention tile's time is, by hollow bodies: a
    kernel of the prompt kernel's blocks and scratch (``[block, hd]``
    bf16 q, k, v in VMEM, fetched once; a float32 accumulator) whose
    every grid step is ONE interior tile of one head, with only its two
    products at float32 operands as the prompt kernel makes them
    (``products``), the same at bf16 operands (``products_bf16``), only
    its softmax over a standing ``[block, block]`` float32 tile (max,
    subtract, exponent, sum: ``softmax``), and both (``tile``): us a
    tile of each, beside the products' floor at the bf16 peak."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(q_ref, k_ref, v_ref, out_ref, acc, m, s_ref, *, form):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            m[...] = jnp.full_like(m, -1e30)
            s_ref[...] = jax.lax.dot_general(
                q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        wide = jnp.bfloat16 if form == "products_bf16" else jnp.float32
        dims = (((1,), (1,)), ((), ()))
        if form == "softmax":
            scores = s_ref[...]
        else:
            scores = jax.lax.dot_general(
                q_ref[...].astype(wide), k_ref[...].astype(wide), dims,
                preferred_element_type=jnp.float32)
        if form in ("softmax", "tile"):
            m_prev = m[:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            m[...] = jnp.broadcast_to(
                m_new + jnp.sum(p, axis=-1, keepdims=True) * 0, m.shape)
        else:
            p = scores
        if form == "softmax":
            acc[...] = acc[...] + p[:, :acc.shape[1]]
        else:
            acc[...] = acc[...] + jax.lax.dot_general(
                p.astype(wide), v_ref[...].astype(wide),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(step == tiles - 1)
        def _():
            out_ref[...] = acc[...] + m[:, :1]

    for hd in widths:
        key = jax.random.PRNGKey(hd)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (block, hd), jnp.bfloat16)
                   for i in range(3))
        floor = 4 * block * block * hd / 197e12 * 1e6
        for form in ("products", "products_bf16", "softmax", "tile"):
            whole = pl.BlockSpec((block, hd), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM)
            call = pl.pallas_call(
                functools.partial(kernel, form=form),
                grid=(tiles,), in_specs=[whole] * 3, out_specs=whole,
                out_shape=jax.ShapeDtypeStruct((block, hd), jnp.float32),
                scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32),
                                pltpu.VMEM((block, 128), jnp.float32),
                                pltpu.VMEM((block, block), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=64 * 1024 * 1024))
            t = _median_time(_looped(call), q, k, v, iters=6)
            yield {"probe": "flash_prefill_hollow", "hd": hd, "block": block,
                   "form": form, "us_a_tile": round(t * 1e6 / tiles, 3),
                   "products_floor_us_at_bf16_peak": round(floor, 3)}


# ONE expert layer of each configuration that holds a share of its
# experts, as its cell runs it: (preset, held experts, rows of the
# prompt program's wave, of them real, rows of a decode step)
EXPERT_LAYER_CONFIGS = {
    "k-exaone-236b-a23b-l5e16": (
        "LGAI-EXAONE/K-EXAONE-236B-A23B", 16, 8192, 5550, 192),
    "mistral-small-4-119b-l4e32": (
        "mistralai/Mistral-Small-4-119B-2603", 32, 8192, 5550, 256),
    "qwen3-next-80b-a3b-l8e128": (
        "Qwen/Qwen3-Next-80B-A3B-Instruct", 128, 1024, 640, 256),
    "nemotron-3-super-120b-a12b-l11e128": (
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16", 128, 1024, 640,
        192),
}


def expert_layer_case(name, seed=0, abstract=None):
    """(spec, lp, stack) of one expert layer of configuration ``name``:
    the routed part alone (no shared expert), bf16 tensors N(0, 0.02),
    the experts' matrices as a stack of one layer.  ``abstract(shape,
    dtype)`` makes shapes instead (an AOT compile)."""
    import dataclasses

    from vgate_tpu.models.specs import spec_for_model_id

    preset, held = EXPERT_LAYER_CONFIGS[name][:2]
    spec = dataclasses.replace(
        spec_for_model_id(preset), name=name, num_experts=held,
        shared_expert_intermediate_size=0, n_shared_experts=0)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    draw = abstract or (lambda shape, dtype: (
        0.02 * jax.random.normal(next(keys), shape, jnp.float32)
    ).astype(dtype))
    D, W, F = spec.hidden_size, spec.expert_in, spec.expert_width
    lp = {"router": draw((D, spec.router_experts), jnp.bfloat16)}
    if spec.router_scoring == "sigmoid":
        lp["router_bias"] = draw((spec.router_experts,), jnp.float32)
    if spec.moe_latent_size:
        lp["latent_in"] = {"w": draw((D, W), jnp.bfloat16)}
        lp["latent_out"] = {"w": draw((W, D), jnp.bfloat16)}
    stack = {n: {"w": draw((1, held) + ((F, W) if n == "down" else (W, F)),
                           jnp.bfloat16)}
             for n in spec.expert_stacks}
    return spec, lp, stack


def _combine_by_gather(out, y, pairs, live, K):
    """The other form of ops/moe.py ``_combine``: an inverse of the
    trip's pairs over all T x K choices, dead ones pointing at one zero
    row, a gather through it and the choices' sum."""
    T, C = out.shape[0], y.shape[0]
    inverse = jnp.full((T * K,), C, jnp.int32).at[
        jnp.where(live, pairs, T * K)].set(
            jnp.arange(C, dtype=jnp.int32), mode="drop")
    y = jnp.concatenate([y, jnp.zeros_like(y[:1])])
    return out + jnp.sum(y[inverse].reshape(T, K, -1), axis=1)


def expert_layer_program(spec, rows, real, block, form, combine, loop=4,
                         parent=None):
    """``loop`` expert layers chained in one program over ``rows`` rows
    of which ``real`` are (the rest masked).  ``form`` ``capacity``: this
    tree's layer in blocks of ``block`` rows; ``all``: its dispatch
    takes every pair at a time, as before PR 39; ``parent``: the layer
    of ``parent``, another checkout's ``ops/moe.py`` (its own dispatch,
    its own blocks).  ``combine`` ``gather``: the combine's other form."""
    from vgate_tpu.models.decoder import _act
    from vgate_tpu.ops import moe

    layer_fn = (parent if form == "parent" else moe).expert_layer
    mask = jnp.arange(rows) < real
    act = lambda x32: _act(x32, spec)

    def run(x, lp, stack):
        def body(carry, _):
            out, stats = layer_fn(
                x + 0 * carry.astype(x.dtype), lp, spec, act, row_mask=mask,
                use_pallas=True, layer=jnp.int32(0), stack=stack)
            return out.astype(jnp.float32), stats

        return jax.lax.scan(
            body, jnp.zeros(x.shape, jnp.float32), None, length=loop)

    def traced(x, lp, stack):
        # the module's rule and combine as this variant wants them, for
        # the duration of the trace
        saved = moe.block_tokens, moe.capacity, moe._combine
        try:
            if form != "parent":
                moe.block_tokens = lambda spec: block
            if form == "all":
                moe.capacity = lambda spec, pairs: pairs
            if combine == "gather":
                moe._combine = _combine_by_gather
            return run(x, lp, stack)
        finally:
            moe.block_tokens, moe.capacity, moe._combine = saved

    return jax.jit(traced)


def _parent_module(path, name):
    """A module of the checkout under ``_parent`` (`git archive` of the
    parent commit), or None."""
    import importlib.util

    if not os.path.exists(path):
        return None
    found = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def _parent_moe(path="_parent/vgate_tpu/ops/moe.py"):
    """ops/moe.py of the parent commit, or None."""
    return _parent_module(path, "parent_moe")


def bench_expert_layer(configs=None, loop=4):
    """ONE expert layer (router, dispatch, grouped products, combine; no
    shared expert) at the four share-holding cells' prompt and decode
    shapes: µs a layer by block rows x what the dispatch takes at a time
    (``capacity``: this tree's rule; ``all``: every pair) x the
    combine's form (``scatter``-add, or ``gather`` through an inverse),
    with the parent's layer first where ``_parent`` holds a checkout;
    each variant's result against the first's, and its counters."""
    from vgate_tpu.ops import moe

    parent = _parent_moe()
    for name in configs or EXPERT_LAYER_CONFIGS:
        _, _, wave, real, step = EXPERT_LAYER_CONFIGS[name]
        spec, lp, stack = expert_layer_case(name)
        K = spec.experts_per_token
        for phase, rows, live in (("prompt", wave, real),
                                  ("decode", step, step)):
            x = jax.random.normal(
                jax.random.PRNGKey(3), (rows, spec.hidden_size),
                jnp.bfloat16)
            variants = ([("parent", min(parent.block_tokens(spec), rows),
                          "inverse")] if parent else [])
            for block in (1024, 2048, 4096, 8192):
                block = min(block, rows)
                if ("all", block, "scatter") in variants:
                    break  # the whole wave was one block already
                variants += [("all", block, "scatter"),
                             ("capacity", block, "scatter"),
                             ("capacity", block, "gather")]
            want = None
            for form, block, combine in variants:
                if form == "all" and block * K * spec.expert_in > (
                        moe.BLOCK_VALUES):
                    continue  # the temporaries the old rule refused
                fn = expert_layer_program(
                    spec, rows, live, block, form, combine, loop, parent)
                out, stats = fn(x, lp, stack)
                got = np.asarray(out, np.float32)[:live]
                want = got if want is None else want
                yield {
                    "probe": "expert_layer", "config": name, "phase": phase,
                    "rows": rows, "real": live, "form": form, "block": block,
                    "at_a_time": (moe.capacity(spec, block * K)
                                  if form == "capacity" else block * K),
                    "combine": combine,
                    "us": round(_median_time(
                        fn, x, lp, stack, iters=6, loop=loop) * 1e6, 1),
                    "max_abs_diff": float(np.abs(got - want).max()),
                    "stats": np.asarray(stats)[0].tolist(),
                }


# the two configurations whose prompt programs are models/decoder.py's
# own pass: (preset, layers the probe keeps)
DENSE_PROMPT_CONFIGS = {
    "qwen2.5-1.5b": ("Qwen/Qwen2.5-1.5B-Instruct", 4),
    "qwen2.5-7b-l14": ("Qwen/Qwen2.5-7B-Instruct", 4),
}
# what a group of 8 rows of 2,048 holds: every row filled so far, and
# the cells' own worst group (five prompts run as eight rows)
DENSE_PROMPT_FILLS = {
    "50": [1024] * 8, "62": [1280] * 8, "75": [1536] * 8,
    "100": [2048] * 8, "5-of-8-at-62": [1280] * 5 + [1] * 3}


def bench_dense_prompt(configs=None, B=8, S=2048, ps=32, iters=6,
                       blocks=(1024, 2048), fills=None, use_pallas=True):
    """The dense stack's prompt program at ``[8, 2048]`` (models/
    decoder.py ``prefill_forward``, four layers of each configuration's
    widths, bf16, the Pallas flash kernel), ms a LAYER by what the group
    holds: the whole bucket (the loop off: the pass as it was, every
    row of the bucket and every query block) beside the packed pass in
    blocks of 1,024 and of 2,048 rows; the same for the position-wise
    work of a layer alone (norms, projections and rotary, ``o_proj``,
    the feed-forward: no attention, no pages, no packing), and the flash
    launch with and without ``skip_padding``.  One line a configuration
    and form, a column a fill."""
    import dataclasses

    from vgate_tpu.models import decoder, hybrid
    from vgate_tpu.models.specs import spec_for_model_id
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    OFF = 1 << 20
    for name in configs or DENSE_PROMPT_CONFIGS:
        preset, layers = DENSE_PROMPT_CONFIGS[name]
        spec = dataclasses.replace(
            spec_for_model_id(preset), name=name, num_layers=layers)
        params = decoder.init_params(
            spec, jax.random.PRNGKey(0), jnp.bfloat16)
        pool = jnp.zeros((layers, spec.num_kv_heads, B * S // ps + 1, ps,
                          spec.head_dim), jnp.bfloat16)
        tables = jnp.asarray(
            1 + np.arange(B * S // ps).reshape(B, S // ps), jnp.int32)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (B, S), 3, 1000, jnp.int32)
        lens = {fill: jnp.asarray(n, jnp.int32)
                for fill, n in (fills or DENSE_PROMPT_FILLS).items()}
        lp = jax.tree.map(lambda w: w[0], params["layers"])

        def forms(block):
            # traced anew for each block (jit keys on the function)
            def program(params, toks, lens, kp, vp):
                return decoder.prefill_forward(
                    params, spec, toks, lens, kp, vp, tables,
                    use_pallas=use_pallas)

            def position_wise(lp, x, lens):
                # ``layers`` layers' position-wise work alone, on the
                # rows a packed pass of these lengths would work on
                at = jnp.broadcast_to(
                    jnp.arange(x.shape[1])[None], x.shape[:2])
                n_rows = None if block == OFF else hybrid.prompt_rows(
                    spec, S, lens)[1]

                def layer(h, _):
                    q, _, _ = hybrid._by_row_blocks(
                        lambda r, at: decoder._prefill_qkv(r, lp, spec, at),
                        (h, at), n_rows)
                    return hybrid._by_row_blocks(
                        lambda r, a: decoder._finish_layer(r, a, lp, spec),
                        (h, hybrid._heads_flat(q)), n_rows), None

                return jax.lax.scan(layer, x, None, length=layers)[0]

            return jax.jit(program), jax.jit(position_wise)

        rows = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (1, B * S, spec.hidden_size),
            jnp.bfloat16)
        for block in (OFF, *blocks):
            was, hybrid.PROMPT_ROW_BLOCK = hybrid.PROMPT_ROW_BLOCK, block
            try:
                run, alone = forms(block)
                form = "whole_bucket" if block == OFF else f"packed/{block}"
                shaped = rows.reshape(B, S, -1) if block == OFF else rows
                line = {"probe": "dense_prompt", "config": name,
                        "form": form, "group": f"[{B}, {S}]",
                        "layers": layers}
                for fill, n in lens.items():
                    line[f"layer_ms@{fill}"] = round(
                        _timed(run, params, toks, n, pool, pool,
                               iters=iters) / layers * 1e3, 3)
                    line[f"position_wise_ms@{fill}"] = round(
                        _timed(alone, lp, shaped, n, iters=iters)
                        / layers * 1e3, 3)
                yield line
            finally:
                hybrid.PROMPT_ROW_BLOCK = was
        if not use_pallas:
            continue
        H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
        q, k, v = (jax.random.normal(
            jax.random.PRNGKey(3 + i), (B, S, h, hd), jnp.bfloat16)
            for i, h in enumerate((H, KV, KV)))
        line = {"probe": "dense_prompt", "config": name,
                "form": "flash_launch", "group": f"[{B}, {S}]"}
        for skip in (False, True):
            fn = jax.jit(functools.partial(
                flash_prefill_attention_pallas, skip_padding=skip))
            for fill, n in lens.items():
                line[f"{'skip' if skip else 'all'}_ms@{fill}"] = round(
                    _timed(fn, q, k, v, n, iters=iters) * 1e3, 3)
        yield line


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


# ---- learned sparse attention (GLM-5.2's cut): the cell's shapes ----
DSA = dict(B=48, ctx=16384, topk=2048, ps=32, W=640, v_width=512, H=64,
           layers=5, lens=(8193, 16047))


def _timed(fn, *args, iters=8):
    """Median seconds of one call of a jitted ``fn`` (first call apart)."""
    _sync(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_dsa_select(loop=4):
    """The top-2,048 of a decode step's [48, 16,384] scores and of a
    512-row block of a prompt's, by each method: ``jax.lax.top_k``
    (positions), the threshold found by counting (a mask)."""
    from vgate_tpu.ops.dsa import select_mask

    rng = np.random.default_rng(0)
    k = DSA["topk"]
    for rows in (DSA["B"], 512):
        scores = jnp.asarray(
            rng.standard_normal((rows, DSA["ctx"])), jnp.float32)
        lens = jnp.asarray(rng.integers(*DSA["lens"], size=rows), jnp.int32)
        live = jnp.arange(DSA["ctx"])[None, :] < lens[:, None]
        scores = jnp.where(live, scores, -jnp.inf)

        def chained(fn):
            def run(s):
                def body(c, _):
                    out = fn(s + 0 * c)
                    return jnp.sum(out.astype(jnp.float32)) * 0, None
                return jax.lax.scan(body, jnp.float32(0), None,
                                    length=loop)[0]
            return jax.jit(run)

        forms = {
            "lax.top_k": lambda s: jax.lax.top_k(s, k)[1],
            "threshold_mask": lambda s: select_mask(s, k),
        }
        want = np.sort(np.asarray(jax.lax.top_k(scores, k)[1]), axis=-1)
        mask = np.asarray(select_mask(scores, k))
        same = all(
            np.array_equal(np.nonzero(mask[i])[0], want[i])
            for i in range(rows))
        for name, fn in forms.items():
            t = _timed(chained(fn), scores) / loop
            yield {"probe": "dsa_select", "rows": rows, "keys": DSA["ctx"],
                   "k": k, "form": name, "us": round(t * 1e6, 1),
                   "mask_is_top_k_set": bool(same)}


def bench_dsa_index():
    """The scoring pass at the cell's shapes, us a launch and the share
    of its roofline (a key row's 256 B against 819 GB/s, or 8,192
    operations against 197 TFLOP/s, the larger): a decode step's 48
    slots over their live pages, and a prompt's block of 1,024 query
    rows against 16,384 keys (its last block: every tile live)."""
    from vgate_tpu.ops.pallas.dsa import (
        dsa_index_scores_pallas, dsa_prompt_scores_pallas,
    )

    rng = np.random.default_rng(0)
    B, ps, Hi, d = DSA["B"], DSA["ps"], 32, 128
    n = DSA["ctx"] // ps
    P = 1 + B * n
    keys = jax.jit(lambda k: jax.random.normal(
        k, (2, 1, P, ps, d), jnp.bfloat16))(jax.random.PRNGKey(0))
    tables = jnp.asarray(
        (rng.permutation(P - 1)[:B * n] + 1).reshape(B, n), jnp.int32)
    lens = jnp.asarray(rng.integers(*DSA["lens"], size=B), jnp.int32)
    qi = jax.random.normal(jax.random.PRNGKey(1), (B, Hi, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(2), (B, Hi), jnp.float32)
    loop = 6

    def decode(qi, keys):
        def body(c, i):
            out = dsa_index_scores_pallas(
                qi + 0 * c.astype(qi.dtype), w, keys, tables, lens, i % 2)
            return jnp.max(jnp.where(jnp.isfinite(out), out, 0)) * 0, None
        return jax.lax.scan(body, jnp.float32(0),
                            jnp.arange(loop, dtype=jnp.int32))[0]

    t = _timed(jax.jit(decode), qi, keys) / loop
    rows = int(jnp.sum(lens))
    least = max(rows * 256 / 819e9, rows * 8192 / 197e12)
    yield {"probe": "dsa_index", "form": "decode, 48 slots' live pages",
           "us": round(t * 1e6, 1), "rows_scored": rows,
           "roofline_pct": round(100 * least / t, 1)}
    R, T = 1024, DSA["ctx"]
    q_rows = jax.random.normal(jax.random.PRNGKey(3), (R, Hi, d), jnp.bfloat16)
    w_rows = jax.random.normal(jax.random.PRNGKey(4), (R, Hi), jnp.float32)
    k_all = jax.random.normal(jax.random.PRNGKey(5), (T, d), jnp.bfloat16)

    def prompt(q_rows, k_all):
        def body(c, _):
            out = dsa_prompt_scores_pallas(
                q_rows + 0 * c.astype(q_rows.dtype), w_rows, k_all, T - R)
            return out[0, 0] * 0, None
        return jax.lax.scan(body, jnp.float32(0), None, length=loop)[0]

    t = _timed(jax.jit(prompt), q_rows, k_all) / loop
    pairs = R * (T - R) + R * (R + 1) // 2
    yield {"probe": "dsa_index", "form": "prompt, 1,024 rows x 16,384 keys",
           "us": round(t * 1e6, 1), "pairs": pairs,
           "tflops": round(pairs * 8192 / t / 1e12, 1),
           "roofline_pct": round(100 * pairs * 8192 / 197e12 / t, 1)}


def dsa_attend_case(seed=0, ctx=None, layers=None):
    """A decode step's latent pool at the cell's size (48 slots x 16,384
    tokens of pages, 5 layers; or ``ctx`` tokens a slot over ``layers``),
    scattered pages, lengths over the cell's range (the upper half of
    another ``ctx``), 2,048 selected positions a slot."""
    rng = np.random.default_rng(seed)
    B, ps, W = DSA["B"], DSA["ps"], DSA["W"]
    L = layers or DSA["layers"]
    lo, hi = DSA["lens"] if ctx is None else (ctx // 2 + 1, ctx - 300)
    n = (ctx or DSA["ctx"]) // ps
    P = 1 + B * n
    pool = jax.jit(lambda k: jax.random.normal(
        k, (L, 1, P, ps, W), jnp.bfloat16))(jax.random.PRNGKey(seed))
    tables = jnp.asarray(
        (rng.permutation(P - 1)[:B * n] + 1).reshape(B, n), jnp.int32)
    lens = rng.integers(lo, hi, size=B)
    sel = np.stack([rng.permutation(int(l))[:DSA["topk"]] for l in lens])
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (B, DSA["H"], W), jnp.bfloat16)
    return (q, pool, tables, jnp.asarray(lens, jnp.int32),
            jnp.asarray(sel, jnp.int32))


# the fused kernel's forms the probe compares: (what a trip does with a
# fetched pair: "" the kernel's own choice, "select" the form for pools
# that are not bf16, "hollow" the fetch alone; picks a trip)
DSA_FUSED_FORMS = (
    ("", 512), ("", 256), ("", 1024), ("select", 512), ("hollow", 512),
)


def bench_dsa_attend(loop=5, ctx=None, layers=None):
    """One layer's decode attention under the selection, us a layer:
    ``fused`` (the kernel that fetches its picked rows itself, a pair of
    token rows a descriptor, from the pool by pairs) in each of its
    forms, and ``order_picks`` (once a pick: the rows' places through
    the page table, in the kernel's order); what they replaced: the
    selected rows gathered by XLA through the page table into [48,
    2,048, 640] (``gather_alone``) and the dense latent kernel over them
    (``gather+dense_kernel``); and what is NOT served: the dense latent
    kernel over the whole context (every row read, no selection).  Each
    fused form's output against the gather's, on the chip."""
    from vgate_tpu.ops.dsa import order_picks
    from vgate_tpu.ops.pallas.dsa import dsa_decode_attention_pallas
    from vgate_tpu.ops.pallas.paged_attention import (
        mla_decode_attention_pallas,
    )

    q, pool, tables, lens, sel = dsa_attend_case(ctx=ctx, layers=layers)
    L, _, P, ps, W = pool.shape
    B, k = DSA["B"], DSA["topk"]
    kw = dict(v_width=DSA["v_width"], scale=576 ** -0.5)
    n_sel = jnp.minimum(lens, k)
    rows = order_picks(tables, sel, n_sel, ps)

    def gather(pool, l, zero):
        # ``zero``: 0 that hangs on the loop's carry, so that nothing
        # here is the same from one trip to the next and lifted out
        page = jnp.take_along_axis(tables, sel // ps + zero, axis=1)
        return pool.reshape(L * P * ps, W)[(l * P + page) * ps + sel % ps]

    def gather_dense(q, pool, l, zero):
        own = gather(pool, l, zero).reshape(1, 1, B * k // ps, ps, W)
        own_tables = jnp.arange(B * k // ps, dtype=jnp.int32).reshape(B, -1)
        return mla_decode_attention_pallas(
            q, own, own_tables, n_sel, 0, **kw)

    forms = {
        "gather_alone": lambda q, pool, l, zero: gather(
            pool, l, zero)[:, :DSA["H"], :DSA["v_width"]],
        "gather+dense_kernel": gather_dense,
        "whole_context_dense_kernel": lambda q, pool, l, zero:
            mla_decode_attention_pallas(q, pool, tables, lens, l, **kw),
        "order_picks": lambda q, pool, l, zero: jnp.broadcast_to(
            order_picks(tables, sel + zero, n_sel, ps)[:, None, :512],
            (B, DSA["H"], 512)),
    }
    for form, chunk in DSA_FUSED_FORMS:
        forms[f"fused/{form or 'kernel'}/{chunk}"] = functools.partial(
            lambda q, pool, l, zero, **how: dsa_decode_attention_pallas(
                q, pool, rows + zero, n_sel, l, **kw, **how),
            form=form, chunk=chunk)
    want = np.asarray(
        jax.jit(gather_dense)(q, pool, L - 1, 0), np.float32)
    # the fused forms last, over the SAME rows by pairs: the one layout
    # is let go before the other is made
    names = sorted(forms, key=lambda name: name.startswith("fused"))
    where = {"ctx": ctx or DSA["ctx"], "layers": L}
    for name in names:
        fn, fused = forms[name], name.startswith("fused")
        if fused and pool.ndim == 5:
            pool = jax.jit(lambda p: p.reshape(L, 1, P, ps // 2, 2, W),
                           donate_argnums=0)(pool)

        def run(q, pool, fn=fn):
            def body(c, i):
                zero = (0 * c[0, 0, 0]).astype(jnp.int32)
                out = fn(q + 0 * c.astype(q.dtype), pool, i % L + zero, zero)
                return jnp.pad(out.astype(jnp.float32), (
                    (0, 0), (0, 0), (0, q.shape[-1] - out.shape[-1]))), None
            return jax.lax.scan(
                body, jnp.zeros(q.shape, jnp.float32),
                jnp.arange(loop, dtype=jnp.int32))[0]
        t = _timed(jax.jit(run), q, pool) / loop
        read = (int(jnp.sum(lens)) if name.startswith("whole")
                else int(jnp.sum(n_sel)))
        line = {"probe": "dsa_attend", **where, "form": name,
                "us_a_layer": round(t * 1e6, 1), "rows_read": read,
                "gb_s": round(read * W * 2 / t / 1e9, 1)}
        if fused and "hollow" not in name:
            got = np.asarray(jax.jit(fn)(q, pool, L - 1, 0), np.float32)
            line["max_abs_diff_to_gather"] = float(np.abs(got - want).max())
        yield line


def bench_dsa_attend_64k():
    """The same at a 64 k context (one layer of pages: five do not fit
    beside each other's two layouts)."""
    yield from bench_dsa_attend(ctx=65536, layers=1)


def bench_eva_decode(forced=(), B=20, H=32, hd=128, ps=32, window=2048,
                     chunk=16, layers=2, loop=LOOP):
    """The EVA decode attention at the EvaByte cell's shape: 20 slots, 32
    heads of 128 (MHA: one query row a KV head), contexts of 8,193-16,047
    bytes (``long-agent``'s), so about 1,024 live window rows and 768
    summary rows a slot: the paged decode kernel over the step's ONE
    sequence of rows (ops/eva.py ``decode_view``), writing the step's
    row, then the summary rows of the windows the step closes
    (``decode_close``).
    Prints the (pages a chunk, slots a program, items a trip) a launch
    ran with and its loop trips, microseconds a launch and GB/s over the
    rows a step HAS to read and write (live rows, real lengths), for the
    kernel alone, for its hollow twin (the trips' bookkeeping), and for
    the kernel with the closers' loop behind it: with no slot closing
    (every step but one in 2,048 a slot: should read as the kernel
    alone) and with two, whose difference is ``close_us_a_slot``, what
    one window's 128 summary rows cost a layer.  Beside them
    ``rewrite_a_step_us``, the form PR 52 took off the path: the open
    chunk's row of every slot rewritten after every launch.  ``forced``
    is (pages a chunk, items a trip) pairs to run beside the rule's own
    (0: the rule's), the probe's alone; each forced form's attention is
    held against the rule's."""
    from vgate_tpu.ops import eva
    from vgate_tpu.ops.pallas.paged_attention import (
        _decode_sizes, paged_decode_attention_pallas,
    )

    rng = np.random.default_rng(0)
    ctx = 16384
    pages = B * (ctx // chunk // ps) + 1
    R = window // ps
    pool = jnp.asarray(rng.standard_normal(
        (layers, H, pages + B * R, ps, hd)), jnp.bfloat16)
    positions = jnp.asarray(rng.integers(8193, 16047, size=B), jnp.int32)
    n = ctx // chunk // ps
    tables = jnp.asarray(
        1 + np.arange(B * n).reshape(B, n), jnp.int32)
    win = eva.window_pages(pages, B, R)[:B]
    view, rows = eva.decode_view(tables, win, positions, window, chunk, ps)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    phi = jnp.asarray(rng.standard_normal((H, hd)), jnp.bfloat16)
    live = int(np.sum(np.asarray(rows) + 1))
    moved = (live + B) * 2 * H * hd * 2  # rows read + the row written

    def attend(q, kp, vp, **kw):
        return paged_decode_attention_pallas(
            q, kp, vp, view, rows + 1, layer=0, k_new=new, v_new=new, **kw)

    def attend_and_close(q, kp, vp, closing=0, **kw):
        """The launch, then the closers' loop with the first ``closing``
        slots at their window's last row (the loop's alone: the launch
        keeps its rows)."""
        out, kp, vp = attend(q, kp, vp, **kw)
        at = jnp.where(jnp.arange(B) < closing,
                       positions | (window - 1), positions & ~1)
        closers = eva.decode_closers(tables, win, at, None, window, chunk,
                                     ps)
        kp, vp = eva.decode_close(kp, vp, phi, phi, 0, closers, chunk,
                                  hd ** -0.5)
        return out, kp, vp

    def attend_and_rewrite(q, kp, vp, **kw):
        """What a step did before PR 52 (``decode_summarize``, kept here
        alone): XLA's row gather of the open chunk's <= 16 rows of every
        slot, their pooling, a row scattered a slot."""
        from vgate_tpu.ops.kv_quant import kv_write_tokens

        out, kp, vp = attend(q, kp, vp, **kw)
        first = positions // chunk * chunk
        at = (first % window)[:, None] + jnp.arange(chunk)[None, :]
        of = jnp.take_along_axis(win, at // ps, axis=1)
        kv = jnp.arange(H, dtype=jnp.int32)
        take = lambda pool: pool[0, kv[None, None, :], of[..., None],
                                 (at % ps)[..., None]]  # [B, c, KV, hd]
        valid = first[:, None] + jnp.arange(chunk)[None, :] <= positions[
            :, None]
        ks, vs = eva.summarize(take(kp), take(vp), phi, phi, valid, chunk,
                               hd ** -0.5)
        row = positions // chunk
        ids = tables[jnp.arange(B), row // ps]
        kp = kv_write_tokens(kp, ids, row % ps, ks[:, 0], layer=0)
        vp = kv_write_tokens(vp, ids, row % ps, vs[:, 0], layer=0)
        return out, kp, vp

    def us_a_launch(fn):
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def run(q, kp, vp):
            def body(carry, _):
                kp, vp, acc = carry
                out, kp, vp = fn(q + 0 * acc.astype(q.dtype), kp, vp)
                return (kp, vp, out.astype(jnp.float32)), None

            (kp, vp, acc), _ = jax.lax.scan(
                body, (kp, vp, jnp.zeros(q.shape, jnp.float32)), None,
                length=loop)
            return acc, kp, vp

        kp, vp = pool + 0, pool + 0  # fresh: a run donates its pools
        times = []
        for i in range(6):
            t0 = time.perf_counter()
            acc, kp, vp = run(q, kp, vp)
            _sync(acc)
            if i:
                times.append((time.perf_counter() - t0) / loop)
        return float(np.median(times)) * 1e6

    ruled = None
    for chunk_pages, items in ((0, 0), *forced):
        kw = {"chunk_pages": chunk_pages, "items": items}
        CP, BS, I, _ = _decode_sizes(
            B, H, 1, hd, ps, view.shape[1], pool.dtype, q.dtype, **kw)
        out = np.asarray(attend(q, pool, pool, **kw)[0], np.float32)
        ruled = out if ruled is None else ruled
        line = {"probe": "eva_decode", "slots": B, "heads": H,
                "forced": bool(chunk_pages or items),
                "chunk_pages": CP, "block_slots": BS, "items": I,
                "chunk_tokens": CP * ps,
                "trips": decode_trips(np.asarray(rows) + 1, CP * ps, BS, I)[1],
                "live_rows_mean": live / B,
                "window_rows_mean": float(np.mean(
                    np.asarray(positions) % window + 1)),
                "max_abs_diff_from_rule": float(np.abs(out - ruled).max())}
        for name, fn in (
            ("kernel", functools.partial(attend, **kw)),
            ("hollow", functools.partial(attend, hollow=True, **kw)),
            ("no_closer", functools.partial(attend_and_close, **kw)),
            ("two_closers",
             functools.partial(attend_and_close, closing=2, **kw)),
            ("rewrite_a_step", functools.partial(attend_and_rewrite, **kw)),
        ):
            us = us_a_launch(fn)
            line[f"{name}_us"] = round(us, 1)
            if name in ("kernel", "no_closer"):
                line[f"{name}_gb_per_s"] = round(moved / us / 1e3, 1)
                line[f"{name}_hbm_roofline_pct"] = round(
                    100 * moved / HBM_BYTES_PER_S / (us * 1e-6), 1)
        line["close_us_a_slot"] = round(
            (line["two_closers_us"] - line["no_closer_us"]) / 2, 1)
        line["rewrite_us_a_step"] = round(
            line["rewrite_a_step_us"] - line["kernel_us"], 1)
        yield line


# the Mamba-2 step's served shapes: (slots, heads, groups, layers held)
SSD_STEP_SHAPES = {
    "granite-4.0-h-micro": (80, 64, 1, 12),
    "nemotron-3-super-120b-a12b-l11e128": (192, 128, 8, 5),
}


def bench_ssd_step(blocks=(), P=64, N=128, loop=8):
    """The Mamba-2 step (``ops/ssd.py ssd_step`` as a decode step calls
    it: the scalars folded in by XLA, then the kernel) at the two served
    shapes, by the kernel's heads a program: the rule's own
    (``ops/pallas/ssd.py block_heads``) and each of ``blocks`` that the
    shape takes (default 16, 32, 64).  A program of ``loop`` steps walks
    the held layers in turn, as a decode chunk does; microseconds a
    launch, GB/s over the float32 tiles it has to read and write, and
    that as a share of the chip's 819 GB/s; each form's result against
    the rule's."""
    from vgate_tpu.ops import ssd
    from vgate_tpu.ops.pallas.ssd import block_heads

    peak = 819e9
    for name, (B, H, G, L) in SSD_STEP_SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], (B, H, P), jnp.bfloat16)
        dt = jnp.exp(jax.random.uniform(
            ks[1], (B, H), minval=jnp.log(1e-3), maxval=jnp.log(0.1)))
        A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
        Bm = jax.random.normal(ks[3], (B, G, N), jnp.bfloat16)
        Cm = jax.random.normal(ks[4], (B, G, N), jnp.bfloat16)
        rule = block_heads(H, G)
        per_group = H // G
        forms = [rule] + [b for b in (blocks or (16, 32, 64))
                          if b != rule and H % b == 0
                          and (b % per_group == 0 or per_group % b == 0)]
        first = None
        for hb in forms:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def run(state, x, hb=hb):
                def step(carry, i):
                    state, acc = carry
                    y, state = ssd.ssd_step(
                        x + 0 * acc.astype(x.dtype), dt, A, Bm, Cm, state,
                        i % L, use_pallas=True, block=hb)
                    return (state, y), None

                (state, y), _ = jax.lax.scan(
                    step, (state, jnp.zeros((B, H, P), jnp.float32)),
                    jnp.arange(loop * L, dtype=jnp.int32))
                return state, y

            state = jax.random.normal(ks[5], (L, B, H, P, N)) * 0.1
            state, y = _sync(run(state, x))
            if first is None:
                first = np.asarray(y)
            err = float(np.abs(np.asarray(y) - first).max())
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                state, y = _sync(run(state, x))
                times.append(time.perf_counter() - t0)
            t = float(np.median(times)) / (loop * L)
            moved = 2 * B * H * P * N * 4
            yield {
                "probe": "ssd_step", "shape": name, "slots": B, "heads": H,
                "groups": G, "block_heads": hb, "rule": hb == rule,
                "grid": [B, H // hb], "us_per_launch": round(t * 1e6, 1),
                "gb_per_s": round(moved / t / 1e9, 1),
                "roofline_pct": round(100 * moved / t / peak, 1),
                "max_abs_vs_rule": err,
            }
            del state


def main() -> None:
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            "bench_kernels needs a real TPU (Pallas kernels don't run on "
            f"{device.platform}); CPU CI covers parity in interpret mode"
        )
    if sys.argv[1:] == ["sample_edits"]:
        for line in bench_sample_edits():
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:] == ["greedy_head"]:
        for line in bench_greedy_head():
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:] == ["flash_prefill_hollow"]:
        for line in bench_flash_prefill_hollow():
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["flash_prefill_cells"]:
        # flash_prefill_cells [CELL ...] [hb=N ...]: forced heads a program
        args = sys.argv[2:]
        forced = tuple(int(a[3:]) for a in args if a.startswith("hb="))
        cells = [a for a in args if not a.startswith("hb=")]
        for line in bench_flash_prefill_cells(cells, forced=forced):
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["swa_prefill"]:
        # swa_prefill [CONFIG ...]: K-EXAONE's shape alone by default
        for name in sys.argv[2:] or ["k-exaone-236b-a23b-l5e16"]:
            for line in bench_swa_prefill(**SWA_PREFILL_CASES[name]):
                print(json.dumps(line), flush=True)
        return
    if sys.argv[1:] == ["mellum_decode"]:
        for line in bench_mellum_decode():
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:] == ["mellum_grouped"]:
        for line in bench_mellum_grouped():
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["eva_decode"]:
        # eva_decode [PAGESxITEMS ...]: forced sizes beside the rule's
        forced = [tuple(map(int, a.split("x"))) for a in sys.argv[2:]]
        for line in bench_eva_decode(forced):
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["ssd_step"]:
        for line in bench_ssd_step(tuple(map(int, sys.argv[2:]))):
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["dense_prompt"]:
        for line in bench_dense_prompt(sys.argv[2:]):
            print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["expert_layer"]:
        for line in bench_expert_layer(sys.argv[2:]):
            print(json.dumps(line), flush=True)
        return
    dsa = {"dsa_index": bench_dsa_index, "dsa_select": bench_dsa_select,
           "dsa_attend": bench_dsa_attend,
           "dsa_attend_64k": bench_dsa_attend_64k}
    if sys.argv[1:] and all(a in dsa for a in sys.argv[1:]):
        for a in sys.argv[1:]:
            for line in dsa[a]():
                print(json.dumps(line), flush=True)
        return
    if sys.argv[1:2] == ["decode_cells"]:
        # decode_cells [CELL ...] [buffers=N ...]: forced chunk buffers
        args = sys.argv[2:]
        forced = tuple(int(a[8:]) for a in args if a.startswith("buffers="))
        cells = [a for a in args if not a.startswith("buffers=")]
        for line in bench_decode_cells(cells, forced=forced):
            print(json.dumps(line), flush=True)
        return
    print(json.dumps(bench_paged_decode()))
    print(json.dumps(bench_paged_decode(ctx=2048)))
    for line in bench_decode_cells():
        print(json.dumps(line))
    for line in bench_sample_edits():
        print(json.dumps(line))
    print(json.dumps(bench_flash_prefill()))
    print(json.dumps(bench_flash_prefill(S=2048)))
    print(json.dumps(bench_decode_window()))
    print(json.dumps(bench_multitok_verify()))


if __name__ == "__main__":
    main()
