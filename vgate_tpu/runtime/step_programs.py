"""The device programs the engine launches, each a pure function of
arrays and a ``ModelSpec``: the two prompt passes with their fused
first-token sampling, the fused decode chunk and the row edit that lets
a prompt pass's rows join its carried state, the speculative verify
round, and the page copies of copy-on-write and the host swap tier.

``runtime/engine_core.py`` calls them and nothing here knows the engine:
the arrows point ``models/*``, ``ops/*`` -> this module -> the engine.
The function names are the device trace's module names
(``jit__decode_chunk``, ``jit__prefill_step``,
``jit__suffix_prefill_step``) that the benchmark's reducers match by
pattern.

The cache a program threads is ``(k_pages, v_pages[, state])``: K and V
pools; or a latent pool and ``None``; or, for a spec that attends under
a learned selection, a latent pool and the index keys' array under the
same page table (``models/hybrid.py``).  What such a spec's programs
read and score is booked by the engine into ``/debug/perf ->
totals.dsa``, and its decode launches carry ``sel_rows`` on the
``vgt.engine.decode_dispatch`` span (docs/observability.md).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from vgate_tpu import integrity
from vgate_tpu.models.decoder import (
    decode_forward,
    decode_head_impl,
    greedy_head,
    prefill_forward,
    prefill_suffix_forward,
    spec_verify_forward,
)
from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.ops.kv_quant import copy_page_prefix
from vgate_tpu.ops.sampling import (
    apply_logit_bias,
    apply_penalties,
    live_stop_ids,
    sample_tokens,
    sample_tokens_with_logprobs,
    suppress_stop_tokens,
    verify_and_sample,
)


def _state_kw(state, slots=None) -> Dict[str, Any]:
    """The forwards' extra arguments for a spec with recurrent layers;
    nothing for the others, whose programs stay what they were."""
    if state is None:
        return {}
    return {"state": state} if slots is None else {
        "state": state, "slots": slots}


@jax.named_scope("sample")
def _sample_first(
    logits, temps, top_ps, top_ks, key, seeds, steps, num_logprobs,
    counts, freq_pens, pres_pens, min_toks, stop_id_mat, bias_ids,
    bias_vals,
):
    """A prompt pass's fused first-token sampling (traced inside
    _prefill_step / _suffix_prefill_step): logit post-processing, then
    the sample.  Returns ``(next_tokens, logprob triple or None)``."""
    if counts is not None:
        # post-preemption re-prefill: folded outputs still count toward
        # the penalties of the re-sampled first token
        logits = apply_penalties(logits, counts, freq_pens, pres_pens)
    if bias_ids is not None:
        logits = apply_logit_bias(logits, bias_ids, bias_vals)
    if min_toks is not None:
        logits = suppress_stop_tokens(logits, steps, min_toks, stop_id_mat)
    if num_logprobs > 0:
        next_tokens, lp, tids, tlps = sample_tokens_with_logprobs(
            logits, temps, top_ps, top_ks, key, seeds=seeds, steps=steps,
            num_top=num_logprobs,
        )
        return next_tokens, (lp, tids, tlps)
    # NOTE: no all_greedy fast path in prefill programs — one sample per
    # PROMPT makes the top-k cost negligible, and skipping the variant
    # split halves the (expensive) batched-prefill compile ladder
    next_tokens = sample_tokens(
        logits, temps, top_ps, top_ks, key, seeds=seeds, steps=steps
    )
    return next_tokens, None


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "mesh", "use_pallas", "num_logprobs"
    ),
    donate_argnames=("k_pages", "v_pages", "state"),
)
def _prefill_step(
    params, spec: ModelSpec, tokens, seq_lens, k_pages, v_pages,
    page_tables, temps, top_ps, top_ks, key, mesh=None, use_pallas=False,
    seeds=None, steps=None, num_logprobs: int = 0,
    counts=None, freq_pens=None, pres_pens=None,
    min_toks=None, stop_id_mat=None, bias_ids=None, bias_vals=None,
    state=None, slots=None,
):
    """The cache is threaded and donated as ONE value: the K/V pools
    and, for a spec with recurrent layers, the per-slot ``state``
    (models/hybrid.py), whose rows ``slots`` this pass overwrites.  It
    comes back as the result's tail: ``(k_pages, v_pages)`` or
    ``(k_pages, v_pages, state)``."""
    logits, *cache = prefill_forward(
        params, spec, tokens, seq_lens, k_pages, v_pages, page_tables,
        mesh=mesh, use_pallas=use_pallas,
        **_state_kw(state, slots),
    )
    out = _sample_first(
        logits, temps, top_ps, top_ks, key, seeds, steps, num_logprobs,
        counts, freq_pens, pres_pens, min_toks, stop_id_mat, bias_ids,
        bias_vals,
    )
    return (out, *cache)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "num_logprobs", "use_pallas", "mesh",
                     "unaligned"),
    donate_argnames=("k_pages", "v_pages", "state"),
)
def _suffix_prefill_step(
    params, spec: ModelSpec, tokens, prefix_lens, suffix_lens, k_pages,
    v_pages, suffix_page_tables, ctx_page_tables, temps, top_ps, top_ks,
    key, seeds=None, steps=None, num_logprobs: int = 0,
    counts=None, freq_pens=None, pres_pens=None,
    min_toks=None, stop_id_mat=None, bias_ids=None, bias_vals=None,
    use_pallas: bool = False, mesh=None, unaligned: bool = False,
    state=None, slots=None,
):
    """Prompt pass for the uncached suffix of a prefix-cache hit, with
    fused first-token sampling (models/decoder.py prefill_suffix_forward).
    ``unaligned`` is the copy-on-write variant: prefix_lens may fall
    mid-page and the KV write becomes a per-token scatter."""
    logits, *cache = prefill_suffix_forward(
        params, spec, tokens, prefix_lens, suffix_lens, k_pages, v_pages,
        suffix_page_tables, ctx_page_tables, use_pallas=use_pallas,
        mesh=mesh, unaligned=unaligned, **_state_kw(state, slots),
    )
    out = _sample_first(
        logits, temps, top_ps, top_ks, key, seeds, steps, num_logprobs,
        counts, freq_pens, pres_pens, min_toks, stop_id_mat, bias_ids,
        bias_vals,
    )
    return (out, *cache)


@functools.partial(jax.jit, donate_argnames=("k_pages", "v_pages"))
def _cow_copy_pages(k_pages, v_pages, src, dst, upto):
    """Copy-on-write page copy (runtime/radix_cache.py): duplicate the
    first ``upto`` token slots of page ``src`` into page ``dst`` across
    every layer and head, so a sequence diverging mid-page gets the
    shared head's KV without recomputing it.  Scalars are traced — one
    compile serves every (src, dst, upto) combination.  int8 pools copy
    the per-slot SCALES with the data (ops/kv_quant.copy_page_prefix):
    a COW'd head dequantizes bit-identically to the page it came from,
    so shared and diverged readers never disagree."""
    ps = k_pages.shape[-2]
    keep = jnp.arange(ps) < upto  # [ps]
    return (
        copy_page_prefix(k_pages, src, dst, keep),
        copy_page_prefix(v_pages, src, dst, keep),
    )


@jax.jit
def _gather_swap_pages(k_pages, v_pages, idx):
    """Device->host half of a KV swap: pull ``idx``'s page slices out
    of the pools (page axis 2 on data AND int8 scale leaves) in one
    program; the caller device_gets the result.  NOT donated — the
    pools stay resident."""
    return jax.tree.map(
        lambda x: jnp.take(x, idx, axis=2), (k_pages, v_pages)
    )


@functools.partial(jax.jit, donate_argnames=("k_pages", "v_pages"))
def _scatter_swap_pages(k_pages, v_pages, idx, k_data, v_data):
    """Host->device half: scatter saved page content back into freshly
    allocated pages.  Duplicate padding indices all target trash page
    0, whose content is never read."""
    put = lambda x, d: x.at[:, :, idx].set(d)
    return (
        jax.tree.map(put, k_pages, k_data),
        jax.tree.map(put, v_pages, v_data),
    )


@functools.partial(
    jax.jit,
    static_argnames=("spec", "num_steps", "use_pallas", "max_position",
                     "mesh", "num_logprobs", "all_greedy", "guard",
                     "guard_threshold"),
    donate_argnames=("k_pages", "v_pages", "counts", "state"),
)
def _decode_chunk(
    params, spec: ModelSpec, tokens, positions, k_pages, v_pages,
    page_tables, active, temps, top_ps, top_ks, base_key, counter,
    num_steps: int = 1, use_pallas=False, max_position: int = 0,
    seeds=None, steps=None, mesh=None, num_logprobs: int = 0,
    counts=None, freq_pens=None, pres_pens=None,
    min_toks=None, stop_id_mat=None, all_greedy: bool = False,
    bias_ids=None, bias_vals=None, guard: bool = False,
    guard_threshold: float = 1.0e4, state=None,
):
    """``num_steps`` decode steps fused into one device program.

    The host reads sampled tokens once per *chunk* instead of once per
    step (fewer dispatches and readbacks).  EOS /
    max_tokens are detected on the host after readback; steps a sequence ran
    past its stopping point are discarded there, and their KV writes land in
    pages the scheduler reserved for the horizon (harmless: the sequence is
    removed and its pages freed).  Returns ``chunk_tokens`` of shape
    ``[num_steps, B]`` plus the threaded device state.

    ``guard`` (integrity.logit_guard) additionally computes a per-step
    per-slot sentinel flag word over the RAW model logits — before
    penalties/bias/min-token suppression, whose deliberate -inf writes
    must not trip the NaN/Inf check — returned as ``[num_steps, B]``
    uint8 (integrity.logit_guard flag bits).  Static, so the guard-off
    program is byte-identical to the pre-integrity one.

    ``state`` (a spec with recurrent layers) rides the scan's carry
    beside the pools, rows of active slots updated in place; for a spec
    the stack walker of models/hybrid.py runs the result
    then ends ``..., chunk_flags, state, moe_stats`` with ``moe_stats``
    ``[num_steps, 5]`` int32, the expert layers' device counters summed
    over the layers of each step (ops/moe.py STAT_NAMES), read back
    with the chunk's tokens.

    A chunk whose rows need nothing of a step's logits but the token
    and the guard's flags (models/decoder.py ``decode_head_impl``, read
    from these static arguments) never holds them as an array: the
    head's product, the guard, the two edits and the argmax are one pass
    (``greedy_head``) with the same tokens and flags.
    """

    if steps is None:
        steps = jnp.zeros_like(positions)
    fused_head = decode_head_impl(
        params, spec, use_pallas, mesh, rows=tokens.shape[0],
        all_greedy=all_greedy, num_logprobs=num_logprobs,
        penalised=counts is not None,
        bias_width=0 if bias_ids is None else bias_ids.shape[-1],
        stop_width=0 if min_toks is None else stop_id_mat.shape[-1],
    ) == "fused"

    @jax.named_scope("sample")
    def sample(logits, key, steps, counts):
        if counts is not None:
            # frequency/presence penalties over the generated-token
            # histogram (ops/sampling.py apply_penalties)
            logits = apply_penalties(logits, counts, freq_pens, pres_pens)
        if bias_ids is not None:
            logits = apply_logit_bias(logits, bias_ids, bias_vals)
        if min_toks is not None:
            logits = suppress_stop_tokens(
                logits, steps, min_toks, stop_id_mat
            )
        if num_logprobs > 0:
            return sample_tokens_with_logprobs(
                logits, temps, top_ps, top_ks, key, seeds=seeds,
                steps=steps, num_top=num_logprobs,
            )
        return (sample_tokens(
            logits, temps, top_ps, top_ks, key, seeds=seeds,
            steps=steps, all_greedy=all_greedy,
        ),)

    def body(carry, _):
        (tokens, positions, counter, steps, counts, k_pages, v_pages,
         state) = carry
        key = jax.random.fold_in(base_key, counter)
        head = None
        if fused_head:
            head = functools.partial(
                greedy_head, bias_ids=bias_ids, bias_vals=bias_vals,
                stop_ids=None if min_toks is None else live_stop_ids(
                    spec.vocab_size, steps, min_toks, stop_id_mat),
                guard=guard, guard_threshold=guard_threshold,
            )
        logits, k_pages, v_pages, *more = decode_forward(
            params, spec, tokens, positions, k_pages, v_pages, page_tables,
            active=active, use_pallas=use_pallas, mesh=mesh, head=head,
            **_state_kw(state),
        )
        if more:
            state, moe_stats = more
        if fused_head:
            next_tokens, step_flags = logits
            ys = (next_tokens,)
        else:
            if guard:
                step_flags = integrity.logit_guard(logits, guard_threshold)
            ys = sample(logits, key, steps, counts)
            next_tokens = ys[0]
        if guard:
            ys = ys + (step_flags,)
        if more:
            ys = ys + (moe_stats,)
        positions = positions + active.astype(positions.dtype)
        steps = steps + active.astype(steps.dtype)
        if counts is not None:
            counts = counts.at[
                jnp.arange(counts.shape[0]), next_tokens
            ].add(active.astype(counts.dtype))
        if max_position > 0:
            # overshoot steps (chunk sized by MAX headroom across slots) must
            # stay in-bounds: on the Pallas path seq_len = position+1 drives
            # the page loop, and past max_pages the DMA reads are undefined
            # rather than clamped like XLA gathers
            positions = jnp.minimum(positions, max_position)
        return (
            next_tokens, positions, counter + 1, steps, counts,
            k_pages, v_pages, state,
        ), ys

    carry, ys = jax.lax.scan(
        body,
        (tokens, positions, counter, steps, counts, k_pages, v_pages,
         state),
        None,
        length=num_steps,
    )
    (tokens, positions, counter, steps, counts, k_pages, v_pages,
     state) = carry
    tail = ()
    if spec.is_hybrid:  # its state (None without recurrent layers)
        tail, ys = (state, ys[-1]), ys[:-1]
    # [num_steps, B] uint8 sentinel words when guarded (host ORs the
    # step axis at readback), None otherwise
    chunk_flags = ys[-1] if guard else None
    if guard:
        ys = ys[:-1]
    chunk_tokens = ys[0]
    # ([steps, B], [steps, B, K], [steps, B, K]) when logprobs, else None
    chunk_lp = ys[1:] if num_logprobs > 0 else None
    return (
        chunk_tokens, chunk_lp, tokens, positions, counter, steps, counts,
        k_pages, v_pages, chunk_flags, *tail,
    )


@jax.jit
def _join_decode_rows(
    tokens, positions, steps, slots, first_tokens, join_positions,
    join_steps,
):
    """A prompt program's rows join the decode batch on the device:
    ``first_tokens`` [J] is that program's sampled-token output, still
    unread by the host, and ``slots`` [J] the decode slot of each of its
    rows (its padding rows point past the batch and are dropped).  The
    chunk programs' carried ``tokens`` / ``positions`` / ``steps`` [B]
    get the joiners' first token, the position it is fed at and its
    index among the generated tokens; every other row keeps what the
    last chunk left there.  One compile per J, a prompt program's batch
    size."""
    def put(carried, rows):
        return carried.at[slots].set(rows.astype(carried.dtype), mode="drop")

    return (
        put(tokens, first_tokens),
        put(positions, join_positions),
        put(steps, join_steps),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "use_pallas", "num_logprobs", "all_greedy", "mesh",
    ),
    donate_argnames=("k_pages", "v_pages"),
)
def _spec_verify_step(
    params, spec: ModelSpec, tokens, positions0, input_lens, k_pages,
    v_pages, page_tables, active, temps, top_ps, top_ks, base_key, counter,
    seeds=None, steps=None, use_pallas=False, num_logprobs: int = 0,
    counts=None, freq_pens=None, pres_pens=None,
    min_toks=None, stop_id_mat=None, all_greedy: bool = False,
    bias_ids=None, bias_vals=None, mesh=None,
):
    """One speculative round: score current token + drafts in a single
    forward (models/decoder.py spec_verify_forward), then verify every
    draft position with the per-slot sampling params — greedy slots by
    exact argmax match, temperature>0 slots by distribution-preserving
    rejection sampling (ops/sampling.py verify_and_sample: accept draft
    t with prob p(t), resample from p minus t on rejection) — and count
    accepted drafts on device.  Returns (model_toks [B, S], accepted
    [B], caches)."""
    from vgate_tpu.runtime.speculative import count_accepted

    logits, k_pages, v_pages = spec_verify_forward(
        params, spec, tokens, positions0, input_lens, k_pages, v_pages,
        page_tables, active=active, use_pallas=use_pallas, mesh=mesh,
    )  # [B, S, V]
    B, S = tokens.shape
    if counts is not None:
        # position j's penalties include the drafts accepted before it
        # (run 1..j); if draft j+1 is later rejected, position j+1's
        # output is discarded anyway, so exactness holds for every token
        # actually appended
        run = counts
        pen = []
        for j in range(S):
            pen.append(
                apply_penalties(logits[:, j], run, freq_pens, pres_pens)
            )
            if j + 1 < S:
                inc = ((j + 1) < input_lens) & active
                run = run.at[jnp.arange(B), tokens[:, j + 1]].add(
                    inc.astype(run.dtype)
                )
        logits = jnp.stack(pen, axis=1)
    key = jax.random.fold_in(base_key, counter)
    # one batched sampler over all (slot, position) rows — per-position
    # step indices keep seeded reproducibility aligned with the token
    # index, exactly like the decode chunk's per-step `steps` increment
    rep = functools.partial(jnp.repeat, repeats=S, axis=0)
    steps_flat = (
        None
        if steps is None
        else (steps[:, None] + jnp.arange(S)[None, :]).reshape(-1)
    )
    if bias_ids is not None:
        # per-slot biases apply at every candidate position
        flat = apply_logit_bias(
            logits.reshape(B * S, -1), rep(bias_ids), rep(bias_vals)
        )
        logits = flat.reshape(logits.shape)
    if min_toks is not None:
        assert steps_flat is not None, "min_tokens requires steps"
        flat = suppress_stop_tokens(
            logits.reshape(B * S, -1),
            steps_flat,
            rep(min_toks),
            rep(stop_id_mat),
        )
        logits = flat.reshape(logits.shape)
    # row (b, j) verifies draft tokens[b, j+1]; the row at input_len-1
    # (and any garbage row past it) draws the plain bonus sample instead
    draft_next = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1
    )
    is_bonus = jnp.arange(S)[None, :] >= (input_lens[:, None] - 1)
    flat_toks, _accept, lp_flat = verify_and_sample(
        logits.reshape(B * S, -1),
        draft_next.reshape(-1),
        is_bonus.reshape(-1),
        rep(temps), rep(top_ps), rep(top_ks), key,
        seeds=None if seeds is None else rep(seeds),
        steps=steps_flat,
        num_top=num_logprobs,
        all_greedy=all_greedy,
    )
    model_toks = flat_toks.reshape(B, S)
    if num_logprobs > 0:
        lp, tids, tlps = lp_flat
        lp_data = (
            lp.reshape(B, S),
            tids.reshape(B, S, -1),
            tlps.reshape(B, S, -1),
        )
    else:
        lp_data = None
    accepted = count_accepted(model_toks, tokens, input_lens)
    if counts is not None:
        # fold the tokens this round actually appends (accepted run +
        # bonus) into the histogram on device
        app = (
            (jnp.arange(S)[None, :] <= accepted[:, None])
            & active[:, None]
        )
        b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
        counts = counts.at[b_idx, model_toks].add(app.astype(counts.dtype))
    return model_toks, accepted, lp_data, counts, k_pages, v_pages
