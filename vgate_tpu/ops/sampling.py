"""Device-side token sampling with *per-request* parameters.

The reference applies the first request's temperature/top_p to the whole
batch (vgate/batcher.py:271 — a documented quirk); here every slot carries
its own (temperature, top_p, top_k) vector and sampling happens on device in
one fused program.

Exactness note: sampling operates on the top ``TRUNC`` logits (lax.top_k)
rather than a full-vocab sort.  Top-k is exact for k <= TRUNC; top-p is
exact whenever the top-TRUNC probability mass covers ``top_p`` (true for all
practical temperatures); both fall back to the best-available distribution
otherwise.  This keeps the per-step cost O(V + TRUNC log TRUNC) instead of a
full 150k-vocab sort per slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TRUNC = 256  # logits kept per slot for sampling
_GREEDY_EPS = 1e-4


def _masked_scaled(
    logits: jnp.ndarray,  # [B, V]
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32, 0 => disabled
):
    """Truncate + scale + apply top-k/top-p masks.  Returns
    (raw top-trunc logits [B, trunc] sorted desc, their token ids,
    the temperature-scaled logits with ineligible entries at -1e30)."""
    B, V = logits.shape
    trunc = min(TRUNC, V)
    logits32 = logits.astype(jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits32, trunc)  # [B, trunc] sorted desc

    safe_temp = jnp.maximum(temperature, _GREEDY_EPS)[:, None]
    scaled = top_vals / safe_temp

    # top-k mask within the truncated, sorted slice
    ranks = jnp.arange(trunc)[None, :]
    k = jnp.where(top_k[:, None] > 0, top_k[:, None], trunc)
    k_mask = ranks < k

    # top-p (nucleus) mask: keep the smallest prefix whose mass >= top_p;
    # exclusive cumsum guarantees the argmax token always stays eligible.
    probs = jax.nn.softmax(scaled, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    p_mask = cum_excl < jnp.clip(top_p, 0.0, 1.0)[:, None]

    mask = k_mask & p_mask
    masked = jnp.where(mask, scaled, -1e30)
    return top_vals, top_idx, masked


def _row_keys(
    key: jax.Array,
    seeds: jnp.ndarray,  # [B] int32, -1 => unseeded
    steps: jnp.ndarray | None,  # [B] int32 per-seq sample index
    B: int,
):
    """Per-row PRNG keys: a row with ``seed >= 0`` derives from
    ``fold_in(PRNGKey(seed), step)`` — reproducible regardless of batch
    composition or engine step — else from the engine key + row index."""

    def slot_key(seed, step, slot):
        seeded = jax.random.fold_in(
            jax.random.PRNGKey(seed.astype(jnp.uint32)), step
        )
        unseeded = jax.random.fold_in(key, slot)
        return jnp.where(seed >= 0, seeded, unseeded)

    return jax.vmap(slot_key)(
        seeds,
        jnp.zeros((B,), jnp.int32) if steps is None else steps,
        jnp.arange(B, dtype=jnp.int32),
    )


def _topk_and_pos(
    logits: jnp.ndarray,  # [B, V]
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32, 0 => disabled
    key: jax.Array,
    seeds: jnp.ndarray | None,
    steps: jnp.ndarray | None,
):
    """Shared sampling core: returns (raw top-trunc logits [B, trunc]
    sorted desc, their token ids, the chosen position within them)."""
    B, V = logits.shape
    top_vals, top_idx, masked = _masked_scaled(
        logits, temperature, top_p, top_k
    )
    trunc = top_idx.shape[1]

    if seeds is None:
        gumbel = jax.random.gumbel(key, (B, trunc), dtype=jnp.float32)
    else:
        slot_keys = _row_keys(key, seeds, steps, B)
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (trunc,), dtype=jnp.float32)
        )(slot_keys)
    sampled_pos = jnp.argmax(masked + gumbel, axis=-1)  # [B]

    greedy = temperature <= _GREEDY_EPS
    pos = jnp.where(greedy, 0, sampled_pos)
    return top_vals, top_idx, pos


def verify_and_sample(
    logits: jnp.ndarray,  # [R, V] processed (penalized/suppressed) logits
    draft_next: jnp.ndarray,  # [R] int32 draft token this row verifies
    is_bonus: jnp.ndarray,  # [R] bool: no draft to verify at this row
    temperature: jnp.ndarray,  # [R]
    top_p: jnp.ndarray,  # [R]
    top_k: jnp.ndarray,  # [R] int32, 0 => disabled
    key: jax.Array,
    seeds: jnp.ndarray | None = None,  # [R] int32, -1 => unseeded
    steps: jnp.ndarray | None = None,  # [R] int32 per-seq sample index
    num_top: int = 0,
    all_greedy: bool = False,  # static: every row is temperature 0
):
    """Distribution-preserving speculative verification (rejection
    sampling with a deterministic proposal).

    Each row holds the model's logits at one candidate position and the
    draft token proposed there.  With the prompt-lookup drafter the
    proposal q is a point mass at the draft t, so the standard
    accept-with-min(1, p/q), resample-from-(p-q)+ rule (Leviathan et al.;
    the scheme vLLM's rejection sampler implements on GPU) reduces to:

      * accept t with probability p(t) — p being the row's actual
        sampling distribution: temperature-scaled, top-k/top-p-masked,
        over the top-``TRUNC`` slice (the distribution ``sample_tokens``
        draws from, so the guarantee is exact w.r.t. the engine, not an
        idealized full-vocab softmax);
      * on rejection, resample from p with t excluded (the normalized
        residual max(0, p - q)).

    The emitted token is then exactly p-distributed at every position,
    whatever the drafter proposed.  Greedy rows (temperature <= eps)
    reduce to exact argmax matching — the pre-existing greedy-exact
    contract.  ``is_bonus`` rows skip verification and draw a plain
    sample (the bonus token at the end of an all-accepted run).

    Returns ``(model_toks [R] int32, accept [R] bool, lp_data)`` where
    ``lp_data`` is ``(chosen_lp [R], top_ids [R, num_top], top_lps
    [R, num_top])`` when ``num_top > 0`` else None.
    """
    R, V = logits.shape
    if all_greedy and num_top == 0:
        # one-pass argmax verification: accept iff the draft IS the
        # argmax (same semantics as the general greedy branch below)
        am = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return am, (am == draft_next) & ~is_bonus, None
    top_vals, top_idx, masked = _masked_scaled(
        logits, temperature, top_p, top_k
    )
    trunc = top_idx.shape[1]

    seeds_eff = (
        jnp.full((R,), -1, jnp.int32) if seeds is None else seeds
    )
    base_keys = _row_keys(key, seeds_eff, steps, R)
    sub = jax.vmap(lambda k: jax.random.split(k, 2))(base_keys)  # [R,2,2]
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(sub[:, 0])
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (trunc,), dtype=jnp.float32)
    )(sub[:, 1])

    probs = jax.nn.softmax(masked, axis=-1)  # ineligible entries ~0
    is_draft = top_idx == draft_next[:, None]  # [R, trunc]
    p_draft = jnp.sum(jnp.where(is_draft, probs, 0.0), axis=-1)
    greedy = temperature <= _GREEDY_EPS
    accept = (
        jnp.where(greedy, top_idx[:, 0] == draft_next, u < p_draft)
        & ~is_bonus
    )

    # One gumbel draw serves both the rejection-resample (draft token
    # excluded — argmax-gumbel over the residual support renormalizes
    # implicitly) and the plain bonus sample (no exclusion): the two are
    # mutually exclusive per row.  A rejected row always has other
    # eligible entries: p_draft == 1 makes rejection impossible
    # (u ~ U[0,1) < 1).
    exclude = is_draft & ~is_bonus[:, None]
    pos_rs = jnp.argmax(
        jnp.where(exclude, -jnp.inf, masked) + gumbel, axis=-1
    )
    pos_draft = jnp.argmax(is_draft, axis=-1)
    pos = jnp.where(greedy, 0, jnp.where(accept, pos_draft, pos_rs))
    model_toks = jnp.take_along_axis(
        top_idx, pos[:, None], axis=-1
    )[:, 0].astype(jnp.int32)

    if num_top > 0:
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1, keepdims=True
        )
        lps = top_vals - lse
        chosen_lp = jnp.take_along_axis(lps, pos[:, None], axis=-1)[:, 0]
        return model_toks, accept, (
            chosen_lp,
            top_idx[:, :num_top].astype(jnp.int32),
            lps[:, :num_top],
        )
    return model_toks, accept, None


def sample_tokens(
    logits: jnp.ndarray,  # [B, V]
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32, 0 => disabled
    key: jax.Array,
    seeds: jnp.ndarray | None = None,  # [B] int32, -1 => unseeded
    steps: jnp.ndarray | None = None,  # [B] int32 per-seq sample index
    all_greedy: bool = False,  # static: every row is temperature 0
) -> jnp.ndarray:
    """Sample one token per slot honoring per-slot params. Returns [B] int32.

    When ``seeds``/``steps`` are given, a slot with ``seed >= 0`` draws its
    gumbel noise from ``fold_in(PRNGKey(seed), step)`` — a function of the
    request's seed and its per-sequence token index only, so the same seed
    reproduces the same tokens regardless of batch composition, engine step
    count, or preemption (the reference exposes vLLM's per-request ``seed``,
    vgate/backends/vllm_backend.py:39-46).  Unseeded slots fold the slot
    index into the engine's step key.  ``key`` must be a legacy uint32[2]
    key (``jax.random.PRNGKey``) so keys can be selected with ``where``.

    ``all_greedy`` (a STATIC flag the engine sets when every active
    request has temperature 0) takes a one-pass argmax instead of the
    top-``TRUNC`` ``lax.top_k`` — on TPU the top-k over a ~150k vocab
    lowers to an expensive sort, pure waste when nothing samples.
    """
    if all_greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _top_vals, top_idx, pos = _topk_and_pos(
        logits, temperature, top_p, top_k, key, seeds, steps
    )
    return jnp.take_along_axis(top_idx, pos[:, None], axis=-1)[:, 0].astype(
        jnp.int32
    )


def sample_tokens_with_logprobs(
    logits: jnp.ndarray,  # [B, V]
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    key: jax.Array,
    seeds: jnp.ndarray | None = None,
    steps: jnp.ndarray | None = None,
    num_top: int = 8,
):
    """``sample_tokens`` plus OpenAI-style logprobs.

    Returns ``(tokens [B], chosen_lp [B], top_ids [B, num_top],
    top_lps [B, num_top])`` where logprobs are log-softmax of the RAW
    logits (temperature/top-k/top-p modify only the sampling draw, not
    the reported distribution — the standard API convention).  The
    full-vocab logsumexp is the only extra work over plain sampling.
    """
    top_vals, top_idx, pos = _topk_and_pos(
        logits, temperature, top_p, top_k, key, seeds, steps
    )
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1, keepdims=True
    )
    lps = top_vals - lse  # [B, trunc] raw-logit log-softmax, sorted desc
    tokens = jnp.take_along_axis(
        top_idx, pos[:, None], axis=-1
    )[:, 0].astype(jnp.int32)
    chosen_lp = jnp.take_along_axis(lps, pos[:, None], axis=-1)[:, 0]
    return (
        tokens,
        chosen_lp,
        top_idx[:, :num_top].astype(jnp.int32),
        lps[:, :num_top],
    )


def apply_penalties(
    logits: jnp.ndarray,  # [B, V]
    counts: jnp.ndarray,  # [B, V] per-slot output-token counts (uint16/int32)
    frequency_penalty: jnp.ndarray,  # [B]
    presence_penalty: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """OpenAI frequency/presence penalties over the full vocabulary.

    ``logits[b, v] -= freq[b] * counts[b, v] + pres[b] * (counts[b, v] > 0)``
    — counts cover the tokens the request has GENERATED so far (not the
    prompt), matching the OpenAI definition.  Applied before temperature/
    top-k/top-p; when a request also asks for logprobs they are computed
    from these penalized logits (the distribution actually sampled).
    """
    c = counts.astype(jnp.float32)
    return (
        logits.astype(jnp.float32)
        - frequency_penalty[:, None] * c
        - presence_penalty[:, None] * (c > 0).astype(jnp.float32)
    )


# The two logit edits below are elementwise where they can be: a
# vocabulary iota compared against the row's K ids, in the [B, V] layout
# the lm-head produced, so that XLA fuses them into whatever pass reads
# the logits next (the argmax, the top-k's input, the log-sum-exp).  A
# scatter into the logits costs that array four passes on a TPU (a flat
# relayout and back, a copy, the update) whatever K; a compare costs the
# vector units K steps an element.  Past COMPARE_MAX_IDS ids a row the
# compares cost more than the passes and the edit is a scatter again
# (benchmarks/bench_kernels.py bench_sample_edits has the chip's
# numbers).  K is the arrays' static shape: the choice is made when the
# program is traced, and the two forms give the same bits.
COMPARE_MAX_IDS = 64


def _vocab_iota(logits: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1
    )


def _row_index(ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.arange(ids.shape[0])[:, None], ids.shape)


def _bias_by_compare(logits, bias_ids, bias_vals):
    iota = _vocab_iota(logits)
    # -0.0 and not 0.0: x + -0.0 is x for every x, so an element that no
    # id names keeps its bits, as under the scatter-add.  Ids are unique
    # within a row, so a chain of selects is the row's sum.
    bias = jnp.full(logits.shape, -0.0, jnp.float32)
    for k in range(bias_ids.shape[-1]):
        bias = jnp.where(
            iota == bias_ids[:, k, None], bias_vals[:, k, None], bias
        )
    return logits + bias


def _bias_by_scatter(logits, bias_ids, bias_vals):
    return logits.at[_row_index(bias_ids), bias_ids].add(
        bias_vals, mode="drop"
    )


def apply_logit_bias(
    logits: jnp.ndarray,  # [B, V]
    bias_ids: jnp.ndarray,  # [B, K] int32 token ids; >= V entries pad
    bias_vals: jnp.ndarray,  # [B, K] f32 additive biases
) -> jnp.ndarray:
    """OpenAI ``logit_bias``: add per-request biases to selected token
    logits before sampling (-100 effectively bans a token, +100
    effectively forces it).  Returns float32.  Ids are unique within a
    row and never negative (the engine fills them from a validated
    dict).  A padding entry carries an out-of-vocab id: it equals no
    vocabulary position, and a scatter drops it, so it is a no-op by
    construction (the same trick as suppress_stop_tokens)."""
    edit = (
        _bias_by_compare
        if bias_ids.shape[-1] <= COMPARE_MAX_IDS
        else _bias_by_scatter
    )
    return edit(logits.astype(jnp.float32), bias_ids, bias_vals)


def live_stop_ids(vocab: int, steps, min_tokens, stop_ids):
    """The rows' stop ids, those of a row at or above its floor turned
    into padding ([B, K] work): the logits' pass then only matches ids."""
    return jnp.where((steps < min_tokens)[:, None], stop_ids, vocab)


def _floor_by_compare(logits, steps, min_tokens, stop_ids):
    stop_ids = live_stop_ids(logits.shape[-1], steps, min_tokens, stop_ids)
    iota = _vocab_iota(logits)
    hit = iota == stop_ids[:, 0, None]
    for k in range(1, stop_ids.shape[-1]):
        hit |= iota == stop_ids[:, k, None]
    return jnp.where(hit, -1e30, logits)


def _floor_by_scatter(logits, steps, min_tokens, stop_ids):
    stop_ids = live_stop_ids(logits.shape[-1], steps, min_tokens, stop_ids)
    return logits.at[_row_index(stop_ids), stop_ids].set(
        -1e30, mode="drop"
    )


def suppress_stop_tokens(
    logits: jnp.ndarray,  # [B, V]
    steps: jnp.ndarray,  # [B] tokens generated so far
    min_tokens: jnp.ndarray,  # [B] per-slot floor (0 = off)
    stop_ids: jnp.ndarray,  # [B, K] int32 stop ids; >= V entries are padding
) -> jnp.ndarray:
    """min_tokens: slots below their floor cannot sample a stop token.

    Padding entries use an out-of-vocab id: it equals no vocabulary
    position, and a scatter drops it, so they are no-ops by
    construction; a row at or above its floor is all padding.
    """
    edit = (
        _floor_by_compare
        if stop_ids.shape[-1] <= COMPARE_MAX_IDS
        else _floor_by_scatter
    )
    return edit(logits, steps, min_tokens, stop_ids)
