"""What the families' tests of a long prompt's row blocks share
(``models/hybrid.py _by_row_blocks``): a whole prompt pass at a bucket of
four blocks of rows (the block's constant patched down to 8) against the
same pass with the loop off, and greedy tokens through the engine both
ways.  The dense stack's pass packs its group's real rows end to end
(``models/decoder.py _packed_prompt_pass``): its cases are groups."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu.config import load_config
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime import step_programs
from vgate_tpu.runtime.engine_core import EngineCore
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers
from vgate_tpu.backends.base import SamplingParams

BLOCK, BUCKET, PS, SLOTS = 8, 32, 4, 4
# what the two prompts of a pass fill (the longer one decides): one
# block, a block and a half, the whole bucket
FILLS = {"one-block": (8, 5), "a-block-and-a-half": (9, 12),
         "the-bucket": (32, 17)}
OFF = 1 << 20  # a block no bucket holds two of: the loop never engages


def _geometry(spec, context: int):
    return KVGeometry(
        num_layers=spec.attn_layers, num_pages=64, page_size=PS,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=context, dtype_bytes=4, pools=spec.kv_pools,
        index_layers=spec.index_layers, index_dim=spec.index_head_dim)


@functools.lru_cache(maxsize=None)
def _compiled(model_id: str, block: int, rows: int = 2):
    """(the whole prompt pass over [rows, BUCKET] tokens, traced with
    blocks of ``block`` rows and returning every row's logits; its
    weights and caches)."""
    spec = spec_for_model_id(model_id)
    params = decoder.init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    caches = (*make_kv_buffers(_geometry(spec, 128), jnp.float32),
              hybrid.make_state(spec, SLOTS, jnp.float32, PS) or None)

    def run(params, toks, lens, kp, vp, tables, state):
        return decoder.prefill_forward(
            params, spec, toks, lens, kp, vp, tables, state=state,
            slots=jnp.arange(rows, dtype=jnp.int32))

    patch = pytest.MonkeyPatch()
    patch.setattr(hybrid, "PROMPT_ROW_BLOCK", block)
    # every row's logits: the final norm and the head on every hidden row
    patch.setattr(decoder, "_last_rows", lambda x, lens: x)
    try:
        shapes = jax.eval_shape(lambda: (
            params, jnp.zeros((rows, BUCKET), jnp.int32),
            jnp.zeros((rows,), jnp.int32), *caches[:2],
            jnp.zeros((rows, BUCKET // PS), jnp.int32), caches[2]))
        return jax.jit(run).lower(*shapes).compile(), params, caches
    finally:
        patch.undo()


def page_tables(lens):
    """A page table a row: its real pages, then the trash page."""
    tables = np.zeros((len(lens), BUCKET // PS), np.int32)
    for b, n in enumerate(lens):
        pages = -(-n // PS)
        tables[b, :pages] = 1 + b * (BUCKET // PS) + np.arange(pages)
    return tables


def prompt_pass(model_id: str, block: int, lens):
    """(every row's logits [len(lens), S, V], the caches and the state
    after) of a whole prompt pass over seeded tokens of ``lens``."""
    run, params, (kp, vp, state) = _compiled(model_id, block, len(lens))
    rng = np.random.default_rng(42)
    toks = rng.integers(3, 250, (len(lens), BUCKET)).astype(np.int32)
    for b, n in enumerate(lens):
        toks[b, n:] = 0
    out = run(params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32), kp,
              vp, jnp.asarray(page_tables(lens)), state)
    return jax.tree.map(np.asarray, out)


def check_prompt_pass(model_id: str, lens):
    """The pass in blocks of rows gives the real rows' logits and every
    page but the trash page as the pass over the whole bucket does."""
    got = prompt_pass(model_id, BLOCK, lens)
    want = prompt_pass(model_id, OFF, lens)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(
            got[0][b, :n], want[0][b, :n], rtol=0, atol=1e-5)
    # pools and rings: page 0 is where padding goes
    leaves = lambda out: jax.tree.leaves(out[1:])
    assert len(leaves(got)) == len(leaves(want)) > 0
    for a, b in zip(leaves(got), leaves(want)):
        np.testing.assert_allclose(a[:, :, 1:], b[:, :, 1:], rtol=0,
                                   atol=1e-5)


# a dense group: padding rows (three prompts run as four), lengths on a
# block's edge, one prompt, a full group
GROUPS = {"three-run-as-four": (12, 30, 8, 1), "on-a-blocks-edge": (8, 9),
          "the-bucket-and-a-token": (32, 1), "one-prompt": (21,),
          "a-full-group": (32, 32, 32, 32)}


def check_packed_pass(model_id: str, lens):
    """The pass over the group's packed rows gives every real row's
    logits and every real token's K and V in its page as the pass over
    the whole bucket does; what lies behind a row's last token in its
    last page is finite (a decode step's attention masks it, and 0 x a
    value that is not finite would not be 0)."""
    got = prompt_pass(model_id, BLOCK, lens)
    want = prompt_pass(model_id, OFF, lens)
    tables = page_tables(lens)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(
            got[0][b, :n], want[0][b, :n], rtol=0, atol=1e-5)
        at = np.arange(n)
        for a, w in zip(got[1:3], want[1:3]):  # [L, KV, P, ps, hd]
            assert np.abs(w[:, :, tables[b, at // PS], at % PS]).max() > 0
            np.testing.assert_allclose(
                a[:, :, tables[b, at // PS], at % PS],
                w[:, :, tables[b, at // PS], at % PS], rtol=0, atol=1e-5)
    assert all(np.isfinite(a).all() for a in got[1:3])


def greedy_tokens(monkeypatch, model_id: str, block: int, prompts, steps,
                  group: int = 1, tp: int = 1):
    """The prompts are all submitted before the engine's first tick,
    which runs them ``group`` a program.  ``tp``: chips the weights'
    heads and columns are sharded over."""
    monkeypatch.setattr(hybrid, "PROMPT_ROW_BLOCK", block)
    # the step program's cache does not know the constant
    step_programs._prefill_step.clear_cache()
    config = load_config(
        model={"model_id": model_id, "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 96},
        tpu={"dp": 1, "tp": tp, "ep": 1, "sp": 1, "kv_num_pages": 96,
             "kv_page_size": PS, "max_batch_slots": SLOTS,
             "prefill_buckets": [BUCKET], "prefill_batch_max": group,
             "use_pallas": False, "decode_chunk": 4},
        scheduler={"max_queue_size": 16}, logging={"level": "WARNING"},
    )
    core = EngineCore(config, devices=jax.devices()[:tp])
    try:
        seqs = [core.submit_tokens(p, SamplingParams(
            max_tokens=steps, min_tokens=steps, temperature=0.0))
            for p in prompts]
        core.start()
        for s in seqs:
            assert s.done_event.wait(timeout=600)
            assert s.error is None, s.error
        return ([list(s.generated_ids) for s in seqs],
                core.perf.totals()["prefill"])
    finally:
        core.stop()
        step_programs._prefill_step.clear_cache()


def check_greedy_identity(monkeypatch, model_id: str, steps: int = 32,
                          group: int = 1, tp: int = 1):
    """Three prompts (one block, a block and a half, nearly the bucket)
    decode to the same tokens with the loop and without, and the counter
    says which of the two programs ran.  ``group`` 4 (a stack of one
    kind): the three run as ONE program of four rows."""
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(3, 250, n)]
               for n in (8, 12, 30)]
    got, rows = greedy_tokens(
        monkeypatch, model_id, BLOCK, prompts, steps, group, tp)
    want, whole = greedy_tokens(
        monkeypatch, model_id, OFF, prompts, steps, group, tp)
    assert got == want and all(len(g) == steps for g in got)
    assert rows["rows_real"] == whole["rows_real"] == 8 + 12 + 30
    if group == 1:
        # a prompt a program: one block, two, four of them; else the
        # bucket
        assert rows["rows_worked"] == 8 + 16 + 32
        assert whole["rows_worked"] == 3 * BUCKET
    else:
        # the three and a padding row's one token, end to end: 51 rows
        # in seven blocks; else four rows of the bucket
        assert rows["rows_worked"] == 56
        assert whole["rows_worked"] == 4 * BUCKET
    assert rows["rows_padding"] == rows["rows_worked"] - rows["rows_real"]


def traced(model_id: str, block: int, shape):
    """The jaxprs, as text, of a whole prompt pass over tokens of
    ``shape`` (group, rows) and of a decode step of four slots, traced
    with blocks of ``block`` rows (shapes alone: nothing is drawn or
    compiled)."""
    group, rows = shape
    spec = spec_for_model_id(model_id)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    params, (kp, vp), state = jax.eval_shape(lambda: (
        decoder.init_params(spec, jax.random.PRNGKey(0), jnp.float32),
        make_kv_buffers(_geometry(spec, rows), jnp.float32),
        hybrid.make_state(spec, SLOTS, jnp.float32, PS) or None))
    patch = pytest.MonkeyPatch()
    patch.setattr(hybrid, "PROMPT_ROW_BLOCK", block)
    try:
        prompt = jax.make_jaxpr(
            lambda params, toks, lens, kp, vp, tables, state, slots:
            decoder.prefill_forward(params, spec, toks, lens, kp, vp,
                                    tables, state=state, slots=slots))(
            params, ints(group, rows), ints(group), kp, vp,
            ints(group, rows // PS), state, ints(group))
        step = jax.make_jaxpr(
            lambda params, toks, at, kp, vp, tables, state:
            decoder.decode_forward(params, spec, toks, at, kp, vp, tables,
                                   state=state))(
            params, ints(SLOTS), ints(SLOTS), kp, vp,
            ints(SLOTS, rows // PS), state)
    finally:
        patch.undo()
    return str(prompt), str(step)


def check_small_programs_hold_no_loop(model_id: str, small=(2, 1024),
                                      large=(2, 2048)):
    """A decode step and a wave of 1,024-row prompts are traced as they
    are with the loop off, to the letter; a 2,048-row prompt program is
    not: it holds the counted loops.  ``small`` and ``large``: the
    (group, rows) of the two prompt programs."""
    loops = lambda text: text.count(" while[")
    assert hybrid.PROMPT_ROW_BLOCK == 1024
    prompt, step = traced(model_id, hybrid.PROMPT_ROW_BLOCK, small)
    prompt_off, step_off = traced(model_id, OFF, small)
    assert prompt == prompt_off and step == step_off
    prompt, step = traced(model_id, hybrid.PROMPT_ROW_BLOCK, large)
    prompt_off, step_off = traced(model_id, OFF, large)
    assert step == step_off
    assert loops(prompt) > loops(prompt_off)
