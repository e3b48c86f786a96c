"""``tiny-mellum`` through the ENGINE against the plain reference
(``perfbench/references/mellum.py``): unequal rows in one wave and what
``/stats`` and ``/debug/perf`` report of the rings and of the rotary by
layer kind, chunked prefill and a slot reused, preemption by recompute,
journal replay; what knows pages only, refused by name at engine
construction; and K-EXAONE's tiny program, which shares every line of
this path, bit for bit what it was."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from perfbench.references import mellum as ref
from tests import family_contract as contract
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

PS, SLOTS, RING = 4, 4, 12  # page, decode slots, a ring's tokens (3 pages)
FAMILY = contract.Family(
    "mellum2-12b-a2.5b-l8.json", ref=ref,
    tol={"float32": 1e-4},  # float32 on both sides: tests/test_mellum.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="per-slot ring")


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (inside a window, past a ring, several
    rings), each a whole-prompt pass and decode steps past a ring's
    length; /stats and /debug/perf say what the cache is, what the rings
    moved and which layers took which rotary."""
    contract.unequal_rows(FAMILY, engine, (7, 19, 45), max_tokens=RING + 2)
    assert not engine.prefix_cache_enabled
    stats = engine.get_stats()
    # pages over the TWO full layers only: 2 x (K, V) x 4 x 2 x 16 x 4 B
    assert stats["kv_page_bytes"] == 2 * 2 * PS * 2 * 16 * 4
    cache = stats["state_cache"]
    assert cache["kind"] == "ring" and cache["layers"] == 6
    assert cache["tokens_per_slot"] == RING and cache["window"] == 8
    assert cache["bytes_per_slot"] == 6 * 2 * 2 * RING * 16 * 4
    assert cache["bytes"] == SLOTS * cache["bytes_per_slot"]
    assert engine.state["ring_k"].shape == (6, 2, 1 + SLOTS * 3, PS, 16)
    assert stats["rotary"] == {
        "sliding_attention": {"type": "default", "theta": 10000.0,
                              "factor": 1.0, "amplitude": 1.0},
        "full_attention": {"type": "yarn", "theta": 10000.0, "factor": 4.0,
                           "amplitude": 1.1386294361119891}}
    swa = engine.perf.totals()["swa"]
    assert swa["prefill_prompts"] == 3 and swa["prefill_launches"] == 18
    assert swa["prefill_rows"] == 6 * (7 + 19 + 45)
    assert swa["decode_launches"] == 6 * swa["decode_steps"]
    # min(context, 8) rows a sequence a layer, three sequences at most
    assert 0 < swa["decode_row_reads"] <= 6 * 8 * 3 * swa["decode_steps"]
    booked = engine.perf.totals()["moe"]
    assert booked["held_assignments"] == booked["assignments"] > 0


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11 (a later chunk's full
    layers read YaRN'd keys off the pool, its window layers the ring);
    the 9-token prompt then takes a ring whose other pages still hold
    the first tenant's rows, and decodes past the ring's length."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 32, (75, 9), (8, RING + 2))


def test_preemption_by_recompute_rebuilds_the_rings():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 15, "prefill_buckets": [32]})


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)


# sha256 of tiny-swa-moe's prompt and decode programs as JAX lowers them
# (StableHLO text, no source locations) at the PARENT commit (fa4d527),
# by the function below.  (The logits themselves were compared once, on
# the builder's CPU: one 21-token prompt and six decode steps through
# rings and pool hash alike on both trees, CHANGES.md PR 57; a digest of
# float bits would tie the test to one machine's vector units.)
EXAONE_PARENT_PROGRAMS = (
    "9234d4dc332d2c28a31b993cfb89a6304023621cc583ca5bf3500828fd53aa54")


def exaone_program_digest():
    spec = spec_for_model_id("tiny-swa-moe")
    A = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: decoder.init_params(
        spec, jax.random.PRNGKey(0), jnp.float32))
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=32, page_size=PS,
        kv_heads=spec.num_kv_heads, head_dim=spec.head_dim,
        max_model_len=64, dtype_bytes=4)
    kp, vp = jax.eval_shape(lambda: make_kv_buffers(geo, jnp.float32))
    st = jax.eval_shape(lambda: hybrid.make_state(spec, 2, jnp.float32, PS))
    i32 = lambda *shape: A(shape, jnp.int32)
    prompt = jax.jit(decoder.prefill_forward, static_argnums=1).lower(
        params, spec, i32(1, 32), i32(1), kp, vp, i32(1, 8), state=st,
        slots=i32(1))
    step = jax.jit(decoder.decode_forward, static_argnums=1).lower(
        params, spec, i32(2), i32(2), kp, vp, i32(2, 16),
        active=A((2,), jnp.bool_), state=st)
    return hashlib.sha256(
        (prompt.as_text() + step.as_text()).encode()).hexdigest()


def test_k_exaones_tiny_program_is_the_parents_to_the_last_operation():
    """The rotary became a property of a layer's kind and the window's
    blocks a function of the window: K-EXAONE's programs (plain rotary
    on its window layers, none on its full layers, a window of 8) must
    be the parent's, operation for operation, and so are its logits bit
    for bit."""
    assert exaone_program_digest() == EXAONE_PARENT_PROGRAMS
