"""Frequency/presence penalties across the sampler, engine, speculative
mode, and HTTP (OpenAI semantics: counts over generated tokens only;
beyond the reference schema, vgate-client/vgate_client/models.py:32-37)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.ops.sampling import apply_penalties
from vgate_tpu.runtime.engine_core import EngineCore

from tests.test_logprobs import engine_config, http_config


def test_apply_penalties_formula():
    logits = jnp.zeros((2, 6), jnp.float32)
    counts = jnp.asarray([[0, 1, 3, 0, 0, 0], [2, 0, 0, 0, 0, 1]],
                         jnp.uint16)
    freq = jnp.asarray([0.5, 1.0], jnp.float32)
    pres = jnp.asarray([0.25, 0.0], jnp.float32)
    out = np.asarray(apply_penalties(logits, counts, freq, pres))
    np.testing.assert_allclose(
        out[0], [0, -0.75, -1.75, 0, 0, 0], atol=1e-6
    )
    np.testing.assert_allclose(out[1], [-2, 0, 0, 0, 0, -1], atol=1e-6)


def _distinct_ratio(ids):
    return len(set(ids)) / max(1, len(ids))


def test_engine_frequency_penalty_suppresses_repeats():
    """Greedy decoding with a huge frequency penalty can never choose the
    same token twice (each choice drops by 100 once used); without
    penalties the random-init model repeats heavily."""
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    try:
        n = 16
        [plain] = core.generate(
            ["repetition probe"],
            [SamplingParams(max_tokens=n, temperature=0.0)],
        )
        [pen] = core.generate(
            ["repetition probe"],
            [SamplingParams(max_tokens=n, temperature=0.0,
                            frequency_penalty=100.0)],
        )
        assert _distinct_ratio(pen["token_ids"]) == 1.0
        # the penalized run must actually differ from the plain one
        # unless the plain one never repeated (random weights usually do)
        if _distinct_ratio(plain["token_ids"]) < 1.0:
            assert pen["token_ids"] != plain["token_ids"]
    finally:
        core.stop()


def test_engine_penalties_isolated_per_slot():
    """A penalized sequence must not alter its co-batched neighbour."""
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    try:
        [alone] = core.generate(
            ["neighbour probe"], [SamplingParams(max_tokens=8,
                                                 temperature=0.0)]
        )
        both = core.generate(
            ["neighbour probe", "penalized one"],
            [
                SamplingParams(max_tokens=8, temperature=0.0),
                SamplingParams(max_tokens=8, temperature=0.0,
                               frequency_penalty=100.0),
            ],
        )
        assert both[0]["token_ids"] == alone["token_ids"]
        assert _distinct_ratio(both[1]["token_ids"]) == 1.0
    finally:
        core.stop()


def test_speculative_penalties_match_plain_engine():
    """Penalties under draft-and-verify must produce the same tokens as
    the plain engine (the verify pass threads the evolving histogram
    through every candidate position)."""
    prompts = ["spec pen probe", "second spec pen"]
    params = [
        SamplingParams(max_tokens=12, temperature=0.0,
                       frequency_penalty=100.0),
        SamplingParams(max_tokens=12, temperature=0.0,
                       presence_penalty=50.0),
    ]
    plain = EngineCore(engine_config(), devices=jax.devices()[:1])
    plain.start()
    try:
        base = plain.generate(prompts, params)
    finally:
        plain.stop()
    spec = EngineCore(
        engine_config(speculative_k=3), devices=jax.devices()[:1]
    )
    spec.start()
    try:
        got = spec.generate(prompts, params)
    finally:
        spec.stop()
    for b, g in zip(base, got):
        assert b["token_ids"] == g["token_ids"]
        assert _distinct_ratio(g["token_ids"]) == 1.0


async def test_http_penalties_roundtrip():
    from aiohttp.test_utils import TestClient, TestServer

    from vgate_tpu.server.app import create_app

    client = TestClient(TestServer(create_app(http_config())))
    await client.start_server()
    try:
        resp = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "pen http"}],
                "max_tokens": 10,
                "temperature": 0,
                "frequency_penalty": 2.0,
            },
        )
        assert resp.status == 200

        bad = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "x"}],
                "frequency_penalty": 5.0,  # out of the -2..2 range
            },
        )
        assert bad.status == 422
    finally:
        await client.close()


def test_min_tokens_suppresses_model_stops():
    """With min_tokens set, a sequence that would stop early (forced by
    stop_token_ids on its own greedy output) keeps generating to the
    floor; without it, it stops immediately."""
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    try:
        [base] = core.generate(
            ["min tokens probe"],
            [SamplingParams(max_tokens=12, temperature=0.0)],
        )
        first = base["token_ids"][0]
        # stopping on the very first token => 1-token completion
        [short] = core.generate(
            ["min tokens probe"],
            [SamplingParams(max_tokens=12, temperature=0.0,
                            stop_token_ids=[first])],
        )
        assert short["num_tokens"] == 1
        # with min_tokens=6 the stop id is suppressed until 6 tokens exist
        [floored] = core.generate(
            ["min tokens probe"],
            [SamplingParams(max_tokens=12, temperature=0.0,
                            stop_token_ids=[first], min_tokens=6)],
        )
        assert floored["num_tokens"] >= 6
        assert first not in floored["token_ids"][:6]
    finally:
        core.stop()


def test_min_tokens_speculative_equivalence():
    """min_tokens composes with draft-and-verify: same output as the
    plain engine."""
    params = [SamplingParams(max_tokens=10, temperature=0.0, min_tokens=8)]
    plain = EngineCore(engine_config(), devices=jax.devices()[:1])
    plain.start()
    try:
        base = plain.generate(["spec min probe"], params)
    finally:
        plain.stop()
    spec = EngineCore(
        engine_config(speculative_k=3), devices=jax.devices()[:1]
    )
    spec.start()
    try:
        got = spec.generate(["spec min probe"], params)
    finally:
        spec.stop()
    assert base[0]["token_ids"] == got[0]["token_ids"]


async def test_http_min_tokens_passthrough():
    from aiohttp.test_utils import TestClient, TestServer

    from vgate_tpu.server.app import create_app

    client = TestClient(TestServer(create_app(http_config())))
    await client.start_server()
    try:
        resp = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "floor"}],
                "max_tokens": 10,
                "min_tokens": 5,
                "temperature": 0,
            },
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["usage"]["completion_tokens"] >= 5
        bad = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "x"}],
                "min_tokens": -1,
            },
        )
        assert bad.status == 422
    finally:
        await client.close()


def test_min_tokens_above_budget_still_finishes_by_length():
    """min_tokens > max_tokens must not hang: the length finish stays
    live below the floor (review finding — the floor gates only stops)."""
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    try:
        [r] = core.generate(
            ["over floor probe"],
            [SamplingParams(max_tokens=4, temperature=0.0, min_tokens=50)],
        )
        assert r["finish_reason"] == "length"
        assert r["num_tokens"] == 4
    finally:
        core.stop()


# -------------------------------------------------------- logit_bias

def test_apply_logit_bias_op():
    import numpy as np

    from vgate_tpu.ops.sampling import apply_logit_bias

    logits = jnp.zeros((2, 8), jnp.float32)
    ids = jnp.asarray([[3, 5], [8, 8]], jnp.int32)  # row 1: all padding
    vals = jnp.asarray([[10.0, -10.0], [1.0, 1.0]], jnp.float32)
    out = np.asarray(apply_logit_bias(logits, ids, vals))
    assert out[0, 3] == 10.0 and out[0, 5] == -10.0
    assert np.all(out[1] == 0.0)  # out-of-vocab ids dropped


# The two logit edits against a plain oracle (a loop over rows and ids),
# bit for bit.  A case: (bias width or None, stop width or None, input
# dtype, which rows stand below their floor).  Widths past
# sampling.COMPARE_MAX_IDS take the scatter form, the others the
# compares: one oracle holds both.
_EDIT_V, _EDIT_B = 203, 6
_EDIT_CASES = [
    *[(w, None, dt, None) for w in (1, 16, 32, 128)
      for dt in ("float32", "bfloat16")],
    *[(None, w, dt, "mixed") for w in (1, 2, 4, 128)
      for dt in ("float32", "bfloat16")],
    (None, 2, "float32", "none"),
    (None, 128, "float32", "none"),
    (None, 2, "float32", "all"),
    *[(bw, sw, dt, "mixed") for bw, sw in ((1, 1), (16, 2), (32, 4), (128, 2))
      for dt in ("float32", "bfloat16")],
]


def _edit_inputs(bias_width, stop_width, dtype, below):
    rng = np.random.default_rng([bias_width or 0, stop_width or 0, len(str(below))])
    V, B = _EDIT_V, _EDIT_B
    logits = rng.normal(size=(B, V)).astype(np.float32) * 5
    logits[0, :4] = [0.0, -0.0, 1e30, -1e30]
    logits = jnp.asarray(logits, dtype)
    bias = floor = None
    if bias_width is not None:
        # distinct ids a row, a third of them padding (>= V, one far past)
        ids = np.stack([
            rng.permutation(V + V // 2)[:bias_width] for _ in range(B)
        ]).astype(np.int32)
        ids[1, 0] = 2**31 - 1
        ids[2] = V  # a row with no bias among biased rows
        vals = rng.choice(
            [100.0, -100.0, 0.5, -0.25, 1e-3, 0.0], size=ids.shape
        ).astype(np.float32)
        # the first and the last vocabulary position, +100 and -100
        ids[0, 0], vals[0, 0] = 0, 100.0
        ids[3, 0], vals[3, 0] = V - 1, -100.0
        ids[3, 1:][ids[3, 1:] == V - 1] = V
        ids[0, 1:][ids[0, 1:] == 0] = V
        bias = (ids, vals)
    if stop_width is not None:
        stops = rng.integers(0, V + V // 2, size=(B, stop_width))
        stops[0, 0], stops[1, -1] = V - 1, 0
        floors = np.asarray([4, 4, 4, 0, 7, 1], np.int32)
        steps = {
            # below, at, above, no floor, below, at
            "mixed": [3, 4, 9, 0, 0, 1],
            "none": [4, 5, 6, 0, 7, 2],
            "all": [0, 0, 0, -1, 0, 0],
        }[below]
        floor = (np.asarray(steps, np.int32), floors,
                 stops.astype(np.int32))
    return logits, bias, floor


def _edit_oracle(logits, bias, floor):
    V = logits.shape[1]
    out = np.array(logits)
    if bias is not None:
        out = out.astype(np.float32)
        for b, (ids, vals) in enumerate(zip(*bias)):
            for tid, val in zip(ids, vals):
                if 0 <= tid < V:
                    out[b, tid] = np.float32(out[b, tid]) + np.float32(val)
    if floor is not None:
        for b, (step, min_tokens, stops) in enumerate(zip(*floor)):
            if step < min_tokens:
                for tid in stops:
                    if 0 <= tid < V:
                        out[b, tid] = -1e30
    return out


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.fast  # a second a case: runs in tier-1, unlike this file
@pytest.mark.parametrize(
    "bias_width,stop_width,dtype,below", _EDIT_CASES,
    ids=lambda v: "-" if v is None else str(v),
)
def test_logit_edits_match_a_plain_oracle(bias_width, stop_width, dtype,
                                          below):
    """`apply_logit_bias`, `suppress_stop_tokens` and the two composed
    in the program's order (bias, then floor), jitted as the step
    programs trace them."""
    from vgate_tpu.ops.sampling import apply_logit_bias, suppress_stop_tokens

    logits, bias, floor = _edit_inputs(bias_width, stop_width, dtype, below)

    @jax.jit
    def edit(logits):
        if bias is not None:
            logits = apply_logit_bias(logits, *map(jnp.asarray, bias))
        if floor is not None:
            logits = suppress_stop_tokens(logits, *map(jnp.asarray, floor))
        return logits

    got, want = edit(logits), _edit_oracle(logits, bias, floor)
    assert got.dtype == (jnp.float32 if bias is not None else logits.dtype)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if below == "none":
        np.testing.assert_array_equal(_bits(got), _bits(logits))
    if below in ("mixed", "all"):  # the floor did fire
        floored = lambda x: (np.asarray(x, np.float32) < -9e29).sum()
        assert floored(got) > floored(logits)


@pytest.mark.fast  # tier-1, as the oracle's cases above
@pytest.mark.parametrize("width", [1, 16, 128])
def test_logit_edit_forms_give_the_same_bits(width):
    """The compare form and the scatter form are one function of the
    width: either gives the other's bits at any width."""
    from vgate_tpu.ops import sampling

    logits, (ids, vals), (steps, floors, stops) = _edit_inputs(
        width, width, "float32", "mixed"
    )
    np.testing.assert_array_equal(
        _bits(jax.jit(sampling._bias_by_compare)(logits, ids, vals)),
        _bits(jax.jit(sampling._bias_by_scatter)(logits, ids, vals)),
    )
    np.testing.assert_array_equal(
        _bits(jax.jit(sampling._floor_by_compare)(logits, steps, floors, stops)),
        _bits(jax.jit(sampling._floor_by_scatter)(logits, steps, floors, stops)),
    )


def test_logit_bias_forces_and_bans_tokens_through_engine():
    """+100 on one token makes greedy pick it every step (including the
    prefill's first token); -100 on the natural argmax bans it for a
    sampled request."""
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    try:
        forced = core.submit_tokens(
            [3, 4, 5, 6],
            SamplingParams(
                max_tokens=6, temperature=0.0, logit_bias={7: 100.0}
            ),
        )
        assert forced.done_event.wait(300)
        assert list(forced.generated_ids) == [7] * 6

        # ban: find the natural greedy first token, then bias it away
        [base] = core.generate(["ban probe"], [
            SamplingParams(max_tokens=1, temperature=0.0)
        ])
        banned_tok = base["token_ids"][0]
        seq = core.submit_prompt(
            "ban probe",
            SamplingParams(
                max_tokens=4, temperature=0.0,
                logit_bias={banned_tok: -100.0},
            ),
        )
        assert seq.done_event.wait(300)
        assert banned_tok not in seq.generated_ids
    finally:
        core.stop()


def test_logit_bias_with_speculative_rounds():
    """Bias applies at every verify position: a +100 forced token under
    spec decoding still emits only that token."""
    from vgate_tpu.config import load_config

    cfg = load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 2, "prefill_buckets": [8],
            "use_pallas": False, "speculative_k": 3,
        },
        logging={"level": "WARNING"},
    )
    core = EngineCore(cfg, devices=jax.devices()[:1])
    core.drafter = lambda seq, k: [7] * k  # drafts the forced token
    core.start()
    try:
        seq = core.submit_tokens(
            [3, 4, 5],
            SamplingParams(
                max_tokens=6, temperature=0.0, logit_bias={7: 100.0}
            ),
        )
        assert seq.done_event.wait(300)
        assert list(seq.generated_ids) == [7] * 6
        assert core.total_spec_accepted > 0  # drafts matched the bias
    finally:
        core.stop()
