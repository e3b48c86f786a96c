"""``tiny-granite-hybrid`` (Granite 4.0-H at toy widths: Mamba-2 layers
with ONE B/C group for all their heads beside GQA attention without
rotary, a dense SwiGLU behind every mixer, the four multipliers at
values other than 1, a tied head) against the plain reference's full
forward (``perfbench/references/granite_hybrid.py``: no cache, no
state, the recurrence one token after another) on the same seeded
weights: the forwards directly (whole prompt, then decode through
pages and state; a chunked prefill); each multiplier left at 1; the
published preset's stack, counts and cache geometry.
``tests/test_granite_hybrid_engine.py`` has the same through the
engine."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import granite_hybrid as ref
from tests.family_contract import one_length
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

SPEC = spec_for_model_id("tiny-granite-hybrid")
PUBLISHED = spec_for_model_id("ibm-granite/granite-4.0-h-micro")
CONFIG = manifest.load_json(
    manifest.HERE, "configs", "granite-4.0-h-micro.json")
# the tiny preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = CONFIG["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (a
# carried state against a scan from zeros, the chunk-wise recurrence
# against the token-wise, blockwise softmax against one): measured 4e-7
TOL = 1e-5
PS, SLOTS = 4, 4
PREFILL = jax.jit(decoder.prefill_forward, static_argnums=1)
SUFFIX = jax.jit(decoder.prefill_suffix_forward, static_argnums=1)
DECODE = jax.jit(decoder.decode_forward, static_argnums=1)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def fresh_cache(spec=SPEC):
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=64, page_size=PS,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=128, dtype_bytes=4)
    return (*make_kv_buffers(geo, jnp.float32),
            hybrid.make_state(spec, SLOTS, jnp.float32, PS))


def served_logprobs(params, seq, prompt_len, slot=2, chunks=None, spec=SPEC,
                    dirty=False):
    """Log-softmax rows for positions ``prompt_len - 1 .. len(seq) - 2``
    from the program's forwards: the prompt whole (or in ``chunks``),
    then one decode step a token through pages and state.  ``dirty``:
    the slot's row starts as another tenant left it."""
    kp, vp, st = fresh_cache(spec)
    if dirty:
        st = {"S": st["S"] + 2.0, "conv": st["conv"] + 3.0}
    table = np.arange(1, 33, dtype=np.int32)[None]
    one = lambda v: jnp.asarray([v])
    if chunks is None:
        S = -(-prompt_len // 16) * 16
        toks = np.zeros((1, S), np.int32)
        toks[0, :prompt_len] = seq[:prompt_len]
        logits, kp, vp, st = PREFILL(
            params, spec, jnp.asarray(toks), one(prompt_len), kp, vp,
            jnp.asarray(table[:, :S // PS]), state=st, slots=one(slot))
    else:
        done = 0
        for want in chunks:
            n = min(want, prompt_len - done)
            S = -(-n // 8) * 8
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = seq[done:done + n]
            own = table[:, done // PS: (done + S) // PS]
            logits, kp, vp, st = SUFFIX(
                params, spec, jnp.asarray(toks), one(done), one(n), kp, vp,
                jnp.asarray(own), jnp.asarray(table), state=st,
                slots=one(slot))
            done += n
    rows = [jax.nn.log_softmax(logits[0])]
    tables = np.zeros((SLOTS, 32), np.int32)
    tables[slot] = table[0]
    active = np.arange(SLOTS) == slot
    idle = {k: np.asarray(v)[:, ~active] for k, v in st.items()}
    for pos in range(prompt_len, len(seq) - 1):
        tok = np.where(active, seq[pos], 0).astype(np.int32)
        at = np.where(active, pos, 0).astype(np.int32)
        logits, kp, vp, st, _ = DECODE(
            params, spec, jnp.asarray(tok), jnp.asarray(at), kp, vp,
            jnp.asarray(tables), active=jnp.asarray(active), state=st)
        rows.append(jax.nn.log_softmax(logits[slot]))
    # idle rows are left alone, whatever the steps wrote
    for k, v in idle.items():
        assert np.array_equal(np.asarray(st[k])[:, ~active], v), k
    return np.stack([np.asarray(r) for r in rows])


def reference_logprobs(seq, prompt_len, cfg=TINY):
    return one_length(functools.partial(ref.logprobs, cfg, 0, jnp.float32),
                      seq, prompt_len, 64)


def sequence(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(3, 500, n)]


@pytest.mark.parametrize("prompt_len, decoded, what", [
    (1, 4, "a prompt shorter than the convolution's tail"),
    (3, 4, "a prompt of exactly the tail's rows"),
    (5, 6, "under two pages"),
    (16, 6, "a prompt that fills its bucket and its chunk: no padding"),
    (13, 20, "twenty decode steps over the state"),
    (30, 7, "two chunks of 16 and a page boundary inside the decode steps"),
])
def test_whole_prompt_then_decode_through_pages_and_state(
        params, prompt_len, decoded, what):
    seq = sequence(prompt_len, prompt_len + decoded)
    got = served_logprobs(params, seq, prompt_len, dirty=True)
    want = reference_logprobs(seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what


@pytest.mark.parametrize("chunks", [(16, 8, 8), (8, 24), (24, 8)])
def test_a_chunked_prefill_carries_state_and_tail_from_chunk_to_chunk(
        params, chunks):
    """A later chunk's recurrence starts from the state and the tail the
    chunks before left in the slot (taken at their REAL length), its
    attention reads their pages."""
    seq = sequence(7, 30 + 5)
    whole = served_logprobs(params, seq, 30)
    chunked = served_logprobs(params, seq, 30, chunks=chunks, dirty=True)
    assert np.abs(chunked - whole).max() < TOL
    assert np.abs(chunked - reference_logprobs(seq, 30)).max() < TOL


@pytest.mark.parametrize("name", ref.MULTIPLIERS)
def test_a_multiplier_left_at_one_fails_the_same_tolerance(params, name):
    """The program with ONE of the four published multipliers left at 1
    (the preset that lost it) is no longer the reference's model, by the
    tolerance the comparison above holds: each is applied, and the
    comparison sees each."""
    assert getattr(SPEC, name) not in (0.0, 1.0)
    assert getattr(SPEC, name) == TINY[name]
    lost = dataclasses.replace(SPEC, name=f"tiny-lost-{name}", **{name: 1.0})
    seq = sequence(11, 13 + 6)
    want = reference_logprobs(seq, 13)
    assert np.abs(served_logprobs(params, seq, 13) - want).max() < TOL
    off = np.abs(served_logprobs(params, seq, 13, spec=lost) - want)
    # (the least, the attention's: 3.1e-4 at scores this flat)
    assert off.max() > 10 * TOL, (name, off.max())
    # and the reference under the same loss agrees with the program's
    same = reference_logprobs(seq, 13, dict(TINY, **{name: 1.0}))
    assert np.abs(
        served_logprobs(params, seq, 13, spec=lost) - same).max() < TOL


def test_the_reference_rounds_its_weights_when_asked():
    """``round_to``: the drawn weights rounded once more before the
    arithmetic (what the tolerance's measurement runs at float8)."""
    seq = sequence(5, 12)
    plain = reference_logprobs(seq, 8)
    low = ref.logprobs(TINY, 0, jnp.float32, [seq], [8],
                       round_to=jnp.float8_e4m3fn)[0]
    assert plain.shape == low.shape
    assert np.abs(plain - low).max() > 1e-3


# ---- the published preset: stack, counts, cache geometry


def test_the_published_stack():
    stack = PUBLISHED.stack
    assert len(stack) == 40
    assert [i for i, layer in enumerate(stack) if layer[0] == "attn"] == [
        5, 15, 25, 35]
    assert stack.count(("mamba", "mlp")) == 36
    assert stack.count(("attn", "mlp")) == 4
    assert (PUBLISHED.lead_layers, PUBLISHED.layers_per_period,
            PUBLISHED.num_periods) == (0, 10, 4)
    assert PUBLISHED.lead_blocks == ()
    assert [b[0] for b in PUBLISHED.period_blocks] == (
        ["mamba", "mlp"] * 5 + ["attn", "mlp"] + ["mamba", "mlp"] * 4)
    # the groups go by a layer's first sub-block and hold its SwiGLU
    assert {b[1] for b in PUBLISHED.period_blocks} == {"mamba", "attn"}
    assert (PUBLISHED.group_layers("mamba"),
            PUBLISHED.group_layers("attn")) == (9, 1)
    # the walker scans five and four repeats of (mamba, mlp)
    assert [(len(u), r) for u, r in hybrid._segments(
        PUBLISHED.period_blocks)] == [(2, 5), (1, 1), (1, 1), (2, 4)]
    assert (SPEC.lead_layers, SPEC.num_periods, SPEC.linear_layers,
            SPEC.attn_layers) == (0, 1, 4, 1)


def test_parameter_counts_and_the_files_keys():
    per = PUBLISHED._kind_params()
    D = PUBLISHED.hidden_size
    assert per["mamba"] + per["mlp"] + 2 * D == 76_182_976
    assert per["attn"] + per["mlp"] + 2 * D == 60_821_504
    assert PUBLISHED.num_params == 3_191_396_096
    assert (PUBLISHED.moe_layers, PUBLISHED.is_moe) == (0, False)
    for key, attr in CONFIG["program"]["spec_keys"].items():
        assert getattr(PUBLISHED, attr) == CONFIG[key], key
    assert PUBLISHED.layer_types == CONFIG["layer_types"]
    assert SPEC.layer_types == TINY["layer_types"]
    assert decoder._query_scale(PUBLISHED) == 0.015625  # not 64 ** -0.5
    tree = jax.eval_shape(lambda: decoder.init_params(
        PUBLISHED, jax.random.PRNGKey(0), jnp.bfloat16))
    assert "lm_head" not in tree, "the head is the embedding"
    count = sum(x.size for x in jax.tree.leaves(tree))
    # (64 zero columns behind dt a Mamba-2 layer: whole lane groups)
    assert count == PUBLISHED.num_params + 36 * 2048 * 64
    assert tree["layers"]["mamba"]["in_proj"]["w"].shape == (
        4, 9, 2048, 4096 + 4352 + 64 + 64)
    assert tree["layers"]["attn"]["gate"]["w"].shape == (4, 1, 2048, 8192)


def test_the_state_and_not_the_pages_sets_the_batch():
    """A slot's row: 36 float32 tiles of [64, 64, 128] and 36 tails of 3
    x 4,352 bf16 = 76.4 MB; its pages at 2,048 tokens 16.8 MB (two KV
    heads of 64 a 128-lane row: 8,192 B a token)."""
    spec = PUBLISHED.pack_kv_heads()
    assert hybrid.state_bytes_per_slot(spec, 2, 32) == 76_437_504
    state = jax.eval_shape(
        lambda: hybrid.make_state(spec, 80, jnp.bfloat16, 32))
    assert state["S"].shape == (36, 80, 64, 64, 128)
    assert state["conv"].shape == (36, 80, 3, 4352)
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=16, page_size=32,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=2048, dtype_bytes=2, pools=spec.kv_pools)
    assert (geo.kv_heads, geo.head_dim) == (4, 128)
    assert geo.page_bytes == 32 * 8192
    weights = 2 * spec.num_params
    held = weights + 80 * 76_437_504 + 80 * 2048 * 8192
    assert 13.8e9 < held < 13.9e9
    assert weights + 96 * (76_437_504 + 2048 * 8192) > 0.9 * 16.9e9
    assert spec.recurrent_kind == "mamba" and spec.slot_state_layers == 36


def test_a_checkpoint_under_the_published_names_loads_into_the_tree():
    """``runtime/weights.py``: GraniteMoeHybrid tensor names -> the
    groups by first sub-block, ``shared_mlp.input_linear`` split into
    gate and up, and the forward of the loaded tree is the drawn one's."""
    from vgate_tpu.runtime.weights import params_from_getter

    drawn = decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)
    layers = drawn["layers"]
    names, seen = {}, {"mamba": 0, "attn": 0}
    put = lambda name, a: names.__setitem__(name, np.asarray(a))
    put("model.embed_tokens.weight", drawn["embed"])
    put("model.norm.weight", drawn["final_norm"])
    for i, kinds in enumerate(SPEC.stack):
        group = kinds[0]
        j = seen[group]
        seen[group] += 1
        lp = jax.tree.map(lambda a: a[0, j], layers[group])
        pre = f"model.layers.{i}."
        put(pre + "input_layernorm.weight", lp["input_norm"])
        put(pre + "post_attention_layernorm.weight", lp["post_norm"])
        put(pre + "shared_mlp.input_linear.weight", jnp.concatenate(
            [lp["gate"]["w"], lp["up"]["w"]], axis=1).T)
        put(pre + "shared_mlp.output_linear.weight", lp["down"]["w"].T)
        if group == "attn":
            for n in "qkvo":
                put(pre + f"self_attn.{n}_proj.weight", lp[n]["w"].T)
            continue
        width = SPEC.mamba_inner + SPEC.mamba_conv_dim + SPEC.mamba_num_heads
        assert not np.asarray(lp["in_proj"]["w"][:, width:]).any()
        put(pre + "mamba.in_proj.weight", lp["in_proj"]["w"][:, :width].T)
        put(pre + "mamba.out_proj.weight", lp["out"]["w"].T)
        put(pre + "mamba.conv1d.weight", lp["conv"][:, None, :])
        put(pre + "mamba.conv1d.bias", lp["conv_bias"])
        put(pre + "mamba.A_log", lp["a_log"])
        put(pre + "mamba.D", lp["d"])
        put(pre + "mamba.dt_bias", lp["dt_bias"])
        put(pre + "mamba.norm.weight", lp["ssm_norm"])
    loaded = params_from_getter(SPEC, names.__getitem__, jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(drawn)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(drawn)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_both_programs_name_their_scopes_for_this_stack(params):
    """What a device trace's op names say for this stack: the Mamba-2
    mixer under ``ssm`` (its convolution ``conv`` inside it), the SwiGLU
    under ``dense_mlp``, the attention layer's cache step, the embedding
    and the head, in the prompt program and in the decode step."""
    kp, vp, st = fresh_cache()
    one = lambda v: jnp.asarray([v])
    prompt = PREFILL.lower(
        params, SPEC, jnp.zeros((1, 16), jnp.int32), one(9), kp, vp,
        jnp.asarray([[1, 2, 3, 4]], jnp.int32), state=st, slots=one(0)
    ).as_text(debug_info=True)
    step = DECODE.lower(
        params, SPEC, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), kp, vp,
        jnp.zeros((SLOTS, 32), jnp.int32),
        active=jnp.ones((SLOTS,), bool), state=st,
    ).as_text(debug_info=True)
    import re

    def named(text, scope):  # at the head of an op's name, or inside it
        return re.search(r'["/]' + re.escape(scope) + "/", text)

    for text in (prompt, step):
        for scope in ("embed", "ssm", "ssm/conv", "dense_mlp",
                      "gated_attn", "gated_attn/attention", "logits"):
            assert named(text, scope), scope
    assert named(step, "gated_attn/kv_write")
