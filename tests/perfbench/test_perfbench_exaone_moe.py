"""The K-EXAONE configuration's files: the plain reference on cases
worked out by hand, the manifest's contract with the new cells, the
shapes module (a cache of two geometries), and the new reducers and
metric files on a synthetic trace."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_exaone_moe as shapes
from perfbench import trace_scopes
from perfbench.reducers import (
    moe_experts_roofline, perf_ratio, scope_share, swa_decode_roofline,
    trace_share)
from perfbench.references import exaone_moe as ref

CELL = "k-exaone-236b-a23b-l5e16.long-prompt"
F32 = jnp.float32


def one_head_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 4,
           "moe_intermediate_size": 4, "num_experts": 2,
           "num_experts_per_tok": 1, "num_shared_experts": 1,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True,
           "sliding_window": 2, "sliding_window_pattern": "LG",
           "first_k_dense_replace": 1, "num_hidden_layers": 2,
           "rms_norm_eps": 1e-6,
           "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}
    cfg.update(over)
    return cfg


def identity_attention():
    eye = jnp.eye(4, dtype=F32)
    return {"q": eye, "k": eye, "v": eye, "o": eye}


def test_a_window_layer_sees_its_last_keys_and_a_full_layer_all():
    """One head, identity projections.  Window 2: row 3's output mixes
    rows 2 and 3 alone, so changing row 0 moves nothing; a full layer
    (window 0) sees row 0.  The first row attends to itself."""
    cfg = one_head_cfg()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 4)), F32)
    w = identity_attention()
    moved = x.at[0].set(x[0] + 3.0)
    near = np.asarray(ref.attention(x, w, cfg, 2))
    np.testing.assert_allclose(
        near[3], np.asarray(ref.attention(moved, w, cfg, 2))[3], atol=1e-6)
    full = np.asarray(ref.attention(x, w, cfg, 0))
    assert np.abs(
        full[3] - np.asarray(ref.attention(moved, w, cfg, 0))[3]).max() > 1e-3
    v0 = np.asarray(x[0])
    np.testing.assert_allclose(near[0], v0, rtol=1e-5)  # itself alone
    np.testing.assert_allclose(full[0], v0, rtol=1e-5)


def test_rotary_is_relative_and_on_window_layers_only():
    """A full layer takes no positions: permuting the order of EARLIER
    rows leaves the last row's output what it was.  A window layer
    rotates: the same permutation inside its window moves it."""
    cfg = one_head_cfg(sliding_window=4)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 4)), F32)
    w = identity_attention()
    swapped = x[jnp.asarray([1, 0, 2, 3])]
    full = lambda rows: np.asarray(ref.attention(rows, w, cfg, 0))[3]
    near = lambda rows: np.asarray(ref.attention(rows, w, cfg, 4))[3]
    np.testing.assert_allclose(full(x), full(swapped), atol=1e-6)
    assert np.abs(near(x) - near(swapped)).max() > 1e-4
    # rotate-half by position: position 0 is the identity
    q = jnp.asarray(rng.standard_normal((2, 1, 4)), F32)
    got = np.asarray(ref.rotate(q, jnp.asarray([0, 1]), 10000.0))
    np.testing.assert_allclose(got[0], np.asarray(q[0]), atol=1e-7)
    c, s = np.cos(1.0), np.sin(1.0)
    a, b = np.asarray(q[1, 0, 0]), np.asarray(q[1, 0, 2])
    np.testing.assert_allclose(got[1, 0, 0], a * c - b * s, rtol=1e-5)
    np.testing.assert_allclose(got[1, 0, 2], b * c + a * s, rtol=1e-5)


def test_the_selection_bias_chooses_and_does_not_weigh():
    """Two experts, top 1.  Scores favour expert 0; a bias of +5 on
    expert 1 makes it the choice, and its weight is still its own
    sigmoid score over itself (norm_topk_prob) x 2.5 = 2.5."""
    cfg = one_head_cfg()
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], F32)
    router = jnp.zeros((4, 2), F32).at[0, 0].set(2.0)
    idx, vals = ref.route(
        x, {"router": router, "router_bias": jnp.zeros((2,), F32)}, cfg)
    assert idx.tolist() == [[0]] and vals[0, 0] == pytest.approx(2.5)
    idx, vals = ref.route(
        x, {"router": router, "router_bias": jnp.asarray([0.0, 5.0])}, cfg)
    assert idx.tolist() == [[1]] and vals[0, 0] == pytest.approx(2.5)
    # without the norm the weight is the score itself: sigmoid(0) x 2.5
    idx, vals = ref.route(
        x, {"router": router, "router_bias": jnp.asarray([0.0, 5.0])},
        dict(cfg, norm_topk_prob=False))
    assert vals[0, 0] == pytest.approx(0.5 * 2.5)


def test_a_held_share_drops_what_absent_experts_would_add():
    cfg = one_head_cfg(num_experts=4, router_width=4,
                       num_experts_per_tok=2, num_shared_experts=0)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((6, 4)), F32)
    w = {"router": jnp.asarray(rng.standard_normal((4, 4)), F32),
         "router_bias": jnp.zeros((4,), F32)}
    for n, shape in (("gate", (4, 4, 4)), ("up", (4, 4, 4)),
                     ("down", (4, 4, 4))):
        w[n] = jnp.asarray(rng.standard_normal(shape), F32)
    whole = np.asarray(ref.moe(x, w, cfg))
    halves = sum(
        np.asarray(ref.moe(
            x, dict(w, **{n: w[n][f:f + 2] for n in ("gate", "up", "down")}),
            cfg, first=f, count=2))
        for f in (0, 2))
    np.testing.assert_allclose(halves, whole, atol=1e-5)


def test_layer_kinds_from_the_lists_or_from_the_pattern():
    cfg = manifest.cell(CELL)["config"]
    assert [ref.window_of(cfg, i) for i in range(5)] == [128, 128, 128, 0, 128]
    assert [ref.is_dense(cfg, i) for i in range(5)] == [
        True, False, False, False, False]
    tiny = cfg["rehearse"]["model"]  # no lists: the pattern's letters
    assert [ref.window_of(tiny, i) for i in range(9)] == [
        8, 8, 8, 0, 8, 8, 8, 0, 8]


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == (
        "long-prompt")
    assert cell["params"] == {"clients": 240, "resumed": 192}
    assert int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"]) == 192
    reported = manifest.metric_names(bench, CELL, "end_to_end")
    assert reported == ["out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    for name in ("kernel.swa_decode_share.tok",
                 "kernel.swa_decode_roofline.tok",
                 "kernel.swa_prefill_share.tok", "kernel.full_attn_share.tok",
                 "model.dense_mlp_share.tok",
                 "moe.l5e16_load_max_over_mean.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "kernel.moe_experts_roofline.tok",
                 "kernel.moe_experts_share.tok",
                 "kernel.prefill_attn_share.tok",
                 "moe.held_assignment_share.tok", "device.state_gb.tok",
                 "scheduler.pool_fill.tok", "scheduler.preemptions.tok"):
        assert name in per_layer, name
    assert not [n for n in per_layer if "mla" in n or "l4e32" in n]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "sliding_windows"]
    assert (config["published"]["num_hidden_layers"],
            config["published"]["num_experts"],
            config["published"]["vocab_size"]) == (48, 128, 153600)
    assert len(config["published"]["layer_types"]) == 48
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["sliding_window"],
            config["router_width"], config["chips_sharing_a_layer"]) == (
                6144, 18432, 2048, 8, 64, 8, 128, 128, 128, 8)
    assert serve.unchecked(config) == []
    # the cache's two geometries, as the reducers and the page check read
    assert (shapes.attn_layers(config), shapes.swa_layers(config),
            shapes.moe_layers(config)) == (1, 4, 4)
    assert shapes.ring_row_bytes(config) == 4096
    assert shapes.kv_bytes_per_token(config) == 4096
    assert shapes.ring_tokens(config, 32) == 160
    assert shapes.ring_bytes_per_slot(config, 32) == 2621440
    assert shapes.swa_decode_flops_per_row_read(config) == 64 * 4 * 128
    assert shapes.swa_prefill_pairs(config, 5000) == (
        128 * 129 // 2 + (5000 - 128) * 128)
    assert shapes.swa_prefill_pairs(config, 100) == 100 * 101 // 2
    assert shapes.held_expert_bytes(config) == 3 * 6144 * 2048 * 2
    assert shapes.expert_flops_per_assignment(config) == 2 * 3 * 6144 * 2048
    traffic = cell["traffic"]
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 200, 1502]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith(
        "exaone_moe")


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size) == (5, 16, 128, 19200)
        assert (spec.linear_layers, spec.swa_layers, spec.moe_layers,
                spec.attn_layers) == (0, 4, 4, 1)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 3.712e9) < 5e6
        # a file that says 16 experts cannot front a program of 128, nor
        # five layers' kinds another five's
        with pytest.raises(SystemExit):
            serve.check(dict(config, num_experts=128), spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, layer_types=["full_attention"] * 5),
                        spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, sliding_window=256), spec)
        # the program's page is what the shapes module says
        assert 32 * shapes.kv_bytes_per_token(config) == (
            spec.kv_pools * spec.attn_layers * 32 * spec.cache_heads
            * spec.cache_head_dim * 2)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_SWA_MOE
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 18


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 1, "kv_bytes_per_token": 4096,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def swa_totals(n, reads_per_step=4 * 192 * 128):
    return {"totals": {"swa": {
        "decode_steps": 1000 * n, "decode_launches": 4000 * n,
        "decode_row_reads": reads_per_step * 1000 * n}}}


def test_window_layer_metrics_on_a_synthetic_trace():
    """64 launches = 16 steps of 4 window layers; the window's counters
    say a step reads 192 slots x 128 live rows a layer: 24,576 x 4,096 B
    = 100.7 MB, 122.9 us at 819 GB/s (the operations, 24,576 x 32,768 =
    0.8 G, need 4 us: memory bounds it); launches of 245.8 us read 50 %.
    The shares tell a ring's launch from the full layer's."""
    ring = "jit__decode_chunk/swa_decode_attention_pallas.3"
    full = "jit__decode_chunk/paged_decode_attention_pallas.8"
    band = "jit__prefill_step/swa_prefill_attention_pallas.2"
    flash = "jit__prefill_step/flash_prefill_attention_pallas.6"
    least = 24576 * 4096 / 819e9
    ctx = trace_ctx({ring: (64 * 2 * least, 64), full: (0.05, 16),
                     band: (0.02, 8), flash: (0.07, 2),
                     "jit__prefill_step/fusion.1": (0.5, 9)})
    ctx["perf"] = {"open": swa_totals(1), "close": swa_totals(3)}
    args = metric_args("kernel.swa_decode_roofline.tok")
    assert swa_decode_roofline.reduce(ctx, **args) == pytest.approx(
        50.0, rel=1e-6)
    share = lambda name: trace_share.reduce(ctx, **metric_args(name))
    assert share("kernel.swa_decode_share.tok") == pytest.approx(
        100 * 64 * 2 * least)
    assert share("kernel.swa_prefill_share.tok") == pytest.approx(2.0)
    assert share("kernel.full_attn_share.tok") == pytest.approx(12.0)
    # the accepted metrics read the full layer's launches alone
    assert share("kernel.decode_attn_share.tok") == pytest.approx(5.0)
    assert share("kernel.prefill_attn_share.tok") == pytest.approx(7.0)
    # at the chip's peak bandwidth over the live rows: 100 %, not more
    ctx["trace"]["op_seconds"][ring] = 64 * least
    assert swa_decode_roofline.reduce(ctx, **args) == pytest.approx(100.0)
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert swa_decode_roofline.reduce(ctx, **args) is None
    ctx["perf"] = {"open": swa_totals(1), "close": swa_totals(3)}
    other = manifest.load_json(
        manifest.HERE, "configs", "mistral-small-4-119b-l4e32.json")
    assert swa_decode_roofline.reduce(dict(ctx, config=other), **args) is None
    assert swa_decode_roofline.reduce(dict(ctx, trace=None), **args) is None


def test_load_ratio_and_experts_roofline_read_this_configuration():
    """64 = 4 expert layers x 16 held experts."""
    totals = lambda n: {"totals": {"moe": {
        "layer_steps": 4000 * n, "experts_hit": 16 * 4000 * n,
        "held_assignments": 192 * 4 * 1000 * n, "assignments": 1536 * 4
        * 1000 * n, "load_max_sum": 30 * 1000 * n, "steps": 1000 * n}}}
    ctx = {"perf": {"open": totals(1), "close": totals(2)}}
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.l5e16_load_max_over_mean.tok")
    ) == pytest.approx(30 * 64 / (192 * 4))
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(12.5)
    # three launches a layer-step read 16 x 75.5 MB: 1.475 ms at the peak
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    full = 16 * 3 * 6144 * 2048 * 2 / 819e9
    tctx = trace_ctx({name: (120 * full / 3 * 2, 120)})
    tctx["perf"] = ctx["perf"]
    assert moe_experts_roofline.reduce(
        tctx, **metric_args("kernel.moe_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)


def test_scopes_are_read_from_the_profiles_own_hlo(tmp_path):
    """A device event carries its HLO text and nothing of the scope; the
    profile's metadata plane holds each module's HLO, whose instructions
    carry the traced name.  One module run twice, three operations: a
    ``while`` of 3 ms whose body holds a dense-layer fusion of 2 ms, and
    an attention fusion of 1 ms: SELF times by scope."""
    assert trace_scopes.scope_of(
        "jit(_decode_chunk)/jit(main)/while/body/closed_call/dense_mlp/"
        "...d,df->...f/dot_general") == "dense_mlp/...d,df->...f"
    assert trace_scopes.scope_of(
        "jit(_prefill_step)/while/body/swa_attn/qkv/dot_general"
    ) == "swa_attn/qkv"
    assert trace_scopes.scope_of("") == ""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from tensorflow.compiler.xla.service import hlo_pb2

    hlo = hlo_pb2.HloProto()
    comp = hlo.hlo_module.computations.add(name="main")
    for name, traced in (
            ("while.1", "jit(f)/jit(main)/while"),
            ("fusion.3", "jit(f)/jit(main)/while/body/dense_mlp/mul"),
            ("fusion.4", "jit(f)/jit(main)/full_attn/o_proj/dot_general")):
        comp.instructions.add(name=name).metadata.op_name = traced
    space = xplane_pb2.XSpace()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    entry = meta.event_metadata[1]
    entry.name = "jit_f(7)"
    entry.stats.add(metadata_id=1).bytes_value = hlo.SerializeToString()
    device = space.planes.add(name="/device:TPU:0")
    texts = {1: "jit_f(7)", 2: "%while.1 = (s32[]) while(%t), body=%b",
             3: "%fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop",
             4: "%fusion.4 = bf16[8]{0} fusion(%p), kind=kOutput",
             5: "%copy.9 = bf16[8]{0} copy(%p)"}
    for key, text in texts.items():
        device.event_metadata[key].name = text
    ms = 10 ** 9  # picoseconds
    modules = device.lines.add(name="XLA Modules", timestamp_ns=1000)
    ops = device.lines.add(name="XLA Ops", timestamp_ns=1000)
    for run in range(2):
        at = run * 10 * ms
        modules.events.add(metadata_id=1, offset_ps=at, duration_ps=5 * ms)
        ops.events.add(metadata_id=2, offset_ps=at, duration_ps=3 * ms)
        ops.events.add(metadata_id=3, offset_ps=at + ms // 2,
                       duration_ps=2 * ms)
        ops.events.add(metadata_id=4, offset_ps=at + 3 * ms, duration_ps=ms)
    # an operation outside every module, which the HLO does not hold
    ops.events.add(metadata_id=5, offset_ps=7 * ms, duration_ps=ms)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    got = trace_scopes.summarize(str(path))
    assert got["busy_s"] == pytest.approx(9e-3)
    assert got["scope_seconds"] == {
        "": pytest.approx(3e-3), "dense_mlp": pytest.approx(4e-3),
        "full_attn/o_proj": pytest.approx(2e-3)}


def test_scope_share_reads_a_summary(tmp_path, monkeypatch):
    data = {"busy_s": 2.0, "scope_seconds": {
        "dense_mlp/...d,df->...f": 0.05, "dense_mlp": 0.01,
        "swa_attn/qkv": 0.3, "": 1.0}}
    monkeypatch.setattr(scope_share, "summary", lambda ctx: data)
    assert scope_share.reduce({}, scopes=["dense_mlp"]) == pytest.approx(3.0)
    assert scope_share.reduce({}, scopes=["swa_attn"]) == pytest.approx(15.0)
    assert scope_share.reduce({}, scopes=["mla_attn"]) is None
    monkeypatch.setattr(scope_share, "summary", lambda ctx: None)
    assert scope_share.reduce({}, scopes=["dense_mlp"]) is None
    # no trace: nothing is read, nothing is run
    monkeypatch.undo()
    assert scope_share.reduce({"trace": None}, scopes=["dense_mlp"]) is None
