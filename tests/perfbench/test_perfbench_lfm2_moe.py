"""The LFM2 configuration's files: the plain reference on cases worked
out by hand, the manifest's contract with the new cell, the shapes
module (a tail a slot, a token of 2,048 B a layer), and the new reducer
and metric files on a synthetic trace."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_lfm2_moe as shapes
from perfbench.reducers import (
    moe_experts_roofline, perf_ratio, scope_share, short_conv_roofline)
from perfbench.references import lfm2_moe as ref

CELL = "lfm2-24b-a2b-e8.decode-heavy"
F32 = jnp.float32


def small_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 4,
           "moe_intermediate_size": 4, "num_experts": 2,
           "num_experts_per_tok": 1, "routed_scaling_factor": 1,
           "norm_topk_prob": True, "use_expert_bias": True,
           "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 1,
           "layer_types": ["conv", "full_attention"],
           "num_hidden_layers": 2, "norm_eps": 1e-6,
           "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}
    cfg.update(over)
    return cfg


def test_the_short_convolution_is_causal_gated_and_has_no_activation():
    """Identity projections laid out ``[B | C | X]``: with B = C = 1 the
    layer is the bare taps over X, ``c_t = w0 x_{t-2} + w1 x_{t-1} + w2
    x_t`` with zeros before row 0; a negative input stays negative (no
    SiLU); B scales the convolution's INPUT and C its result."""
    cfg = small_cfg()
    D = 4
    x = jnp.asarray([[1.0, -2.0, 0.5, 3.0], [2.0, 1.0, -1.0, 0.0],
                     [-3.0, 0.5, 2.0, 1.0], [0.5, 0.5, 0.5, 0.5]], F32)
    taps = jnp.asarray([[0.5, -1.0, 2.0]] * D, F32)
    # u = [ones | ones | x] through an in_proj that reads a wider input
    u = jnp.concatenate([jnp.ones((4, D)), jnp.ones((4, D)), x], axis=1)
    w = {"in_proj": jnp.eye(3 * D, dtype=F32), "conv": taps,
         "out_proj": jnp.eye(D, dtype=F32)}
    got = np.asarray(ref.short_conv(u, w, cfg))
    xs = np.asarray(x)
    want = 2.0 * xs
    want[1:] += -1.0 * xs[:-1]
    want[2:] += 0.5 * xs[:-2]
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert got[0, 1] == pytest.approx(-4.0)  # 2 x (-2): no activation
    # a later row moves nothing before it
    moved = u.at[3, 2 * D:].add(5.0)
    np.testing.assert_allclose(
        np.asarray(ref.short_conv(moved, w, cfg))[:3], got[:3], atol=1e-6)
    # B gates the input (it passes through the taps), C the result
    b2 = u.at[:, :D].set(2.0)
    np.testing.assert_allclose(
        np.asarray(ref.short_conv(b2, w, cfg)), 2.0 * want, atol=1e-6)
    c_row = u.at[2, D:2 * D].set(3.0)
    got_c = np.asarray(ref.short_conv(c_row, w, cfg))
    np.testing.assert_allclose(got_c[2], 3.0 * want[2], atol=1e-6)
    np.testing.assert_allclose(got_c[3], want[3], atol=1e-6)


def test_attention_is_causal_grouped_normed_then_rotated():
    """Two query heads on one KV head of 4, identity projections: the
    first row attends to itself alone; changing a LATER row moves no
    earlier output; the per-head norm comes before the rotation, so
    scaling the q weights by 10 changes nothing (the norm undoes it)."""
    cfg = small_cfg()
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((5, 4)), F32)
    eye = jnp.eye(4, dtype=F32)
    w = {"q": jnp.concatenate([eye, eye], axis=1), "k": eye, "v": eye,
         "o": jnp.concatenate([eye, eye], axis=0)}
    got = np.asarray(ref.attention(u, w, cfg))
    np.testing.assert_allclose(got[0], 2 * np.asarray(u[0]), rtol=1e-5)
    later = u.at[4].add(3.0)
    np.testing.assert_allclose(
        np.asarray(ref.attention(later, w, cfg))[:4], got[:4], atol=1e-6)
    scaled = dict(w, q=10.0 * w["q"])
    np.testing.assert_allclose(
        np.asarray(ref.attention(u, scaled, cfg)), got, atol=1e-4)
    # rotate-half by position: position 0 is the identity
    q = jnp.asarray(rng.standard_normal((2, 1, 4)), F32)
    rot = np.asarray(ref.rotary(q, 10000.0))
    np.testing.assert_allclose(rot[0], np.asarray(q[0]), atol=1e-7)
    c, s = np.cos(1.0), np.sin(1.0)
    a, b = np.asarray(q[1, 0, 0]), np.asarray(q[1, 0, 2])
    np.testing.assert_allclose(rot[1, 0, 0], a * c - b * s, rtol=1e-5)
    np.testing.assert_allclose(rot[1, 0, 2], b * c + a * s, rtol=1e-5)


def test_the_selection_bias_chooses_and_does_not_weigh():
    """Two experts, top 1.  Scores favour expert 0; a bias of +5 on
    expert 1 makes it the choice, and its weight is still its own
    sigmoid score over itself plus the published 1e-6."""
    cfg = small_cfg()
    u = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], F32)
    router = jnp.zeros((4, 2), F32).at[0, 0].set(2.0)
    picked, weight = ref.choose(
        u, {"router": router, "router_bias": jnp.zeros((2,), F32)}, cfg)
    s0 = 1 / (1 + np.exp(-2.0))
    assert picked.tolist() == [[0]]
    assert weight[0, 0] == pytest.approx(s0 / (s0 + 1e-6), rel=1e-6)
    picked, weight = ref.choose(
        u, {"router": router, "router_bias": jnp.asarray([0.0, 5.0])}, cfg)
    assert picked.tolist() == [[1]]
    assert weight[0, 0] == pytest.approx(0.5 / (0.5 + 1e-6), rel=1e-6)
    _, bare = ref.choose(
        u, {"router": router, "router_bias": jnp.asarray([0.0, 5.0])},
        dict(cfg, norm_topk_prob=False))
    assert bare[0, 0] == pytest.approx(0.5)


def test_a_held_share_drops_what_absent_experts_would_add():
    cfg = small_cfg(num_experts=4, router_width=4, num_experts_per_tok=2)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((6, 4)), F32)
    w = {"router": jnp.asarray(rng.standard_normal((4, 4)), F32),
         "router_bias": jnp.zeros((4,), F32)}
    for n in ref.EXPERTS:
        w[n] = jnp.asarray(rng.standard_normal((4, 4, 4)), F32)
    whole = np.asarray(ref.experts(u, w, cfg))
    halves = sum(
        np.asarray(ref.experts(
            u, dict(w, **{n: w[n][f:f + 2] for n in ref.EXPERTS}), cfg,
            first=f, count=2))
        for f in (0, 2))
    np.testing.assert_allclose(halves, whole, atol=1e-5)


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"]["chips"] == 1
    assert cell["entry"]["traffic"] == "decode-heavy"
    assert "eight chips' batch" in cell["entry"]["why"]
    assert cell["params"] == {"clients": 320, "resumed": 256}
    assert int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"]) == 256
    assert manifest.metric_names(bench, CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    for name in ("model.conv_mixer_share.tok", "kernel.short_conv_share.tok",
                 "kernel.short_conv_roofline.tok",
                 "moe.lfm2_load_max_over_mean.tok",
                 "model.decode_step_ms.tok", "model.decode_share.tok",
                 "model.prefill_share.tok", "kernel.decode_attn_share.tok",
                 "kernel.decode_attn_roofline.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "kernel.moe_experts_share.tok",
                 "kernel.moe_experts_roofline.tok",
                 "moe.held_assignment_share.tok", "model.dense_mlp_share.tok",
                 "device.state_gb.tok", "engine.compiles_in_window.tok",
                 "device.idle_share.tok"):
        assert name in per_layer, name
    assert not [n for n in per_layer
                if "gdn" in n or "ssd" in n or "mla" in n or "swa" in n]
    # the whole depth and every width as published; the experts cut
    assert config["reduced"] == ["num_experts"]
    assert config["published"] == {"num_experts": 64}
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts"], config["router_width"],
            config["num_experts_per_tok"], config["num_attention_heads"],
            config["num_key_value_heads"], config["vocab_size"],
            config["chips_sharing_a_layer"], config["first_expert"]) == (
                40, 2048, 11776, 1536, 8, 64, 4, 32, 8, 65536, 8, 0)
    assert len(config["layer_types"]) == 40
    assert serve.unchecked(config) == []
    assert set(config["assumed"]) >= {
        "tied_embeddings", "conv_layout", "router", "stop_ids", "weights"}
    # what the reducers and the page check read
    assert (shapes.attn_layers(config), shapes.conv_layers(config),
            shapes.moe_layers(config)) == (10, 30, 38)
    assert shapes.kv_bytes_per_token(config) == 20480
    assert shapes.conv_state_bytes_per_slot_layer(config) == 8192
    assert shapes.conv_state_bytes_per_slot(config) == 245760
    assert shapes.conv_step_bytes_per_row_layer(config) == 32768
    assert shapes.held_expert_bytes(config) == 3 * 2048 * 1536 * 2
    assert shapes.expert_flops_per_assignment(config) == 2 * 3 * 2048 * 1536
    traffic = cell["traffic"]
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 200, 1502]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith(
        "lfm2_moe")


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size) == (40, 8, 64, 65536)
        assert (spec.conv_layers, spec.attn_layers, spec.moe_layers,
                spec.linear_layers) == (30, 10, 38, 0)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 3.761e9) < 1e6
        # a file that says 8 experts cannot front a program of 64, nor
        # forty layers' kinds another forty's
        with pytest.raises(SystemExit):
            serve.check(dict(config, num_experts=64), spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, layer_types=["conv"] * 40), spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, conv_L_cache=4), spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, num_dense_layers=1), spec)
        # the program's page is what the shapes module says: no padding
        packed = spec.pack_kv_heads()
        assert 32 * shapes.kv_bytes_per_token(config) == (
            packed.kv_pools * packed.attn_layers * 32 * packed.cache_heads
            * packed.cache_head_dim * 2) == 32 * 20480
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_LFM2_MOE
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 20


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 10, "kv_bytes_per_token": 20480,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def test_conv_metrics_on_a_synthetic_trace(monkeypatch):
    """160 launches of the decode kernel = 16 steps of 10 attention
    layers; the decode spans say a step ran 200 rows: 16 x 200 x 30
    row-layers x 32,768 B = 3.15 GB, 3.84 ms at 819 GB/s; 19.2 ms under
    the ``short_conv`` scope read 20 %.  The shares read the scopes'
    self time over the device's busy second; a program without the
    scopes (the parent) reads nothing."""
    attn = "jit__decode_chunk/paged_decode_attention_pallas.4"
    ctx = trace_ctx({attn: (0.2, 160), "jit__decode_chunk/fusion.9": (0.3, 5)})
    least = 16 * 200 * 30 * 32768 / 819e9
    scopes = {"busy_s": 1.0, "scope_seconds": {
        "conv_mixer/short_conv": 5 * least, "conv_mixer": 0.08,
        "full_attn/attention": 0.2, "dense_mlp": 0.015}}
    monkeypatch.setattr(scope_share, "summary", lambda ctx: scopes)
    monkeypatch.setattr(short_conv_roofline, "scope_summary",
                        lambda ctx: scopes)
    monkeypatch.setattr(
        short_conv_roofline.trace_spans, "load",
        lambda ctx: {"decode": [{"steps": 8, "rows": 192},
                                {"steps": 8, "rows": 208},
                                {"steps": 8, "rows": None}]})
    assert short_conv_roofline.reduce(
        ctx, **metric_args("kernel.short_conv_roofline.tok")
    ) == pytest.approx(20.0, rel=1e-6)
    share = lambda name: scope_share.reduce(ctx, **metric_args(name))
    assert share("kernel.short_conv_share.tok") == pytest.approx(
        100 * 5 * least)
    assert share("model.conv_mixer_share.tok") == pytest.approx(
        100 * (5 * least + 0.08))
    assert share("model.dense_mlp_share.tok") == pytest.approx(1.5)
    # the parent: no such scope, no reading, no error
    bare = {"busy_s": 1.0, "scope_seconds": {"dense_mlp": 0.015}}
    monkeypatch.setattr(scope_share, "summary", lambda ctx: bare)
    monkeypatch.setattr(short_conv_roofline, "scope_summary",
                        lambda ctx: bare)
    assert share("kernel.short_conv_share.tok") is None
    assert short_conv_roofline.reduce(
        ctx, **metric_args("kernel.short_conv_roofline.tok")) is None
    # another configuration's shapes module counts no conv step
    other = dict(ctx, config=manifest.cell(
        "qwen3-next-80b-a3b-l8e128.decode-heavy")["config"])
    monkeypatch.setattr(short_conv_roofline, "scope_summary",
                        lambda ctx: scopes)
    assert short_conv_roofline.reduce(
        other, **metric_args("kernel.short_conv_roofline.tok")) is None


def moe_totals(n):
    # a step: 38 expert layers, 256 rows x 4 choices, an eighth held
    return {"totals": {"moe": {
        "steps": 1000 * n, "layer_steps": 38000 * n,
        "assignments": 38000 * n * 1024, "held_assignments": 38000 * n * 128,
        "experts_hit": 38000 * n * 8, "load_max_sum": 1000 * n * 28}}}


def test_expert_metrics_read_the_cells_counters():
    """Eight held experts all hit a layer-step: 8 x 18.9 MB = 151 MB,
    184 us at 819 GB/s (128 pairs x 18.9 MFLOP need 12 us: memory bounds
    it); three launches a layer of 123 us each read 50 %.  The largest
    load a step (28) over the mean a held expert a layer (128 / 8 = 16)
    reads 1.75."""
    mm = "jit__decode_chunk/moe_grouped_matmul_pallas.2"
    least = 8 * 3 * 2048 * 1536 * 2 / 819e9
    ctx = trace_ctx({mm: (2 * least * 38 * 16, 3 * 38 * 16)})
    ctx["perf"] = {"open": moe_totals(1), "close": moe_totals(3)}
    assert moe_experts_roofline.reduce(
        ctx, **metric_args("kernel.moe_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.lfm2_load_max_over_mean.tok")
    ) == pytest.approx(28 / 16, rel=1e-6)
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(12.5, rel=1e-6)
