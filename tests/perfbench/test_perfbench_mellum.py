"""The Mellum2 configuration's files: the plain reference on cases worked
out by hand (a rotary a layer kind, YaRN's amplitude, the window, the
softmax router), the manifest's contract with the new cell, the shapes
module's arithmetic, and the accepted reducers on a synthetic trace of
this configuration's launches."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_mellum as shapes
from perfbench.reducers import (
    moe_experts_roofline, perf_ratio, swa_decode_roofline, trace_share)
from perfbench.references import mellum as ref

CELL = "mellum2-12b-a2.5b-l8.long-agent"
F32 = jnp.float32
YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000}


def one_head_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 4,
           "moe_intermediate_size": 4, "num_experts": 4,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "sliding_window": 2, "num_hidden_layers": 2,
           "layer_types": ["sliding_attention", "full_attention"],
           "mlp_layer_types": ["sparse", "sparse"], "rms_norm_eps": 1e-6,
           "rope_parameters": {"sliding_attention": PLAIN,
                               "full_attention": YARN}}
    cfg.update(over)
    return cfg


def identity_attention():
    eye = jnp.eye(4, dtype=F32)
    return {"q": eye, "k": eye, "v": eye, "o": eye}


def test_a_window_layer_sees_its_last_keys_and_a_full_layer_all():
    """One head, identity projections.  Window 2 (layer 0): row 3's
    output mixes rows 2 and 3 alone, so changing row 0 moves nothing; the
    full layer (layer 1) sees row 0.  The first row attends to itself."""
    cfg = one_head_cfg()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 4)), F32)
    w = identity_attention()
    moved = x.at[0].set(x[0] + 3.0)
    near = np.asarray(ref.attention(x, w, cfg, 0))
    np.testing.assert_allclose(
        near[3], np.asarray(ref.attention(moved, w, cfg, 0))[3], atol=1e-6)
    full = np.asarray(ref.attention(x, w, cfg, 1))
    assert np.abs(
        full[3] - np.asarray(ref.attention(moved, w, cfg, 1))[3]).max() > 1e-3
    # a row alone: its own V (no norm on v), whatever the kind
    np.testing.assert_allclose(near[0], np.asarray(x[0]), rtol=1e-5)
    np.testing.assert_allclose(full[0], np.asarray(x[0]), rtol=1e-5)


def test_each_kind_rotates_by_its_own_group_and_yarn_lengthens():
    """Position 0 is the identity times the amplitude; position 1 turns
    dimension pair (0, 2) by f_0 and (1, 3) by f_1 of the GROUP: plain
    frequencies for the default type, YaRN's blend (ramp 0 .. 1 over the
    two frequencies at these numbers) times 1.5 for the yarn type."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 1, 4)), F32)
    pos = jnp.asarray([0, 1])
    plain = np.asarray(ref.rotate(q, pos, PLAIN))
    np.testing.assert_allclose(plain[0], np.asarray(q[0]), atol=1e-7)
    f1 = 10000.0 ** -0.5
    a, b = float(q[1, 0, 1]), float(q[1, 0, 3])
    np.testing.assert_allclose(
        plain[1, 0, 1], a * math.cos(f1) - b * math.sin(f1), rtol=1e-5)
    yarn = np.asarray(ref.rotate(q, pos, YARN))
    np.testing.assert_allclose(yarn[0], 1.5 * np.asarray(q[0]), rtol=1e-6)
    low, high = ref.yarn_ramp(YARN, 4)
    assert (low, high) == (0, 1)  # c(32) < 0 -> 0; c(1) = 0.35 -> 1
    g1 = f1 / 4  # the second frequency lies past the ramp: interpolated
    np.testing.assert_allclose(
        yarn[1, 0, 1], 1.5 * (a * math.cos(g1) - b * math.sin(g1)),
        rtol=1e-5)
    # the first keeps its frequency (ramp 0): 1 rad a position
    a, b = float(q[1, 0, 0]), float(q[1, 0, 2])
    np.testing.assert_allclose(
        yarn[1, 0, 0], 1.5 * (a * math.cos(1.0) - b * math.sin(1.0)),
        rtol=1e-5)
    # without a stated attention_factor: 0.1 ln(factor) + 1
    bare = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert ref.rotary_of(bare, 4)[1] == pytest.approx(0.1 * math.log(4) + 1)
    with pytest.raises(ValueError):
        ref.rotary_of(dict(PLAIN, rope_type="llama3"), 4)


def test_the_amplitude_sharpens_a_full_layers_softmax_by_its_square():
    """Identity projections, every row at position 0's angle apart from
    rotation: scores under YaRN are 1.5^2 those without the amplitude,
    so the layer's output differs, and equals the plain softmax of the
    scaled scores (two rows, worked by hand)."""
    cfg = one_head_cfg(sliding_window=8)
    cfg["rope_parameters"] = {
        "sliding_attention": PLAIN,
        "full_attention": dict(YARN, factor=1, attention_factor=1.5)}
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 2.0]], F32)
    w = identity_attention()
    out = np.asarray(ref.attention(x, w, cfg, 1))
    # q and k are the normed rows (norm = 2 each: unit-rms rows), rotated
    # and x 1.5; row 1 sees row 0 and itself
    qn = np.asarray(ref.norm(x.reshape(2, 1, 4), 1e-6))[:, 0]
    r = np.asarray(ref.rotate(jnp.asarray(qn)[:, None], jnp.arange(2),
                              cfg["rope_parameters"]["full_attention"]))[:, 0]
    s = np.array([r[1] @ r[0], r[1] @ r[1]]) * 4 ** -0.5
    p = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
    np.testing.assert_allclose(out[1], p[0] * x[0] + p[1] * x[1], rtol=1e-5)
    assert s[1] == pytest.approx(1.5 ** 2 * 4 * 4 ** -0.5, rel=1e-5)


def test_the_router_takes_the_largest_probabilities_renormalised():
    cfg = one_head_cfg()
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], F32)
    router = jnp.zeros((4, 4), F32).at[0].set(
        jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    idx, vals = ref.route(x, {"router": router}, cfg)
    assert sorted(idx[0].tolist()) == [0, 1]
    e = np.exp([2.0, 1.0])
    np.testing.assert_allclose(sorted(vals[0]), sorted(e / e.sum()),
                               rtol=1e-6)
    # without the norm: the probabilities themselves
    idx, vals = ref.route(x, {"router": router},
                          dict(cfg, norm_topk_prob=False))
    all4 = np.exp([2.0, 1.0, 0.0, -1.0])
    np.testing.assert_allclose(sorted(vals[0]), sorted(e / all4.sum()),
                               rtol=1e-6)
    # the layer: the two experts' outputs under those weights
    rng = np.random.default_rng(2)
    w = {"router": router}
    for n in ("gate", "up", "down"):
        w[n] = jnp.asarray(rng.standard_normal((4, 4, 4)), F32)
    want = sum(
        wt * ref.swiglu(x, w["gate"][e_], w["up"][e_], w["down"][e_])
        for e_, wt in zip((0, 1), e / e.sum()))
    np.testing.assert_allclose(np.asarray(ref.moe(x, w, cfg)),
                               np.asarray(want), rtol=1e-5)


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"] == dict(
        cell["entry"], chips=1, traffic="long-agent",
        config="mellum2-12b-a2.5b-l8")
    assert cell["params"] == {"clients": 100, "resumed": 80}
    env = config["server"]["env"]
    assert int(env["VGT_TPU__MAX_BATCH_SLOTS"]) == 80
    assert set(env) == set(config["server"]["why"])
    assert manifest.metric_names(bench, CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    exaone = manifest.metric_names(
        bench, "k-exaone-236b-a23b-l5e16.long-prompt", "per_layer")
    # K-EXAONE's cell's, less the two that read what this stack lacks
    assert per_layer == [n for n in exaone if n not in (
        "model.dense_mlp_share.tok", "moe.l5e16_load_max_over_mean.tok")]
    for name in ("kernel.swa_decode_share.tok",
                 "kernel.swa_decode_roofline.tok",
                 "kernel.swa_prefill_share.tok", "kernel.full_attn_share.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "kernel.moe_experts_roofline.tok",
                 "moe.held_assignment_share.tok", "device.state_gb.tok",
                 "model.window_decode_step_ms.tok",
                 "device.window_idle_share.tok"):
        assert name in per_layer, name
    assert len(bench["per_layer"]) == 128  # the list is full: none added
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert manifest.cut_problems(config) == []
    assert config["published"]["num_hidden_layers"] == 28
    assert len(config["published"]["layer_types"]) == 28
    assert config["layer_types"] == config["published"]["layer_types"][:8]
    assert config["chips_sharing_a_layer"] == 1
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["vocab_size"]) == (
                2304, 7168, 896, 64, 8, 32, 4, 128, 1024, 98304)
    # every number of the catalog row's config stands under its own key
    assert config["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert serve.unchecked(config) == []
    for key in ("norm_placement", "qk_norm", "rotary", "mtp", "stop_ids",
                "weights"):
        assert config["assumed"][key], key
    traffic = cell["traffic"]
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(env["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [200, 1100, 4222]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith("mellum")


def test_the_shapes_modules_arithmetic_is_the_issues():
    config = manifest.cell(CELL)["config"]
    assert (shapes.attn_layers(config), shapes.swa_layers(config),
            shapes.moe_layers(config)) == (2, 6, 8)
    assert shapes.ring_row_bytes(config) == 2048
    assert shapes.kv_bytes_per_token(config) == 4096
    assert shapes.ring_tokens(config, 32) == 1056
    assert shapes.ring_bytes_per_slot(config, 32) == 12976128
    assert 80 * shapes.ring_bytes_per_slot(config, 32) == 1038090240
    # the pool at its cap: 80 x 512 + 1 pages of 32 tokens
    assert 40961 * 32 * shapes.kv_bytes_per_token(config) == 5368840192
    # one pool for all eight layers would hold a token in 16,384 B
    assert 8 * shapes.ring_row_bytes(config) == 16384
    assert shapes.swa_decode_flops_per_row_read(config) == 32 * 4 * 128
    assert shapes.swa_prefill_pairs(config, 12000) == (
        1024 * 1025 // 2 + (12000 - 1024) * 1024)
    assert shapes.swa_prefill_pairs(config, 200) == 200 * 201 // 2
    assert shapes.held_expert_bytes(config) == 3 * 2304 * 896 * 2
    assert shapes.held_expert_bytes_per_layer(config) == 792723456
    assert shapes.expert_flops_per_assignment(config) == 2 * 3 * 2304 * 896
    assert shapes.expert_launches_per_layer(config) == 3
    assert shapes.params(config) == 3794968832
    assert shapes.params(dict(
        config, num_hidden_layers=28)) == 12149923072
    tiny = config["rehearse"]["model"]
    assert (shapes.attn_layers(tiny), shapes.swa_layers(tiny)) == (2, 6)


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_experts,
                spec.vocab_size) == (8, 64, 64, 98304)
        assert (spec.linear_layers, spec.swa_layers, spec.moe_layers,
                spec.attn_layers, spec.lead_layers) == (0, 6, 8, 2, 0)
        assert hash(spec) is not None  # a static jit argument
        assert spec.num_params == shapes.params(config)
        # a file that says YaRN on the full layers cannot front a program
        # that rotates them plainly, nor another window or depth's kinds
        plain = dict(config["rope_parameters"],
                     full_attention=config["rope_parameters"][
                         "sliding_attention"])
        for wrong in (dict(rope_parameters=plain),
                      dict(sliding_window=128),
                      dict(num_experts=128),
                      dict(layer_types=["full_attention"] * 8)):
            with pytest.raises(SystemExit):
                serve.check(dict(config, **wrong), spec)
        # the program's page is what the shapes module says
        assert 32 * shapes.kv_bytes_per_token(config) == (
            spec.kv_pools * spec.attn_layers * 32 * spec.cache_heads
            * spec.cache_head_dim * 2)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_MELLUM
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 16


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 2, "kv_bytes_per_token": 4096,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def test_the_accepted_reducers_read_this_configurations_launches():
    """96 ring launches = 16 steps of 6 window layers; the counters say
    a step reads 80 slots x 1,024 live rows a layer: 81,920 x 2,048 B =
    167.8 MB, 204.8 us at 819 GB/s (the operations, 81,920 x 16,384 =
    1.3 G, need 7 us: memory bounds it); launches of twice that read
    50 %.  The expert product: 64 experts hit in each of 8 layers, three
    launches a layer."""
    ring = "jit__decode_chunk/swa_decode_attention_pallas.3"
    full = "jit__decode_chunk/paged_decode_attention_pallas.8"
    band = "jit__prefill_step/swa_prefill_attention_pallas.2"
    flash = "jit__prefill_step/flash_prefill_attention_pallas.6"
    least = 81920 * 2048 / 819e9
    ctx = trace_ctx({ring: (96 * 2 * least, 96), full: (0.05, 32),
                     band: (0.02, 12), flash: (0.07, 4),
                     "jit__prefill_step/fusion.1": (0.5, 9)})
    swa = lambda n: {"totals": {"swa": {
        "decode_steps": 1000 * n, "decode_launches": 6000 * n,
        "decode_row_reads": 6 * 81920 * 1000 * n}}}
    ctx["perf"] = {"open": swa(1), "close": swa(3)}
    args = metric_args("kernel.swa_decode_roofline.tok")
    assert swa_decode_roofline.reduce(ctx, **args) == pytest.approx(
        50.0, rel=1e-6)
    share = lambda name: trace_share.reduce(ctx, **metric_args(name))
    assert share("kernel.swa_prefill_share.tok") == pytest.approx(2.0)
    assert share("kernel.full_attn_share.tok") == pytest.approx(12.0)
    assert share("kernel.decode_attn_share.tok") == pytest.approx(5.0)
    assert share("kernel.prefill_attn_share.tok") == pytest.approx(7.0)
    moe = lambda n: {"totals": {"moe": {
        "layer_steps": 8000 * n, "experts_hit": 64 * 8000 * n,
        "held_assignments": 640 * 8 * 1000 * n,
        "assignments": 640 * 8 * 1000 * n}}}
    perf = {"open": moe(1), "close": moe(2)}
    assert perf_ratio.reduce(
        {"perf": perf}, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(100.0)
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    layer = 64 * 3 * 2304 * 896 * 2 / 819e9  # 0.968 ms a layer-step
    tctx = trace_ctx({name: (240 * layer / 3 * 2, 240)})
    tctx["perf"] = perf
    assert moe_experts_roofline.reduce(
        tctx, **metric_args("kernel.moe_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)
