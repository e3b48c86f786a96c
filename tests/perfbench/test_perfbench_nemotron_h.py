"""The Nemotron-H configuration's files: the plain reference on cases
worked out by hand, the manifest's contract with the new cell, the
shapes module, and the new reducer and metric files on a synthetic
trace."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_nemotron_h as shapes
from perfbench.reducers import (
    gdn_step_roofline, latent_experts_roofline, perf_ratio, trace_share)
from perfbench.references import nemotron_h as ref

CELL = "nemotron-3-super-120b-a12b-l11e128.decode-heavy"
F32 = jnp.float32


def test_a_step_of_zero_leaves_the_state():
    S0 = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4))
    x = jnp.ones((3, 2, 3))
    B = C = jnp.ones((3, 1, 4))
    y, S1 = ref.ssm_scan(x, jnp.zeros((3, 2)), -jnp.ones((2,)), B, C,
                         jnp.zeros((2,)), S0)
    np.testing.assert_array_equal(S1, S0)
    # and what is read out is the state's own: S0 C
    np.testing.assert_allclose(y[0], S0.sum(-1), rtol=1e-6)


def test_without_decay_the_state_accumulates_x_b_and_reads_it_through_c():
    """One head, A -> 0, a step of 1: S = sum_t x_t B_t^T.  With
    orthonormal B, C_t = B_j reads x_j back (for j <= t)."""
    x = jnp.asarray([[[1., 2., 3.]], [[4., 5., 6.]], [[-1., 0., 1.]]])
    B = jnp.eye(4, dtype=F32)[:3][:, None, :]  # [S=3, G=1, N=4]
    ones = jnp.ones((3, 1))
    y, S1 = ref.ssm_scan(x, ones, jnp.full((1,), -1e-9), B, B,
                         jnp.zeros((1,)))
    np.testing.assert_allclose(y, x, atol=1e-6)  # each reads its own
    np.testing.assert_allclose(
        S1[0], jnp.einsum("tp,tn->pn", x[:, 0], B[:, 0]), atol=1e-6)
    C = jnp.broadcast_to(B[0], (3, 1, 4))  # every token asks for x_0
    y0, _ = ref.ssm_scan(x, ones, jnp.full((1,), -1e-9), B, C,
                         jnp.zeros((1,)))
    np.testing.assert_allclose(y0, jnp.broadcast_to(x[0], (3, 1, 3)),
                               atol=1e-6)
    # a decay of one half a step halves what was stored two steps ago,
    # and the skip adds D x
    y2, S2 = ref.ssm_scan(x, ones, jnp.full((1,), jnp.log(0.5)), B, C,
                          jnp.full((1,), 2.0))
    np.testing.assert_allclose(S2[0, :, 0], 0.25 * x[0, 0], atol=1e-6)
    np.testing.assert_allclose(y2[2, 0], 0.25 * x[0, 0] + 2.0 * x[2, 0],
                               atol=1e-6)


def test_heads_read_the_b_and_c_of_their_group():
    """Four heads in two groups: heads 0, 1 read group 0 and heads 2, 3
    group 1 (``h // (heads / groups)``)."""
    x = jnp.ones((1, 4, 2))
    B = jnp.asarray([[[1., 0.], [0., 1.]]])  # [S=1, G=2, N=2]
    _, S1 = ref.ssm_scan(x, jnp.ones((1, 4)), -jnp.ones((4,)) * 1e-9, B, B,
                         jnp.zeros((4,)))
    np.testing.assert_allclose(S1[:2, 0], [[1., 0.], [1., 0.]], atol=1e-6)
    np.testing.assert_allclose(S1[2:, 0], [[0., 1.], [0., 1.]], atol=1e-6)


def test_the_gate_is_applied_before_the_grouped_norm():
    y = jnp.asarray([[1., 2., 3., 4.]])
    z = jnp.asarray([[0.5, -1., 2., 0.]])
    got = ref.gated_group_norm(y, z, None, groups=2, eps=0.0)
    gated = y * jax.nn.silu(z)
    want = jnp.concatenate([
        gated[:, :2] / jnp.sqrt(jnp.mean(gated[:, :2] ** 2)),
        gated[:, 2:] / jnp.sqrt(jnp.mean(gated[:, 2:] ** 2))], -1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # each group has unit mean square: the norm came LAST
    np.testing.assert_allclose(
        jnp.mean(got.reshape(2, 2) ** 2, -1), [1., 1.], rtol=1e-5)
    # the other order gives something else
    other = ref.norm(y.reshape(1, 2, 2), 0.0).reshape(1, 4) * jax.nn.silu(z)
    assert float(jnp.abs(other - got).max()) > 0.1


ROUTE_CFG = {"num_experts_per_tok": 2, "routed_scaling_factor": 5.0,
             "norm_topk_prob": True}


def test_a_bias_changes_the_choice_and_leaves_the_chosen_weights_alone():
    """Scores 0.9, 0.8, 0.2, 0.1: the top 2 are experts 0 and 1 at 5 x
    0.9 / 1.7 and 5 x 0.8 / 1.7.  A bias of +1 on expert 3 puts it in
    place of expert 1 -- at ITS score 0.1, not 1.1."""
    logit = lambda p: np.log(p / (1 - p))
    w = {"router": jnp.asarray([[logit(0.9), logit(0.8), logit(0.2),
                                 logit(0.1)]], F32),
         "router_bias": jnp.zeros((4,))}
    x = jnp.ones((1, 1))
    idx, vals = ref.route(x, w, ROUTE_CFG)
    assert sorted(idx[0]) == [0, 1]
    np.testing.assert_allclose(sorted(vals[0]),
                               [5 * 0.8 / 1.7, 5 * 0.9 / 1.7], rtol=1e-5)
    w["router_bias"] = jnp.asarray([0., 0., 0., 1.])
    idx, vals = ref.route(x, w, ROUTE_CFG)
    assert sorted(idx[0]) == [0, 3]
    by = dict(zip(idx[0].tolist(), vals[0].tolist()))
    assert by[0] == pytest.approx(5 * 0.9 / 1.0, rel=1e-5)
    assert by[3] == pytest.approx(5 * 0.1 / 1.0, rel=1e-5)


def test_a_token_whose_experts_are_all_absent_gets_the_shared_expert_only():
    cfg = dict(manifest.cell(CELL)["config"]["rehearse"]["model"],
               n_routed_experts=2, router_width=8, first_expert=6)
    z = ref.sizes(cfg)
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    w = {name: jax.random.normal(k, shape) * 0.3 for k, (name, shape)
         in zip(ks, ref.layer_shapes(z, "moe").items())}
    # the bias lifts experts 0..2 over all: none of the held 6, 7 chosen
    w["router_bias"] = jnp.zeros((8,)).at[:3].set(10.0)
    x = jax.random.normal(ks[7], (5, z["D"]))
    shared = ref.relu2(x @ w["shared_up"]) @ w["shared_down"]
    np.testing.assert_allclose(ref.moe(x, w, cfg), shared, atol=1e-6)
    here = dict(cfg, first_expert=0)
    assert float(jnp.abs(ref.moe(x, w, here) - shared).max()) > 1e-3


def test_the_files_keep_the_manifests_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config = cell["config"]
    assert cell["entry"] == {
        "name": CELL, "config": "nemotron-3-super-120b-a12b-l11e128",
        "traffic": "decode-heavy", "chips": 1, "why": cell["entry"]["why"]}
    assert cell["params"] == {"clients": 240, "resumed": 192}
    assert manifest.metric_names(cell["bench"], CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    reported = manifest.metric_names(cell["bench"], CELL, "per_layer")
    for name in ("kernel.ssd_step_share.tok", "kernel.ssd_step_roofline.tok",
                 "kernel.latent_experts_roofline.tok",
                 "moe.latent_load_max_over_mean.tok",
                 "kernel.moe_experts_share.tok",
                 "moe.held_assignment_share.tok", "device.state_gb.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "model.decode_step_ms.tok", "engine.boot_weights_s.setup"):
        assert name in reported
    for name in ("kernel.gdn_step_share.tok", "kernel.gdn_step_roofline.tok",
                 "kernel.moe_experts_roofline.tok",
                 "moe.load_max_over_mean.tok"):
        assert name not in reported  # the other hybrid's own
    # every width is the catalog's; the four cuts are a chip's share
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    published = config["published"]["hybrid_override_pattern"]
    assert len(published) == 88
    assert published[26:37] == config["hybrid_override_pattern"]
    assert (config["hidden_size"], config["head_dim"],
            config["moe_intermediate_size"], config["moe_latent_size"],
            config["num_experts_per_tok"], config["mamba_head_dim"],
            config["ssm_state_size"], config["router_width"]) == (
                4096, 128, 2688, 1024, 22, 64, 128, 512)
    assert serve.unchecked(config) == []
    # the shapes the reducers and the page check read
    assert shapes.kv_bytes_per_token(config) == 1024
    assert (shapes.attn_layers(config), shapes.linear_layers(config),
            shapes.moe_layers(config)) == (1, 5, 5)
    assert shapes.state_bytes_per_slot_layer(config) == 4194304
    assert shapes.held_expert_bytes(config) == 11010048
    assert shapes.expert_launches_per_layer(config) == 2
    assert shapes.expert_flops_per_assignment(config) == 4 * 1024 * 2688


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size, spec.layer_pattern) == (
                    11, 128, 512, 32768, "EMEMEMEMEM*")
        assert (spec.linear_layers, spec.moe_layers, spec.attn_layers) == (
            5, 5, 1)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 4.648e9) < 5e6
        # a file that says 128 experts cannot front a program of 512
        with pytest.raises(SystemExit):
            serve.check(dict(config, n_routed_experts=512), spec)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_NEMOTRON_H
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key


def trace_ctx(tmp_path, names):
    config = manifest.cell(CELL)["config"]
    spans = {"engine_thread": True, "decode": [
        {"steps": 8, "ctx_tokens": 9000, "rows": 190, "lead": 0},
        {"steps": 8, "ctx_tokens": 9000, "rows": 190, "lead": 8}]}
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace_spans.json").write_text(json.dumps(spans))
    return {
        "config": config, "profile": {"trace_dir": str(tmp_path / "trace")},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def test_ssd_step_metrics_on_a_synthetic_trace(tmp_path):
    """80 launches = 16 steps of 5 Mamba-2 layers; 190 rows a step move
    190 x 2 x 4 MiB a launch = 1.594 GB, 1.946 ms at 819 GB/s; launches
    of 3.892 ms read 50 %, and their 0.311 s are 31.1 % of a busy
    second."""
    name = "jit__decode_chunk/ssd_step_pallas.3"
    ctx = trace_ctx(tmp_path, {name: (80 * 3.892e-3, 80),
                               "jit__decode_chunk/fusion.9": (0.1, 16)})
    got = gdn_step_roofline.reduce(
        ctx, **metric_args("kernel.ssd_step_roofline.tok"))
    assert got == pytest.approx(50.0, rel=0.01)
    share = trace_share.reduce(ctx, **metric_args("kernel.ssd_step_share.tok"))
    assert share == pytest.approx(31.1, rel=0.01)
    # the other hybrid's kernel is not this one's, and the reverse
    assert gdn_step_roofline.reduce(
        ctx, **metric_args("kernel.gdn_step_roofline.tok")) is None
    assert gdn_step_roofline.reduce(dict(ctx, trace=None), pattern=".") is None


def test_latent_experts_roofline_on_a_synthetic_trace(tmp_path):
    """160 launches = 80 layer-steps of two products; the window's
    counters say 120 held experts hit and 1,056 held pairs a layer-step:
    120 x 11.01 MB = 1.321 GB, 1.613 ms at 819 GB/s (the operations,
    1,056 x 11 MFLOP, need 0.06 ms: memory bounds it); 2 launches of
    1.613 ms read 50 %."""
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    ctx = trace_ctx(tmp_path, {name: (160 * 1.6132e-3, 160)})
    totals = lambda n, hit=120: {"totals": {"moe": {
        "layer_steps": 1000 * n, "experts_hit": hit * 1000 * n,
        "held_assignments": 1056000 * n, "assignments": 4224000 * n,
        "load_max_sum": 4000 * n}}}
    ctx["perf"] = {"open": totals(1), "close": totals(3)}
    args = metric_args("kernel.latent_experts_roofline.tok")
    got = latent_experts_roofline.reduce(ctx, **args)
    assert got == pytest.approx(50.0, rel=0.01)
    # every held expert hit, the products at the chip's peak bandwidth:
    # the share reads 100 % and cannot pass it, however many pairs fell
    # on them (operations stay under the bytes' time up to 16 x these)
    full = 128 * 11010048 / 819e9 / 2  # a launch: half a layer's bytes
    ctx["trace"]["op_seconds"][name] = 160 * full
    ctx["perf"] = {"open": totals(1, 128), "close": totals(3, 128)}
    assert latent_experts_roofline.reduce(ctx, **args) == pytest.approx(
        100.0, rel=1e-6)
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert latent_experts_roofline.reduce(ctx, **args) is None
    # a configuration whose shapes say nothing of launches: nothing
    other = manifest.load_json(
        manifest.HERE, "configs", "qwen3-next-80b-a3b-l8e128.json")
    ctx["perf"] = {"open": totals(1), "close": totals(3)}
    assert latent_experts_roofline.reduce(
        dict(ctx, config=other), **args) is None


def test_latent_load_ratio_is_the_largest_load_over_the_mean():
    """Per layer-step 1,056 held pairs over 128 experts are 8.25 a held
    expert; a step's largest load of 33 over its five layers reads 4 x
    the mean: load_max_sum counts one maximum a STEP, held_assignments
    add over its 5 layers, so the scale is 5 x 128."""
    totals = lambda n: {"totals": {"moe": {
        "held_assignments": 5 * 1056 * 100 * n, "load_max_sum": 33 * 100 * n}}}
    ctx = {"perf": {"open": totals(1), "close": totals(2)}}
    got = perf_ratio.reduce(
        ctx, **metric_args("moe.latent_load_max_over_mean.tok"))
    assert got == pytest.approx(4.0, rel=1e-6)
