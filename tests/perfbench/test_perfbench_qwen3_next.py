"""The Qwen3-Next configuration: its plain reference on cases checked by
hand, its files against the manifest's contract, and its two roofline
reducers on a synthetic trace.  (The cell's CPU rehearsal is
``tests/test_zz_hybrid_rehearsal.py``: it starts a server, and runs at
the suite's end so as not to starve the dense cells' rehearsals, which
assert that nothing compiles in their three-second windows.)"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_qwen3_next as shapes
from perfbench.reducers import gdn_step_roofline, moe_experts_roofline
from perfbench.references import qwen3_next as ref

CELL = "qwen3-next-80b-a3b-l8e128.decode-heavy"
F32 = jnp.float32


def unit(rows, dim):
    """``rows`` orthonormal vectors of ``dim``."""
    return jnp.eye(dim, dtype=F32)[:rows]


def test_beta_zero_leaves_the_state():
    S0 = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4))
    k = unit(3, 4)[:, None, :].repeat(2, 1)  # [S, Hv, dk]
    v = jnp.ones((3, 2, 4))
    zeros = jnp.zeros((3, 2))
    _, S1 = ref.gated_delta(k, k, v, zeros, zeros, S0)
    np.testing.assert_array_equal(S1, S0)


def test_orthonormal_keys_store_the_values_and_return_them():
    """g = 0, beta = 1: S = sum k_t v_t^T, so a later query k_j reads v_j
    back exactly, and the first token reads its own value."""
    k = unit(3, 4)[:, None, :]  # [S=3, Hv=1, dk=4]
    v = jnp.asarray([[[1., 2., 3., 4.]], [[5., 6., 7., 8.]],
                     [[-1., 0., 1., 0.]]])
    zeros, ones = jnp.zeros((3, 1)), jnp.ones((3, 1))
    o, S1 = ref.gated_delta(k, k, v, zeros, ones)
    np.testing.assert_allclose(o, v, atol=1e-6)
    np.testing.assert_allclose(S1[0], jnp.einsum("tk,tv->kv", k[:, 0],
                                                 v[:, 0]), atol=1e-6)
    # a decay of one half a step halves what was stored two steps ago
    g = jnp.full((3, 1), jnp.log(0.5))
    _, S2 = ref.gated_delta(k, k, v, g, ones)
    np.testing.assert_allclose(S2[0, 0], 0.25 * v[0, 0], atol=1e-6)


def test_partial_rotary_leaves_the_other_dimensions_alone():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 16))
    y = ref.partial_rope(x, 10000.0, 4)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)  # position 0
    assert float(jnp.abs(y[1:, :, :4] - x[1:, :, :4]).max()) > 1e-3
    np.testing.assert_allclose(  # a rotation keeps the length
        jnp.linalg.norm(y[..., :4], axis=-1),
        jnp.linalg.norm(x[..., :4], axis=-1), rtol=1e-5)


def test_a_token_whose_experts_are_all_absent_gets_the_shared_expert_only():
    cfg = {"hidden_size": 4, "num_hidden_layers": 4,
           "full_attention_interval": 4, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 8,
           "linear_num_key_heads": 1, "linear_num_value_heads": 1,
           "linear_key_head_dim": 4, "linear_value_head_dim": 4,
           "linear_conv_kernel_dim": 4, "num_experts": 2, "router_width": 8,
           "first_expert": 6, "num_experts_per_tok": 2,
           "moe_intermediate_size": 3, "shared_expert_intermediate_size": 3}
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    w = {
        # the router loves expert d for a token that is e_d
        "router": 10.0 * jnp.eye(4, 8, dtype=F32),
        "gate": jax.random.normal(ks[0], (2, 4, 3)),
        "up": jax.random.normal(ks[1], (2, 4, 3)),
        "down": jax.random.normal(ks[2], (2, 3, 4)),
        "shared_gate": jax.random.normal(ks[3], (4, 3)),
        "shared_up": jax.random.normal(ks[4], (4, 3)),
        "shared_down": jax.random.normal(ks[5], (3, 4)),
        "shared_router": jax.random.normal(ks[6], (4,)),
    }
    # token e_0 chooses expert 0 and one of 1..7 at a tie; force the tie
    # away from the held 6, 7 by making them the least loved
    w["router"] = w["router"].at[:, 6:].add(-5.0)
    x = jnp.eye(4, dtype=F32)[:1] * 2.0
    shared = (jax.nn.sigmoid(x @ w["shared_router"])[:, None]
              * ref.expert(x, w["shared_gate"], w["shared_up"],
                           w["shared_down"]))
    np.testing.assert_allclose(ref.moe(x, w, cfg), shared, atol=1e-6)
    # held here, the same token gets its expert's part as well
    here = dict(cfg, first_expert=0)
    assert float(jnp.abs(ref.moe(x, w, here) - shared).max()) > 1e-3


def test_the_files_keep_the_manifests_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config = cell["config"]
    assert cell["entry"] == {
        "name": CELL, "config": "qwen3-next-80b-a3b-l8e128",
        "traffic": "decode-heavy", "chips": 1, "why": cell["entry"]["why"]}
    assert cell["params"] == {"clients": 320, "resumed": 256}
    assert manifest.metric_names(cell["bench"], CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    reported = manifest.metric_names(cell["bench"], CELL, "per_layer")
    for name in ("kernel.gdn_step_share.tok", "kernel.gdn_step_roofline.tok",
                 "kernel.moe_experts_share.tok",
                 "kernel.moe_experts_roofline.tok",
                 "moe.held_assignment_share.tok",
                 "moe.load_max_over_mean.tok", "device.state_gb.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "model.decode_step_ms.tok", "engine.boot_weights_s.setup"):
        assert name in reported
    # every width is the catalog's; the three cuts are a chip's share
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["hidden_size"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["linear_key_head_dim"], config["router_width"]) == (
                2048, 256, 512, 10, 128, 512)
    assert serve.unchecked(config) == []
    # the shapes the reducers and the page check read
    assert shapes.kv_bytes_per_token(config) == 4096
    assert (shapes.attn_layers(config), shapes.linear_layers(config)) == (2, 6)
    assert shapes.state_bytes_per_slot_layer(config) == 32 * 128 * 128 * 4
    assert shapes.held_expert_bytes_per_layer(config) == 128 * 6291456
    assert shapes.expert_flops_per_assignment(config) == 6 * 2048 * 512


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size) == (8, 128, 512, 37984)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 3.667e9) < 5e6
    finally:
        specs._PRESETS.pop(name, None)


def trace_ctx(tmp_path, names):
    config = manifest.cell(CELL)["config"]
    spans = {"engine_thread": True, "decode": [
        {"steps": 8, "ctx_tokens": 9000, "rows": 250, "lead": 0},
        {"steps": 8, "ctx_tokens": 9000, "rows": 250, "lead": 8}]}
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace_spans.json").write_text(json.dumps(spans))
    return {
        "config": config, "profile": {"trace_dir": str(tmp_path / "trace")},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def test_gdn_step_roofline_on_a_synthetic_trace(tmp_path):
    """96 launches = 16 steps of 6 linear layers; 250 rows a step move
    250 x 2 x 2 MiB a launch = 1.0486 GB, 1.28 ms at 819 GB/s; launches
    of 2.56 ms read 50 %."""
    name = "jit__decode_chunk/gated_delta_step_pallas.3"
    ctx = trace_ctx(tmp_path, {name: (96 * 2.56e-3, 96),
                               "jit__decode_chunk/fusion.9": (0.1, 16)})
    got = gdn_step_roofline.reduce(
        ctx, pattern="jit__decode_chunk/gated_delta_step")
    assert got == pytest.approx(50.0, rel=0.01)
    # a program without the kernel, or a run without the spans: nothing
    assert gdn_step_roofline.reduce(ctx, pattern="no_such_kernel") is None
    assert gdn_step_roofline.reduce(dict(ctx, trace=None), pattern=".") is None
    dense = manifest.load_json(manifest.HERE, "configs", "qwen2.5-1.5b.json")
    assert gdn_step_roofline.reduce(
        dict(ctx, config=dense), pattern="gated_delta") is None


def test_moe_experts_roofline_on_a_synthetic_trace(tmp_path):
    """384 launches = 128 layer-steps; the window's counters say 120
    held experts hit and 640 held assignments a layer-step: 120 x 6.29
    MB = 755 MB, 0.92 ms at 819 GB/s (the operations, 640 x 6.3 MFLOP,
    need 0.02 ms: memory bounds it); 3 launches of 0.615 ms read 50 %."""
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    ctx = trace_ctx(tmp_path, {name: (384 * 0.6146e-3, 384)})
    totals = lambda n: {"totals": {"moe": {
        "layer_steps": 1000 * n, "experts_hit": 120000 * n,
        "held_assignments": 640000 * n, "assignments": 2560000 * n}}}
    ctx["perf"] = {"open": totals(1), "close": totals(3)}
    got = moe_experts_roofline.reduce(
        ctx, pattern="jit__decode_chunk/moe_grouped_matmul")
    assert got == pytest.approx(50.0, rel=0.01)
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert moe_experts_roofline.reduce(ctx, pattern="moe_grouped") is None
