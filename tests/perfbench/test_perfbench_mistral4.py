"""The Mistral-Small-4 configuration's files: the plain reference on
cases worked out by hand, the manifest's contract with the new cell, the
shapes module, and the new reducer and metric files on a synthetic
trace."""

import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_mistral4 as shapes
from perfbench.reducers import (
    latent_experts_roofline, mla_roofline, perf_ratio, trace_share,
    trace_step_ms)
from perfbench.references import mistral4 as ref

CELL = "mistral-small-4-119b-l4e32.long-prompt"
F32 = jnp.float32
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 4,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 32, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}


def one_head_cfg(heads=1, nope=2, rope=2, kl=2, vd=2):
    return {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": heads,
            "q_lora_rank": 4, "kv_lora_rank": kl, "qk_nope_head_dim": nope,
            "qk_rope_head_dim": rope, "v_head_dim": vd,
            "n_routed_experts": 2, "num_experts_per_tok": 1,
            "moe_intermediate_size": 4, "n_shared_experts": 1,
            "num_hidden_layers": 1, "rms_norm_eps": 1e-6,
            "rope_parameters": dict(ROPE)}


def test_with_identity_w_uk_the_scores_are_latent_dot_products():
    """One head, W_uk = W_uv = I (2 x 2), no rotary part in q (its
    columns of W_qb are zero) and V = c_kv: the output at the last
    position is softmax(sigma q . c_kv) weighted c_kv, by hand."""
    cfg = one_head_cfg()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 4)), F32)
    w = {
        "q_a": jnp.eye(4, dtype=F32),
        "q_b": jnp.asarray(rng.standard_normal((4, 4)), F32).at[:, 2:].set(0),
        "kv_a": jnp.asarray(rng.standard_normal((4, 4)), F32),
        "kv_b": jnp.concatenate(
            [jnp.eye(2, dtype=F32), jnp.eye(2, dtype=F32)], -1),
        "o": jnp.eye(2, 4, dtype=F32),
    }
    got = np.asarray(ref.attention(x, w, cfg))
    cq = np.asarray(ref.norm(x, 1e-6))
    q = (cq @ np.asarray(w["q_b"]))[:, :2]
    c_kv = np.asarray(ref.norm((x @ w["kv_a"])[:, :2], 1e-6))
    sigma = 4 ** -0.5 * (0.1 * math.log(4) + 1) ** 2
    assert ref.softmax_scale(cfg) == pytest.approx(sigma)
    s = sigma * (q[2] @ c_kv.T)
    p = np.exp(s - s.max())
    p /= p.sum()
    np.testing.assert_allclose(got[2, :2], p @ c_kv, rtol=1e-5)
    np.testing.assert_allclose(got[2, 2:], 0, atol=1e-7)
    # the first position attends to itself alone
    np.testing.assert_allclose(got[0, :2], c_kv[0], rtol=1e-5)


def test_position_scaling_at_0_31_32_95():
    pos = jnp.array([0, 31, 32, 95])
    got = np.asarray(ref.position_scale(pos, ROPE))
    np.testing.assert_allclose(
        got, [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3)], rtol=1e-6)


def test_one_rotated_key_is_shared_by_two_heads():
    """Two heads whose queries are equal and whose W_uk are equal get
    equal scores: the rotary key is one for both.  And the rotation is
    by position: shifting every position by 5 changes nothing inside
    the original maximum (gamma = 1 there), a relative encoding."""
    cfg = one_head_cfg(heads=2)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 4)), F32)
    head = rng.standard_normal((4, 4)).astype(np.float32)
    uk = rng.standard_normal((2, 1, 4)).astype(np.float32)
    w = {"q_a": jnp.eye(4, dtype=F32),
         "q_b": jnp.asarray(np.concatenate([head, head], -1)),
         "kv_a": jnp.asarray(rng.standard_normal((4, 4)), F32),
         "kv_b": jnp.asarray(np.concatenate([uk, uk], 1)),
         "o": jnp.asarray(rng.standard_normal((4, 4)), F32)}
    base = ref.attention(x, w, cfg)
    # head 1 alone through W_o's rows of head 0 gives what head 0 alone
    # gives: the two heads' outputs are equal
    first = ref.attention(x, dict(w, o=w["o"].at[2:].set(0)), cfg)
    second = ref.attention(x, dict(w, o=jnp.concatenate(
        [jnp.zeros((2, 4), F32), w["o"][:2]])), cfg)
    np.testing.assert_allclose(first, second, rtol=1e-5, atol=1e-6)
    shifted = ref.attention(x, w, cfg, pos=jnp.arange(6) + 5)
    np.testing.assert_allclose(shifted, base, rtol=1e-4, atol=1e-5)
    # past the original maximum the queries are scaled: not the same
    far = ref.attention(x, w, cfg, pos=jnp.arange(6) + 64)
    assert np.abs(np.asarray(far - base)).max() > 1e-3


def test_a_token_whose_experts_are_absent_gets_the_shared_expert_only():
    cfg = dict(one_head_cfg(), router_width=4, first_expert=2)
    rng = np.random.default_rng(2)
    w = {"router": jnp.asarray([[9., 0, 0, 0]] * 4, F32),
         **{n: jnp.asarray(rng.standard_normal(s), F32) for n, s in (
             ("gate", (2, 4, 4)), ("up", (2, 4, 4)), ("down", (2, 4, 4)),
             ("shared_gate", (4, 4)), ("shared_up", (4, 4)),
             ("shared_down", (4, 4)))}}
    x = jnp.ones((2, 4), F32)  # every row chooses expert 0: not held
    got = ref.moe(x, w, cfg)
    want = ref.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not np.asarray(ref.moe(x, w, cfg, shared=False)).any()


def test_the_files_keep_the_manifests_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config = cell["config"]
    assert manifest.cut_problems(config) == []
    assert cell["entry"] == {
        "name": CELL, "config": "mistral-small-4-119b-l4e32",
        "traffic": "long-prompt", "chips": 1, "why": cell["entry"]["why"]}
    assert cell["params"] == {"clients": 320, "resumed": 256}
    assert manifest.metric_names(cell["bench"], CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    reported = manifest.metric_names(cell["bench"], CELL, "per_layer")
    for name in ("kernel.mla_decode_share.tok",
                 "kernel.mla_decode_roofline.tok",
                 "kernel.prefill_attn_share.tok",
                 "kernel.mla_prefill_roofline.tok",
                 "moe.l4e32_load_max_over_mean.tok",
                 "model.mla_decode_step_ms.tok",
                 "kernel.latent_experts_roofline.tok",
                 "kernel.moe_experts_share.tok",
                 "moe.held_assignment_share.tok", "model.prefill_share.tok",
                 "scheduler.pool_fill.tok", "scheduler.live_tokens_mean.tok",
                 "engine.boot_weights_s.setup"):
        assert name in reported
    for name in ("kernel.decode_attn_share.tok",
                 "kernel.decode_attn_roofline_live.tok",
                 "model.decode_step_ms.tok", "kernel.gdn_step_share.tok",
                 "kernel.moe_experts_roofline.tok", "device.state_gb.tok",
                 "moe.load_max_over_mean.tok"):
        assert name not in reported  # read a kernel this one never launches
    # every width is the catalog's; the three cuts are a chip's share
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 36, "n_routed_experts": 128,
        "vocab_size": 131072}
    assert (config["hidden_size"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["router_width"]) == (
                4096, 1024, 256, 64, 64, 128, 2048, 4, 128)
    assert serve.unchecked(config) == []
    # the shapes the reducers and the page check read
    assert shapes.latent_values(config) == 320
    assert shapes.latent_row_bytes(config) == 768
    assert shapes.kv_bytes_per_token(config) == 4 * 768
    assert (shapes.attn_layers(config), shapes.moe_layers(config)) == (4, 4)
    assert shapes.mla_decode_flops_per_token_read(config) == 36864
    assert shapes.mla_prefill_flops_per_pair(config) == 16384
    assert shapes.held_expert_bytes(config) == 50331648
    assert shapes.expert_launches_per_layer(config) == 3
    assert shapes.expert_flops_per_assignment(config) == 2 * 3 * 4096 * 2048
    # the traffic: every prompt, a resumed one too, fits the one bucket
    traffic = cell["traffic"]
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"]))


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size) == (4, 32, 128, 32768)
        assert (spec.linear_layers, spec.moe_layers, spec.attn_layers) == (
            0, 4, 4)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 3.705e9) < 5e6
        # a file that says 32 experts cannot front a program of 128, nor
        # one YaRN factor another
        with pytest.raises(SystemExit):
            serve.check(dict(config, n_routed_experts=128), spec)
        with pytest.raises(SystemExit):
            serve.check(dict(config, rope_parameters=dict(
                config["rope_parameters"], factor=64)), spec)
        # the program's page is what the shapes module says
        assert 32 * shapes.kv_bytes_per_token(config) == (
            spec.kv_pools * spec.attn_layers * 32 * spec.cache_heads
            * spec.cache_head_dim * 2)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_MLA_MOE
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 4,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def mla_totals(n, reads_per_step=4 * 1_500_000, pairs=4 * 15_000_000):
    return {"totals": {"mla": {
        "decode_steps": 1000 * n, "decode_token_reads": reads_per_step
        * 1000 * n, "prefill_prompts": 100 * n,
        "prefill_pairs": pairs * 100 * n, "latent_rows_written": 7 * n}}}


def test_mla_decode_metrics_on_a_synthetic_trace():
    """64 launches = 16 steps of 4 layers; the window's counters say a
    step reads 1.5 M rows a layer: 1.5 M x 768 B = 1.152 GB, 1.4066 ms
    at 819 GB/s (the operations, 1.5 M x 36,864 = 55 G, need 0.28 ms:
    memory bounds it); launches of 2.8132 ms read 50 %, and their
    0.18 s are 18 % of a busy second; the step is the module's 0.32 s
    over 16."""
    name = "jit__decode_chunk/mla_decode_attention_pallas.3"
    ctx = trace_ctx({name: (64 * 2.8132e-3, 64),
                     "jit__decode_chunk/fusion.9": (0.32 - 64 * 2.8132e-3,
                                                    16)})
    ctx["perf"] = {"open": mla_totals(1), "close": mla_totals(3)}
    args = metric_args("kernel.mla_decode_roofline.tok")
    assert mla_roofline.reduce(ctx, **args) == pytest.approx(50.0, rel=1e-3)
    assert trace_share.reduce(
        ctx, **metric_args("kernel.mla_decode_share.tok")
    ) == pytest.approx(18.0, rel=1e-3)
    assert trace_step_ms.reduce(
        ctx, **metric_args("model.mla_decode_step_ms.tok")
    ) == pytest.approx(20.0, rel=1e-6)
    # the kernel at the chip's peak bandwidth over the rows counted
    # (real rows only): 100 %, and it cannot pass it
    ctx["trace"]["op_seconds"][name] = 64 * 1.5e6 * 768 / 819e9
    assert mla_roofline.reduce(ctx, **args) == pytest.approx(100.0, rel=1e-6)
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert mla_roofline.reduce(ctx, **args) is None
    # another configuration's shapes say nothing of a latent row
    other = manifest.load_json(
        manifest.HERE, "configs", "qwen3-next-80b-a3b-l8e128.json")
    ctx["perf"] = {"open": mla_totals(1), "close": mla_totals(3)}
    assert mla_roofline.reduce(dict(ctx, config=other), **args) is None
    assert mla_roofline.reduce(dict(ctx, trace=None), **args) is None
    # the accepted cells' decode kernel is not this one's
    assert trace_share.reduce(
        ctx, **metric_args("kernel.decode_attn_share.tok")) == 0.0


def test_mla_prefill_roofline_on_a_synthetic_trace():
    """40 launches = 10 prompts of 4 layers; a prompt of 5,477 tokens
    has 15.0 M (query, key) pairs at or under the diagonal a layer: 15 M
    x 16,384 = 245.8 G operations, 1.2475 ms at 197 T/s; launches of
    4.99 ms read 25 %."""
    name = "jit__prefill_step/flash_prefill_attention_pallas.6"
    ctx = trace_ctx({name: (40 * 4.99e-3, 40),
                     "jit__decode_chunk/flash_prefill_attention": (9.0, 9)})
    ctx["perf"] = {"open": mla_totals(1), "close": mla_totals(2)}
    args = metric_args("kernel.mla_prefill_roofline.tok")
    assert mla_roofline.reduce(ctx, **args) == pytest.approx(25.0, rel=1e-3)
    assert trace_share.reduce(
        ctx, **metric_args("kernel.prefill_attn_share.tok")
    ) == pytest.approx(40 * 0.499, rel=1e-3)
    # at the chip's peak rate over the pairs counted: 100 %, not more
    ctx["trace"]["op_seconds"][name] = 40 * 15e6 * 16384 / 197e12
    assert mla_roofline.reduce(ctx, **args) == pytest.approx(100.0, rel=1e-6)


def test_load_ratio_and_experts_roofline_read_this_configuration():
    """128 = 4 layers x 32 held experts: a step's largest load summed
    over steps, over the held pairs a step a held expert."""
    totals = lambda n: {"totals": {"moe": {
        "layer_steps": 4000 * n, "experts_hit": 32 * 4000 * n,
        "held_assignments": 251 * 4 * 1000 * n, "assignments": 1004 * 4
        * 1000 * n, "load_max_sum": 20 * 1000 * n, "steps": 1000 * n}}}
    ctx = {"perf": {"open": totals(1), "close": totals(2)}}
    got = perf_ratio.reduce(
        ctx, **metric_args("moe.l4e32_load_max_over_mean.tok"))
    assert got == pytest.approx(20 * 128 / (251 * 4))
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(25.0)
    # three launches a layer-step read 32 x 50.3 MB: 1.966 ms at the peak
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    full = 32 * 50331648 / 819e9
    tctx = trace_ctx({name: (120 * full / 3 * 2, 120)})
    tctx["perf"] = ctx["perf"]
    assert latent_experts_roofline.reduce(
        tctx, **metric_args("kernel.latent_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)
