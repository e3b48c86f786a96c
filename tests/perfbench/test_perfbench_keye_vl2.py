"""The Keye-VL-2.0 configuration's files: the plain reference on cases
worked out by hand, the manifest's contract with the new cell, the
shapes module (a page of K over V and an index key a layer), the
selection's and the experts' reducers on a synthetic trace of THIS
configuration, and the rehearsal's model."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_keye_dsa as shapes
from perfbench.reducers import (
    dsa_roofline, moe_experts_roofline, perf_ratio, trace_share,
    trace_step_ms)
from perfbench.references import keye_vl2 as ref

CELL = "keye-vl-2.0-30b-a3b-l12e32.long-agent"
F32 = jnp.float32


def tiny_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 2,
           "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
           "rope_scaling": {"mrope_section": [1, 1]},
           "num_experts": 2, "num_experts_per_tok": 1,
           "moe_intermediate_size": 4, "norm_topk_prob": True,
           "sa_config": {"indexer_head_dim": 4, "indexer_num_heads": 2,
                         "indexer_num_kv_heads": 1, "topk": 2}}
    cfg.update(over)
    return cfg


def test_the_pick_is_the_top_k_under_the_diagonal_ties_to_the_lower():
    scores = jnp.asarray([[5., 9, 9, 9], [1, 3, 2, 9], [4, 4, 4, 9],
                          [0, 7, 1, 7]], F32)
    causal = jnp.where(np.tri(4, dtype=bool), scores, -jnp.inf)
    picked = ref.selection(causal, 2)
    assert picked.tolist() == [
        [True, False, False, False],   # one key: all of them
        [True, True, False, False],    # two keys: both
        [True, True, False, False],    # three equal: the lower two
        [False, True, False, True]]    # the two 7s
    assert ref.selection(causal, 4).tolist() == np.tri(4, dtype=bool).tolist()


def test_index_scores_weigh_relu_of_the_heads_dot_products():
    """One index head of weight w, from the normed rows themselves: I(t,
    s) = w relu(q_t . k_s); a negative dot product scores 0 whatever w;
    position 0 takes no rotation."""
    cfg = tiny_cfg(sa_config={"indexer_head_dim": 4, "indexer_num_heads": 1,
                              "indexer_num_kv_heads": 1, "topk": 2})
    x = jnp.asarray([[2., 0, 0, 0]], F32)
    w = {"index_q": jnp.eye(4, dtype=F32), "index_k": jnp.eye(4, dtype=F32),
         "index_w": jnp.full((4, 1), 1.5, F32)}
    got = ref.index_scores(x, w, cfg, jnp.arange(1), slice(0, 1))
    k = ref.layer_norm(x @ w["index_k"], None, None)[0]
    want = 2 * 1.5 * (1 ** -0.5 * 4 ** -0.5) * max(0.0, float(x[0] @ k))
    assert float(got[0, 0]) == pytest.approx(want, rel=1e-6)
    flipped = ref.index_scores(x, dict(w, index_q=-w["index_q"]), cfg,
                               jnp.arange(1), slice(0, 1))
    assert float(flipped[0, 0]) == 0.0


def test_every_layer_picks_its_own_and_a_group_shares_its_key_head():
    cfg = tiny_cfg()
    layers = [ref.draw_layer(cfg, 0, i, F32) for i in range(2)]
    assert all("index_q" in lw and "router" in lw for lw in layers)
    assert ref.picks(cfg, 0) and ref.picks(cfg, 1)
    picked = []
    seq = [1, 5, 2, 7, 3, 6]
    ends = ref.draw_ends(cfg, 0, F32)
    ref.logprobs(cfg, 0, F32, [seq], [5], selections=picked,
                 weights=dict(ends, layers=layers))
    assert picked[1][0] is not picked[0][0]
    for layer in picked:
        assert layer[0].sum(-1).tolist() == [1, 2, 2, 2, 2, 2]
    # two query heads on ONE key head: with q's columns alike both heads
    # give the same output
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 4)), F32)
    w = {k: v.astype(F32) for k, v in layers[0].items()}
    w["q"] = jnp.concatenate([w["q"][:, :4]] * 2, axis=1)
    w["o"] = jnp.eye(8, 4, dtype=F32)
    out, _ = ref.attention(x, w, cfg)
    again, _ = ref.attention(x, dict(w, o=jnp.eye(8, 4, k=-4, dtype=F32)), cfg)
    np.testing.assert_allclose(out, again, rtol=1e-6)


def test_the_router_is_a_softmax_top_k_renormalised_without_a_bias():
    cfg = tiny_cfg(num_experts=4, num_experts_per_tok=2)
    x = jnp.asarray([[1., 0, 0, 0]], F32)
    w = {"router": jnp.asarray([[2., 0, 1, -1]] + [[0.] * 4] * 3, F32)}
    idx, vals = ref.route(x, w, cfg)
    assert idx.tolist() == [[0, 2]]
    e = np.exp([2., 1])
    np.testing.assert_allclose(vals[0], e / e.sum(), rtol=1e-6)
    assert vals.sum() == pytest.approx(1.0)


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == (
        "long-agent")
    assert len(cell["entry"]["why"]) <= 200
    slots = int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"])
    assert cell["params"] == {"clients": slots * 5 // 4, "resumed": slots}
    assert manifest.metric_names(bench, CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    for name in ("kernel.dsa_index_share.tok", "kernel.dsa_attend_share.tok",
                 "kernel.dsa_index_roofline.tok",
                 "kernel.dsa_attend_roofline.tok",
                 "model.dsa_select_share.tok", "model.dsa_prefill_share.tok",
                 "model.dsa_decode_step_ms.tok", "dsa.selected_share.tok",
                 "moe.held_assignment_share.tok",
                 "kernel.moe_experts_share.tok",
                 "kernel.moe_experts_roofline.tok",
                 "device.hbm_in_use_gb.tok", "device.idle_share.tok",
                 "scheduler.pool_fill.tok", "scheduler.preemptions.tok",
                 "engine.compiles_in_window.tok"):
        assert name in per_layer, name
    # a kernel's metric only where the cell makes that kernel's launches;
    # no dense feed-forward, no other configuration's load ratio
    assert not [n for n in per_layer if "mla" in n or "swa" in n
                or "dense_mlp" in n or "load_max" in n
                or n in ("kernel.prefill_attn_share.tok",
                         "kernel.decode_attn_share.tok")]
    # the benchmark gained this cell and no metric
    assert len(bench["per_layer"]) == 128
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["num_local_experts"], published["vocab_size"]) == (
                48, 128, 128, 151936)
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["router_width"],
            config["chips_sharing_a_layer"], config["rope_theta"]) == (
                2048, 6144, 768, 8, 32, 4, 128, 128, 4, 10000000)
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert manifest.cut_problems(config) == []
    assert serve.unchecked(config) == []
    # every item ISSUE 53 marks ASSUMED, and the tower left out
    for item in ("qk_norm", "indexer", "weights"):
        assert "ASSUMED" in config["assumed"][item], item
    assert "LEFT OUT" in config["assumed"]["vision_tower"]
    assert "q_chunk_size" in config["assumed"]["indexer"]
    assert "Hadamard" in config["assumed"]["indexer"]
    # a page's two rows, as the reducers and the page check read them
    assert (shapes.attn_layers(config), shapes.index_layers(config),
            shapes.moe_layers(config)) == (12, 12, 12)
    assert shapes.latent_row_bytes(config) == 2048
    assert shapes.index_row_bytes(config) == 256
    assert shapes.kv_bytes_per_token(config) == 12 * (2048 + 256) == 27648
    assert shapes.attend_flops_per_row(config) == 16384
    assert shapes.index_flops_per_row(config) == 2048
    assert shapes.held_expert_bytes(config) == 3 * 2048 * 768 * 2
    assert shapes.held_expert_bytes_per_layer(config) == 32 * 9437184
    traffic = cell["traffic"]
    assert (traffic["prompt_tokens"]["lo"], traffic["prompt_tokens"]["hi"],
            traffic["output_tokens"]["lo"], traffic["output_tokens"]["hi"],
            traffic["lead_in_s"], traffic["requests_per_client"]) == (
                8193, 14000, 1536, 2048, 60.0, 8)
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 2500, 6014]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith("keye_vl2")
    assert set(config["server"]["why"]) == set(config["server"]["env"])


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size, spec.first_expert) == (12, 32, 128, 37984, 0)
        assert (spec.attn_layers, spec.index_layers, spec.moe_layers,
                spec.index_topk) == (12, 12, 12, 2048)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 2.2243e9) < 1e6
        keys = serve.checked_keys(config)
        for key in ("num_experts", "num_local_experts", "sa_config",
                    "rope_scaling", "router_width", "first_expert",
                    "num_experts_per_tok", "moe_intermediate_size"):
            assert key in keys and key in config, key
        # a file that says 32 experts cannot front a program of 128, nor
        # a pick of 2,048 a program that picks 512, nor sections others
        for wrong in ({"num_experts": 128}, {"num_local_experts": 128},
                      {"sa_config": dict(config["sa_config"], topk=512)},
                      {"rope_scaling": dict(config["rope_scaling"],
                                            mrope_section=[24, 20, 20])},
                      {"num_hidden_layers": 48}, {"vocab_size": 151936}):
            with pytest.raises(SystemExit):
                serve.check(dict(config, **wrong), spec)
        # the program's page is what the shapes module says
        from vgate_tpu.runtime.kv_cache import _page_bytes

        assert 32 * shapes.kv_bytes_per_token(config) == _page_bytes(
            spec.attn_layers, 32, spec.cache_heads, spec.cache_head_dim, 2,
            0, spec.kv_pools, spec.index_layers, spec.index_key_lanes)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_KEYE_DSA
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 18
    page = int(config["rehearse"]["env"]["VGT_TPU__KV_PAGE_SIZE"])
    assert shapes.kv_bytes_per_token(tiny, "float32") * page == (
        page * 4 * (2 * 32 + 128) * 4)


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 12, "kv_bytes_per_token": 27648,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def dsa_totals(n, ctx=16 * 12000, picked=16 * 2048):
    return {"totals": {"dsa": {
        "decode_steps": 1000 * n, "rows_scored": 12 * ctx * 1000 * n,
        "rows_attended": 12 * picked * 1000 * n,
        "rows_in_context": 12 * ctx * 1000 * n}}}


def test_selection_metrics_read_this_configurations_launches():
    """96 attend launches = 8 steps of 12 layers, and as many scoring
    launches; the window's counters say a step attends to 16 x 2,048
    tokens a layer (32,768 x 2,048 B = 67.1 MB, 81.9 us at 819 GB/s; the
    operations, 0.54 G, need 2.7 us: memory bounds it) and scores 16 x
    12,000 tokens a layer (192,000 x 256 B = 49.2 MB, 60 us)."""
    attend = "jit__decode_chunk/dsa_decode_attention_pallas.3"
    index = "jit__decode_chunk/dsa_index_scores_pallas.8"
    prompt_index = "jit__prefill_step/dsa_index_scores_pallas.2"
    prompt = "jit__prefill_step/dsa_prefill_attention_pallas.6"
    a_least = 32768 * 2048 / 819e9
    i_least = 192000 * 256 / 819e9
    ctx = trace_ctx({attend: (96 * 4 * a_least, 96),
                     index: (96 * 2 * i_least, 96),
                     prompt_index: (0.03, 16), prompt: (0.2, 40),
                     "jit__decode_chunk/fusion.1": (0.1, 9)})
    ctx["perf"] = {"open": dsa_totals(1), "close": dsa_totals(3)}
    roof = lambda name: dsa_roofline.reduce(ctx, **metric_args(name))
    assert roof("kernel.dsa_attend_roofline.tok") == pytest.approx(25.0)
    assert roof("kernel.dsa_index_roofline.tok") == pytest.approx(50.0)
    share = lambda name: trace_share.reduce(ctx, **metric_args(name))
    assert share("kernel.dsa_attend_share.tok") == pytest.approx(
        100 * 96 * 4 * a_least)
    assert share("kernel.dsa_index_share.tok") == pytest.approx(
        100 * (96 * 2 * i_least + 0.03))
    assert share("kernel.prefill_attn_share.tok") == 0.0  # not its name
    assert perf_ratio.reduce(
        ctx, **metric_args("dsa.selected_share.tok")
    ) == pytest.approx(100 * 2048 / 12000)
    step = trace_step_ms.reduce(
        ctx, **metric_args("model.dsa_decode_step_ms.tok"))
    assert step == pytest.approx(
        1000 * (96 * 4 * a_least + 96 * 2 * i_least + 0.1) / 8)
    # at the chip's peak bandwidth over the counted rows: 100 %, not more
    ctx["trace"]["op_seconds"][attend] = 96 * a_least
    assert roof("kernel.dsa_attend_roofline.tok") == pytest.approx(100.0)
    # a program without the selection's counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert roof("kernel.dsa_attend_roofline.tok") is None
    assert roof("kernel.dsa_index_roofline.tok") is None


def test_the_experts_roofline_reads_this_configuration():
    """384 = 12 expert layers x 32 held experts; a step's 128 pairs fall
    on 24 of a layer's 32."""
    totals = lambda n: {"totals": {"moe": {
        "layer_steps": 12000 * n, "experts_hit": 24 * 12000 * n,
        "held_assignments": 32 * 12 * 1000 * n, "assignments": 128 * 12
        * 1000 * n, "steps": 1000 * n}}}
    ctx = {"perf": {"open": totals(1), "close": totals(2)}}
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(25.0)
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    hit = 24 * 3 * 2048 * 768 * 2 / 819e9
    tctx = trace_ctx({name: (360 * hit / 3 * 2, 360)})
    tctx["perf"] = ctx["perf"]
    assert moe_experts_roofline.reduce(
        tctx, **metric_args("kernel.moe_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)
