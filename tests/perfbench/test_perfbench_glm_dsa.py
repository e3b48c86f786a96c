"""The GLM-5.2 configuration's files: the plain reference on cases worked
out by hand, the manifest's contract with the new cell, the shapes
module (a page of two rows a token), and the new reducer and metric
files on a synthetic trace."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_glm_dsa as shapes
from perfbench.reducers import (
    dsa_roofline, moe_experts_roofline, perf_ratio, trace_share,
    trace_step_ms)
from perfbench.references import glm_moe_dsa as ref

CELL = "glm-5.2-l5e16.long-agent"
F32 = jnp.float32


def tiny_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 1,
           "q_lora_rank": 4, "kv_lora_rank": 4, "qk_nope_head_dim": 2,
           "qk_rope_head_dim": 2, "v_head_dim": 4, "intermediate_size": 4,
           "moe_intermediate_size": 4, "n_routed_experts": 2,
           "num_experts_per_tok": 1, "n_shared_experts": 1,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True,
           "index_topk": 2, "index_n_heads": 2, "index_head_dim": 4,
           "indexer_types": ["full", "shared"],
           "mlp_layer_types": ["dense", "sparse"], "num_hidden_layers": 2,
           "rms_norm_eps": 1e-6,
           "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}
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
    """One index head of weight w: I(t, s) = w relu(q_t . k_s); a
    negative dot product scores 0 whatever w; position 0 takes no
    rotation."""
    cfg = tiny_cfg(index_n_heads=1, qk_rope_head_dim=2)
    x = jnp.asarray([[1., 0, 0, 0]], F32)
    cq = jnp.asarray([[2., 0, 0, 0]], F32)
    w = {"index_q": jnp.eye(4, dtype=F32), "index_k": jnp.eye(4, dtype=F32),
         "index_w": jnp.full((4, 1), 3.0, F32)}
    got = ref.index_scores(x, cq, w, cfg, jnp.arange(1))
    k = ref.layer_norm(x @ w["index_k"], None, None)[0]
    want = 3.0 * (1 ** -0.5 * 4 ** -0.5) * max(0.0, float(cq[0] @ k))
    assert float(got[0, 0]) == pytest.approx(want, rel=1e-6)
    flipped = ref.index_scores(x, -cq, w, cfg, jnp.arange(1))
    assert float(flipped[0, 0]) == 0.0


def test_a_shared_layer_attends_under_the_pick_of_the_full_layer_below():
    cfg = tiny_cfg()
    layers = [ref.draw_layer(cfg, 0, i, F32) for i in range(2)]
    assert "index_q" in layers[0] and "index_q" not in layers[1]
    assert "mlp_gate" in layers[0] and "router" in layers[1]
    picked = []
    seq = [1, 5, 2, 7, 3, 6]
    ends = ref.draw_ends(cfg, 0, F32)
    ref.logprobs(cfg, 0, F32, [seq], [5], selections=picked,
                 weights=dict(ends, layers=layers))
    assert picked[1][0] is picked[0][0]
    assert picked[0][0].sum(-1).tolist() == [1, 2, 2, 2, 2, 2]


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == (
        "long-agent")
    slots = int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"])
    assert cell["params"] == {"clients": slots * 5 // 4, "resumed": slots}
    reported = manifest.metric_names(bench, CELL, "end_to_end")
    assert reported == ["out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    for name in ("kernel.dsa_index_share.tok", "kernel.dsa_attend_share.tok",
                 "kernel.dsa_index_roofline.tok",
                 "kernel.dsa_attend_roofline.tok",
                 "model.dsa_select_share.tok", "model.dsa_prefill_share.tok",
                 "model.dsa_decode_step_ms.tok", "dsa.selected_share.tok",
                 "moe.glm52_load_max_over_mean.tok",
                 "moe.held_assignment_share.tok",
                 "kernel.moe_experts_share.tok", "model.dense_mlp_share.tok",
                 "device.hbm_in_use_gb.tok", "device.idle_share.tok",
                 "scheduler.pool_fill.tok", "scheduler.preemptions.tok"):
        assert name in per_layer, name
    # a kernel's metric only where the cell makes that kernel's launches
    assert not [n for n in per_layer if "mla" in n or "swa" in n
                or n == "kernel.prefill_attn_share.tok"]
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = config["published"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (78, 256, 154880)
    # the per-layer lists are the published ones, whole; the cut is five
    # layers of them from the third
    assert (len(config["indexer_types"]), config["first_layer"],
            config["first_k_dense_replace"]) == (78, 2, 3)
    assert config["indexer_types"].count("full") == 21
    assert config["indexer_types"][2:7] == [
        "full", "shared", "shared", "shared", "full"]
    assert config["mlp_layer_types"][2:7] == ["dense"] + ["sparse"] * 4
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_attention_heads"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["index_topk"], config["index_n_heads"],
            config["index_head_dim"], config["router_width"],
            config["chips_sharing_a_layer"]) == (
                6144, 12288, 2048, 8, 64, 2048, 512, 192, 64, 256, 2048, 32,
                128, 256, 16)
    assert manifest.cut_problems(config) == []
    assert serve.unchecked(config) == []
    # a page's two rows, as the reducers and the page check read them
    assert (shapes.attn_layers(config), shapes.index_layers(config),
            shapes.moe_layers(config)) == (5, 2, 4)
    assert shapes.latent_row_bytes(config) == 1280
    assert shapes.index_row_bytes(config) == 256
    assert shapes.kv_bytes_per_token(config) == 6912
    assert shapes.attend_flops_per_row(config) == 139264
    assert shapes.index_flops_per_row(config) == 8192
    assert shapes.held_expert_bytes(config) == 3 * 6144 * 2048 * 2
    traffic = cell["traffic"]
    assert (traffic["prompt_tokens"]["lo"], traffic["prompt_tokens"]["hi"],
            traffic["output_tokens"]["lo"], traffic["output_tokens"]["hi"],
            traffic["lead_in_s"], traffic["requests_per_client"]) == (
                8193, 14000, 1536, 2048, 60.0, 8)
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 2500, 6014]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith(
        "glm_moe_dsa")


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.num_experts, spec.router_width,
                spec.vocab_size, spec.first_k_dense, spec.first_layer) == (
                    5, 16, 256, 19360, 3, 2)
        assert spec.lead_blocks == (("dsa", "mlp"),)
        assert (spec.attn_layers, spec.index_layers, spec.moe_layers,
                spec.index_topk) == (5, 2, 4, 2048)
        assert max(spec.eos_token_id, spec.bos_token_id,
                   *spec.extra_stop_ids, 0) < spec.vocab_size
        assert hash(spec) is not None  # a static jit argument
        assert abs(spec.num_params - 3.881e9) < 1e6
        keys = serve.checked_keys(config)
        for key in ("index_topk", "index_n_heads", "index_head_dim",
                    "indexer_rope_interleave", "indexer_types",
                    "mlp_layer_types", "first_k_dense_replace",
                    "first_layer"):
            assert key in keys and key in config, key
        # a file that says 16 experts cannot front a program of 256, nor
        # one pick of 2,048 a program that picks 512, nor five layers'
        # kinds another five's
        for wrong in ({"n_routed_experts": 256}, {"index_topk": 512},
                      {"indexer_types": ["full"] * 78},
                      {"first_k_dense_replace": 1}, {"first_layer": 0}):
            with pytest.raises(SystemExit):
                serve.check(dict(config, **wrong), spec)
        # the program's page is what the shapes module says
        from vgate_tpu.runtime.kv_cache import _page_bytes

        assert 32 * shapes.kv_bytes_per_token(config) == _page_bytes(
            spec.attn_layers, 32, spec.cache_heads, spec.cache_head_dim, 2,
            0, spec.kv_pools, spec.index_layers, spec.index_head_dim)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_presets():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny, spec = config["rehearse"]["model"], specs.TINY_DSA_MOE
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 24


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 5, "kv_bytes_per_token": 6912,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def dsa_totals(n, ctx=48 * 12000, picked=48 * 2048):
    return {"totals": {"dsa": {
        "decode_steps": 1000 * n, "rows_scored": 2 * ctx * 1000 * n,
        "rows_attended": 5 * picked * 1000 * n,
        "rows_in_context": 5 * ctx * 1000 * n}}}


def test_selection_metrics_on_a_synthetic_trace():
    """80 attend launches = 16 steps of 5 layers, 32 scoring launches =
    16 steps of 2 picking layers; the window's counters say a step
    attends to 48 x 2,048 rows a layer (98,304 x 1,280 B = 125.8 MB,
    153.6 us at 819 GB/s; the operations, 13.7 G, need 69.5 us: memory
    bounds it) and scores 48 x 12,000 rows a picking layer (576,000 x 256
    B = 147.5 MB, 180 us).  The shares tell this configuration's launches
    from the dense latent layer's by name."""
    attend = "jit__decode_chunk/dsa_decode_attention_pallas.3"
    index = "jit__decode_chunk/dsa_index_scores_pallas.8"
    prompt_index = "jit__prefill_step/dsa_index_scores_pallas.2"
    prompt = "jit__prefill_step/dsa_prefill_attention_pallas.6"
    a_least = 98304 * 1280 / 819e9
    i_least = 576000 * 256 / 819e9
    ctx = trace_ctx({attend: (80 * 2 * a_least, 80),
                     index: (32 * 4 * i_least, 32),
                     prompt_index: (0.03, 16), prompt: (0.2, 40),
                     "jit__decode_chunk/fusion.1": (0.1, 9)})
    ctx["perf"] = {"open": dsa_totals(1), "close": dsa_totals(3)}
    roof = lambda name: dsa_roofline.reduce(ctx, **metric_args(name))
    assert roof("kernel.dsa_attend_roofline.tok") == pytest.approx(50.0)
    assert roof("kernel.dsa_index_roofline.tok") == pytest.approx(25.0)
    share = lambda name: trace_share.reduce(ctx, **metric_args(name))
    assert share("kernel.dsa_attend_share.tok") == pytest.approx(
        100 * 80 * 2 * a_least)
    assert share("kernel.dsa_index_share.tok") == pytest.approx(
        100 * (32 * 4 * i_least + 0.03))
    # the accepted metrics read none of these launches
    for name in ("kernel.mla_decode_share.tok",
                 "kernel.prefill_attn_share.tok",
                 "kernel.decode_attn_share.tok"):
        assert share(name) == 0.0, name
    assert perf_ratio.reduce(
        ctx, **metric_args("dsa.selected_share.tok")
    ) == pytest.approx(100 * 2048 / 12000)
    # one step's device time: everything under the decode module over
    # the 16 steps the attend launches make
    step = trace_step_ms.reduce(
        ctx, **metric_args("model.dsa_decode_step_ms.tok"))
    assert step == pytest.approx(
        1000 * (80 * 2 * a_least + 32 * 4 * i_least + 0.1) / 16)
    # at the chip's peak bandwidth over the counted rows: 100 %, not more
    ctx["trace"]["op_seconds"][attend] = 80 * a_least
    assert roof("kernel.dsa_attend_roofline.tok") == pytest.approx(100.0)
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert roof("kernel.dsa_attend_roofline.tok") is None
    assert roof("kernel.dsa_index_roofline.tok") is None
    ctx["perf"] = {"open": dsa_totals(1), "close": dsa_totals(3)}
    other = manifest.load_json(
        manifest.HERE, "configs", "mistral-small-4-119b-l4e32.json")
    args = metric_args("kernel.dsa_attend_roofline.tok")
    assert dsa_roofline.reduce(dict(ctx, config=other), **args) is None
    assert dsa_roofline.reduce(dict(ctx, trace=None), **args) is None


def test_load_ratio_and_experts_roofline_read_this_configuration():
    """64 = 4 expert layers x 16 held experts."""
    totals = lambda n: {"totals": {"moe": {
        "layer_steps": 4000 * n, "experts_hit": 12 * 4000 * n,
        "held_assignments": 24 * 4 * 1000 * n, "assignments": 384 * 4
        * 1000 * n, "load_max_sum": 12 * 1000 * n, "steps": 1000 * n}}}
    ctx = {"perf": {"open": totals(1), "close": totals(2)}}
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.glm52_load_max_over_mean.tok")
    ) == pytest.approx(12 * 64 / (24 * 4))
    assert perf_ratio.reduce(
        ctx, **metric_args("moe.held_assignment_share.tok")
    ) == pytest.approx(6.25)
    name = "jit__decode_chunk/moe_grouped_matmul_pallas.5"
    hit = 12 * 3 * 6144 * 2048 * 2 / 819e9
    tctx = trace_ctx({name: (120 * hit / 3 * 2, 120)})
    tctx["perf"] = ctx["perf"]
    assert moe_experts_roofline.reduce(
        tctx, **metric_args("kernel.moe_experts_roofline.tok")
    ) == pytest.approx(50.0, rel=1e-6)
