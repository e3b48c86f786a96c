"""The EvaByte configuration's files: the plain reference on cases
worked out by hand, the manifest's contract with the new cells, the
shapes module against the issue's hand numbers (a pool row that stands
for 16 tokens), and the new reducer and metric files on a made-up
``ctx``."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_evabyte as shapes
from perfbench.reducers import eva_roofline, perf_ratio, trace_share, \
    trace_step_ms
from perfbench.references import evabyte as ref

CELL = "evabyte-6.5b-l8.long-agent"
DENSE_CELL = "qwen2.5-1.5b.long-context"
F32 = jnp.float32


def test_a_chunks_summary_is_its_softmax_weighted_rows_and_the_shift():
    """Two chunks of two rows, one head of two dims: with phi = 0 the
    weights are uniform, and mu goes to the key alone; with phi along
    dim 0 the row whose key is larger there weighs e^(s d) more."""
    k = jnp.asarray([[[1., 0]], [[3., 0]], [[0., 2]], [[0., 4]]], F32)
    v = jnp.asarray([[[1., 1]], [[3., 5]], [[2., 2]], [[6., 6]]], F32)
    mu = jnp.asarray([[10., 20]], F32)
    ks, vs = ref.summaries(k, v, jnp.zeros((1, 2), F32), mu, 2)
    assert ks[:, 0].tolist() == [[12., 20], [10., 23]]
    assert vs[:, 0].tolist() == [[2., 3], [4., 4]]
    ks, vs = ref.summaries(k, v, jnp.asarray([[1., 0]], F32), mu, 2)
    a = 1 / (1 + np.exp(-2 * 2 ** -0.5))  # the weight of the row k = 3
    assert float(ks[0, 0, 0]) == pytest.approx(10 + 3 * a + (1 - a), 1e-6)
    assert float(vs[0, 0, 1]) == pytest.approx(5 * a + (1 - a), 1e-6)
    # a partial last chunk is never read: it is not summarised
    assert ref.summaries(k[:3], v[:3], mu, mu, 2)[0].shape[0] == 1


def test_a_query_sees_its_window_exactly_and_closed_windows_chunks():
    """W = 4, c = 2, nine positions: a value that marks each key shows
    which a query attended to (q = 0: every seen key weighs alike)."""
    cfg = {"hidden_size": 2, "num_attention_heads": 1, "window_size": 4,
           "chunk_size": 2, "rope_theta": 10000.0, "intermediate_size": 2,
           "vocab_size": 4}
    S = 9
    x = jnp.ones((S, 2), F32)
    marks = jnp.eye(S, dtype=F32)  # v_i = e_i needs D = S: use o to read
    w = {"q": jnp.zeros((2, 2), F32), "k": jnp.zeros((2, 2), F32),
         "v": jnp.eye(2, dtype=F32), "o": jnp.eye(2, dtype=F32),
         "phi": jnp.zeros((1, 2), F32), "mu": jnp.zeros((1, 2), F32)}
    # with q = k = 0 every seen key (exact or summary) weighs 1 / count,
    # and every v is x = (1, 1): the output is (1, 1) whatever is seen
    out = ref.attention(x, w, cfg)
    assert np.allclose(out, 1.0)
    # counts by the masks themselves: t sees t % 4 + 1 exact keys and
    # 2 * (t // 4) summaries
    pos = np.arange(S)
    exact = [(pos >= t // 4 * 4) & (pos <= t) for t in pos]
    assert [int(e.sum()) for e in exact] == [1, 2, 3, 4, 1, 2, 3, 4, 1]
    assert [2 * (t // 4) for t in pos] == [0, 0, 0, 0, 2, 2, 2, 2, 4]
    del marks


def test_the_cells_files_keep_the_contract():
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    config, bench = cell["config"], cell["bench"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == (
        "long-agent")
    slots = int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"])
    assert slots == 20
    assert cell["params"] == {"clients": slots * 5 // 4, "resumed": slots}
    assert manifest.metric_names(bench, CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    per_layer = manifest.metric_names(bench, CELL, "per_layer")
    for name in ("kernel.decode_attn_share.tok", "model.decode_step_ms.tok",
                 "kernel.eva_prefill_share.tok",
                 "kernel.eva_decode_roofline.tok",
                 "model.eva_summarize_share.tok", "eva.chunk_read_share.tok",
                 "model.dense_mlp_share.tok", "device.state_gb.tok",
                 "device.hbm_in_use_gb.tok", "device.idle_share.tok",
                 "engine.prefill_pad_share.tok", "scheduler.pool_fill.tok",
                 "scheduler.preemptions.tok", "model.decode_share.tok",
                 "model.prefill_share.tok"):
        assert name in per_layer, name
    # a kernel's metric only where the cell makes that kernel's launches;
    # the paged decode kernel's token-counted rooflines do not hold here
    # (a pool row stands for 16 tokens): kernel.eva_decode_roofline.tok
    assert not [n for n in per_layer if "moe" in n or "dsa" in n
                or "mla" in n or "swa" in n or "decode_attn_roofline" in n]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["window_size"], config["chunk_size"],
            config["num_pred_heads"], config["vocab_size"],
            config["num_hidden_layers"], config["rope_theta"],
            config["fp32_skip_add"], config["norm_add_unit_offset"]) == (
                4096, 11008, 32, 32, 2048, 16, 8, 320, 8, 100000, True, True)
    assert manifest.cut_problems(config) == []
    assert serve.unchecked(config) == []
    for item in ("chunk_logit_scale", "mu_on_keys",
                 "rotary_before_summarising", "visibility", "residual_form",
                 "head_for_sampling", "tokenizer_offset"):
        assert "from memory of the released modelling code" in (
            config["assumed"][item]), item
    traffic = cell["traffic"]
    assert (traffic["prompt_tokens"]["lo"], traffic["prompt_tokens"]["hi"],
            traffic["output_tokens"]["lo"], traffic["output_tokens"]["hi"],
            traffic["lead_in_s"], traffic["requests_per_client"]) == (
                8193, 14000, 1536, 2048, 60.0, 8)
    ref_cfg = config["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 2045, 8200]
    assert ref_cfg["decode_tokens"] == 64  # 1,536 values: tolerance_why
    # each limit between its two readings (my chip runs, PR 44): the
    # configured program's, and the nearest below it that has to fail
    sound = (0.068295, 0.012802)  # largest, mean of the 1,536 values
    bf16_stream_mean, float8_weights = 0.015880, (1.477830, 0.262738)
    assert sound[0] < ref_cfg["tolerance"] <= 2 * sound[0] < float8_weights[0]
    assert (1.1 * sound[1] < ref_cfg["mean_tolerance"]
            < 0.91 * bf16_stream_mean < float8_weights[1])
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith("evabyte")


def test_the_shapes_module_against_the_issues_hand_numbers():
    config = manifest.cell(CELL)["config"]
    assert shapes.attn_layers(config) == 8
    assert shapes.eva_row_bytes(config) == 16384  # 2 x 32 x 128 x 2 B
    # ONE POOL ROW over the 8 layers: 16 tokens of a closed window
    assert shapes.kv_bytes_per_token(config) == 8 * 16384
    assert shapes.pool_bytes_per_token(config) == 8 * 1024  # 1 KB a layer
    assert shapes.eva_window_bytes_per_slot(config) == 8 * 2048 * 16384
    assert shapes.eva_window_bytes_per_slot(config) == 268435456  # 268 MB
    # a context of 16,384: 1,024 summary rows x 16,384 B x 8 = 134 MB
    assert 1024 * shapes.kv_bytes_per_token(config) == 134217728
    assert shapes.eva_decode_flops_per_row(config) == 32 * 2 * 2 * 128
    # 1 flop a byte: the decode launch is pure bandwidth
    assert shapes.eva_decode_flops_per_row(config) == (
        shapes.eva_row_bytes(config))


def test_serve_takes_the_cut_and_the_program_has_every_checked_size():
    from vgate_tpu.models import specs
    from vgate_tpu.runtime.kv_cache import _page_bytes

    config = manifest.cell(CELL)["config"]
    name = config["program"]["model_id"].lower()
    try:
        serve.register(config, rehearse=False)
        spec = specs.spec_for_model_id(config["program"]["model_id"])
        assert (spec.num_layers, spec.eva_layers, spec.attn_layers,
                spec.cache_row_tokens, spec.num_pred_heads) == (8, 8, 8, 16, 8)
        assert spec.fp32_residual and spec.unit_offset_norm
        assert hash(spec) is not None  # a static jit argument
        # 8 x 202.4 M + 1.3 M + 10.5 M and the final norm
        assert spec.num_params == 8 * 202391552 + 9 * 320 * 4096 + 4096
        for wrong in ({"window_size": 1024}, {"chunk_size": 8},
                      {"num_pred_heads": 1}, {"fp32_skip_add": False},
                      {"num_hidden_layers": 32}):
            with pytest.raises(SystemExit):
                serve.check(dict(config, **wrong), spec)
        assert 32 * shapes.kv_bytes_per_token(config) == _page_bytes(
            spec.attn_layers, 32, spec.cache_heads, spec.cache_head_dim, 2,
            0, spec.kv_pools)
    finally:
        specs._PRESETS.pop(name, None)


def test_the_rehearsals_model_is_the_tiny_preset_with_a_wider_window():
    import dataclasses

    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    tiny = config["rehearse"]["model"]
    spec = dataclasses.replace(
        specs.TINY_EVA, **config["rehearse"]["overrides"])
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(spec, attr), key
            checked += 1
    assert checked >= 12


def trace_ctx(names):
    config = manifest.cell(CELL)["config"]
    return {
        "config": config, "attn_layers": 8,
        "kv_bytes_per_token": 8 * 16384,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {n: s for n, (s, _) in names.items()},
                  "op_counts": {n: c for n, (_, c) in names.items()}},
    }


def metric_args(name):
    return manifest.metric(name)["args"]


def eva_totals(n, window=20 * 1024, chunks=20 * 750):
    """A step of 20 slots reads 1,024 window rows and 750 summary rows a
    slot and layer, and writes a row of each."""
    return {"totals": {"eva": {
        "decode_steps": 1000 * n, "window_rows_read": 8 * window * 1000 * n,
        "chunk_rows_read": 8 * chunks * 1000 * n,
        "window_rows_written": 8 * 20 * 1000 * n,
        "chunk_rows_written": 8 * 20 * 1000 * n, "windows_closed": 9 * n}}}


def test_eva_metrics_on_a_made_up_trace():
    """128 decode launches = 16 steps of 8 layers; the window's counters
    say a step and layer reads 20 x 1,774 rows and writes 20 (35,500 x
    16,384 B = 581.6 MB, 710 us at 819 GB/s; 1 flop a byte: the
    operations need 3 us)."""
    decode = "jit__decode_chunk/paged_decode_attention_pallas.3"
    prompt = "jit__prefill_step/eva_prefill_attention_pallas.6"
    least = 20 * (1024 + 750 + 1) * 16384 / 819e9
    ctx = trace_ctx({decode: (128 * 2 * least, 128), prompt: (0.2, 16),
                     "jit__decode_chunk/fusion.1": (0.1, 9)})
    ctx["perf"] = {"open": eva_totals(1), "close": eva_totals(3)}
    roof = lambda: eva_roofline.reduce(
        ctx, **metric_args("kernel.eva_decode_roofline.tok"))
    assert roof() == pytest.approx(50.0)
    share = lambda name: trace_share.reduce(ctx, **metric_args(name))
    # the decode launch is the paged decode kernel under its own name:
    # the accepted share and step time read it here as in a dense cell
    assert share("kernel.decode_attn_share.tok") == pytest.approx(
        100 * 128 * 2 * least)
    assert share("kernel.eva_prefill_share.tok") == pytest.approx(20.0)
    # the other accepted kernel shares read none of these launches
    for name in ("kernel.prefill_attn_share.tok",
                 "kernel.swa_decode_share.tok"):
        assert share(name) == 0.0, name
    assert perf_ratio.reduce(
        ctx, **metric_args("eva.chunk_read_share.tok")
    ) == pytest.approx(100 * 750 / 1774)
    step = trace_step_ms.reduce(
        ctx, **metric_args("model.decode_step_ms.tok"))
    assert step == pytest.approx(1000 * (128 * 2 * least + 0.1) / 16)
    # at the chip's peak bandwidth over the counted rows: 100 %.  More is
    # impossible by construction: the counters hold the rows a step HAS
    # to read (live rows, real lengths), a launch reads at least those,
    # and none moves a byte faster than the peak
    ctx["trace"]["op_seconds"][decode] = 128 * least
    assert roof() == pytest.approx(100.0)
    # a program that reads whole pages where rows were live reads under
    ctx["trace"]["op_seconds"][decode] = 128 * least * 1.02
    assert 97 < roof() < 100
    # the parent's program has no such counters: nothing, and no error
    ctx["perf"] = {"open": {"totals": {}}, "close": {"totals": {}}}
    assert roof() is None
    assert perf_ratio.reduce(
        ctx, **metric_args("eva.chunk_read_share.tok")) is None
    ctx["perf"] = {"open": eva_totals(1), "close": eva_totals(3)}
    other = manifest.load_json(
        manifest.HERE, "configs", "k-exaone-236b-a23b-l5e16.json")
    args = metric_args("kernel.eva_decode_roofline.tok")
    assert eva_roofline.reduce(dict(ctx, config=other), **args) is None
    assert eva_roofline.reduce(dict(ctx, trace=None), **args) is None


def test_the_dense_long_context_cell_is_data_files_alone():
    cell = manifest.cell(DENSE_CELL)
    assert cell["entry"]["config"] == "qwen2.5-1.5b"
    assert cell["params"] == {"clients": 256, "resumed": 256}
    traffic, config = cell["traffic"], cell["config"]
    assert (traffic["loop"], traffic["sharing"]["kind"],
            traffic["lead_in_s"], traffic["requests_per_client"]) == (
                "closed", "none", 45.0, 8)
    longest = traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"]
    assert longest == 1856 < int(
        config["server"]["env"]["VGT_MODEL__MAX_MODEL_LEN"])
    assert traffic["prompt_tokens"]["lo"] > 1024  # the 2,048 bucket alone
    bench = cell["bench"]
    assert manifest.metric_names(bench, DENSE_CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    # every per-layer metric decode-heavy reports but the one whose list
    # tests/perfbench/test_perfbench_delivery.py pins to two cells
    assert manifest.metric_names(bench, DENSE_CELL, "per_layer") == [
        n for n in manifest.metric_names(
            bench, "qwen2.5-1.5b.decode-heavy", "per_layer")
        if n != "gateway.tokens_per_delivery.tok"]
