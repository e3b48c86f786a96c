"""The Granite configuration's files: the plain reference on cases worked
out by hand, the manifest's contract with the new cell, the shapes
module against ``ModelSpec``'s own counts (a slot's state 76.4 MB, a
token 8,192 B), and the metrics the cell takes from the benchmark as it
stands (no metric file is its own) on a synthetic trace."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, serve, shapes_granite_hybrid as shapes
from perfbench.reducers import gdn_step_roofline, scope_share
from perfbench.references import granite_hybrid as ref

CELL = "granite-4.0-h-micro.decode-heavy"
NEMOTRON = "nemotron-3-super-120b-a12b-l11e128.decode-heavy"
F32 = jnp.float32


def small_cfg(**over):
    cfg = {"hidden_size": 4, "vocab_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "shared_intermediate_size": 4,
           "mamba_n_heads": 2, "mamba_d_head": 2, "mamba_d_state": 3,
           "mamba_n_groups": 1, "mamba_d_conv": 2, "mamba_conv_bias": True,
           "layer_types": ["mamba", "attention"], "num_hidden_layers": 2,
           "rms_norm_eps": 1e-6, "embedding_multiplier": 3.0,
           "attention_multiplier": 0.25, "residual_multiplier": 0.5,
           "logits_scaling": 2.0}
    cfg.update(over)
    return cfg


def test_the_recurrence_by_hand():
    """One head of size 1, a state of 2: ``S <- exp(delta A) S + delta x
    B``, ``y = S . C + D x`` with ``D = 1``, one token after another
    from zeros; B and C are ONE row that both heads read."""
    x = jnp.asarray([[[2.0], [1.0]], [[1.0], [3.0]]], F32)  # [S, H, P]
    delta = jnp.asarray([[0.5, 1.0], [1.0, 0.5]], F32)
    A = jnp.asarray([-1.0, -2.0], F32)
    B = jnp.asarray([[[1.0, 0.0]], [[0.0, 2.0]]], F32)  # [S, G = 1, N]
    C = jnp.asarray([[[1.0, 1.0]], [[1.0, 0.5]]], F32)
    got = np.asarray(ref.selective_scan(x, delta, A, B, C, jnp.ones(2)))
    e = np.exp
    # head 0: S1 = 0.5 * 2 * [1, 0] = [1, 0]; y1 = 1 + 2
    #         S2 = e(-1) [1, 0] + 1 * 1 * [0, 2]; y2 = e(-1) + 1 + 1
    # head 1: S1 = 1 * 1 * [1, 0]; y1 = 1 + 1
    #         S2 = e(-1) [1, 0] + 0.5 * 3 * [0, 2]; y2 = e(-1) + 1.5 + 3
    want = [[[3.0], [2.0]], [[e(-1.0) + 2.0], [e(-1.0) + 4.5]]]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_the_convolution_is_causal_has_a_bias_and_then_silu():
    x = jnp.asarray([[1.0, -2.0], [3.0, 0.5], [-1.0, 2.0]], F32)
    taps = jnp.asarray([[0.5, 2.0], [-1.0, 1.0]], F32)  # [C, K = 2]
    bias = jnp.asarray([0.25, -0.5], F32)
    got = np.asarray(ref.conv_silu(x, taps, bias))
    xs = np.asarray(x)
    pre = np.asarray(taps)[:, 1] * xs + np.asarray(bias)
    pre[1:] += np.asarray(taps)[:, 0] * xs[:-1]
    np.testing.assert_allclose(got, pre / (1 + np.exp(-pre)), rtol=1e-6)
    # a later row moves nothing before it
    moved = np.asarray(ref.conv_silu(x.at[2].add(5.0), taps, bias))
    np.testing.assert_allclose(moved[:2], got[:2], rtol=1e-6)


def test_attention_has_no_rotation_and_takes_the_multiplier_as_its_scale():
    """Identity projections, two query heads on one KV head of 2: the
    first row attends to itself alone; a permutation of the EARLIER rows
    leaves the last row's output where it was (no position enters:
    "nope"); the scale is ``attention_multiplier``, not ``hd ** -0.5``."""
    cfg = small_cfg(hidden_size=2, head_dim=2)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((4, 2)), F32)
    eye = jnp.eye(2, dtype=F32)
    w = {"q": jnp.concatenate([eye, eye], axis=1), "k": eye, "v": eye,
         "o": jnp.concatenate([eye, eye], axis=0)}
    got = np.asarray(ref.attention(u, w, cfg))
    np.testing.assert_allclose(got[0], 2 * np.asarray(u[0]), rtol=1e-5)
    swapped = u[jnp.asarray([1, 0, 2, 3])]
    np.testing.assert_allclose(
        np.asarray(ref.attention(swapped, w, cfg))[3], got[3], rtol=1e-5)
    q, k = np.asarray(u[3]), np.asarray(u)
    p = np.exp(0.25 * k @ q)
    want = 2 * (p / p.sum()) @ k
    np.testing.assert_allclose(got[3], want, rtol=1e-5)
    other = np.asarray(ref.attention(
        u, w, dict(cfg, attention_multiplier=2 ** -0.5)))
    assert np.abs(other[3] - got[3]).max() > 1e-3


def test_each_multiplier_sits_where_the_equations_put_it():
    """A stack of NO layers is ``log_softmax(N(m_e e) E^T / s)``: the
    embedding's multiplier falls out under the norm, the logits'
    divisor does not.  One layer: the residual multiplier scales what a
    sub-block adds, not the stream."""
    cfg = small_cfg(layer_types=[], num_hidden_layers=0)
    seq = [1, 5, 2]
    got = ref.logprobs(cfg, 0, F32, [seq], [1])[0]
    E = np.asarray(ref.embedding(cfg, 0, F32))
    h = 3.0 * E[seq[:2]]
    normed = h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-6)
    logits = normed @ E.T / 2.0
    want = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    np.testing.assert_allclose(got, want, atol=1e-6)
    same = ref.logprobs(dict(cfg, embedding_multiplier=7.0), 0, F32,
                        [seq], [1])[0]
    np.testing.assert_allclose(same, got, atol=2e-4)  # (the norm's eps)
    assert np.abs(ref.logprobs(dict(cfg, logits_scaling=1.0), 0, F32,
                               [seq], [1])[0] - got).max() > 1e-4
    one = small_cfg(layer_types=["attention"], num_hidden_layers=1)
    w = {k: v.astype(F32) for k, v in next(
        ref.layer_weights(one, 0, F32)).items()}
    h = jnp.asarray(np.random.default_rng(1).standard_normal((3, 4)), F32)
    full = np.asarray(ref.layer(h, w, dict(one, residual_multiplier=1.0),
                                "attention"))
    half = np.asarray(ref.layer(h, w, one, "attention"))
    mixed = np.asarray(ref.attention(ref.norm(h, 1e-6), w, one))
    mid = np.asarray(h) + 0.5 * mixed
    np.testing.assert_allclose(
        half, mid + 0.5 * np.asarray(ref.swiglu(
            ref.norm(jnp.asarray(mid), 1e-6), w)), rtol=1e-5)
    assert np.abs(full - half).max() > 1e-4


def test_the_reference_module_runs_as_the_harness_runs_it(tmp_path):
    """``python -m perfbench.references.granite_hybrid CONFIG JOB OUT`` at
    the rehearsal's sizes: ``main`` writes one log-probability for each
    asked id, those of ``logprobs``."""
    config = manifest.cell(CELL)["config"]
    tiny = {"name": config["name"], **config["rehearse"]["model"]}
    seqs, first = [[5, 9, 300, 17, 44, 2], [7, 8, 9, 10]], [3, 2]
    top = [[[1, 2], [3, 4], [5, 6]], [[0, 511], [100, 200]]]
    paths = [tmp_path / n for n in ("cfg.json", "job.json", "out.json")]
    paths[0].write_text(json.dumps(tiny))
    paths[1].write_text(json.dumps({
        "weights_seed": 0, "sequences": seqs, "first": first,
        "top_ids": top}))
    assert ref.main([str(p) for p in paths]) == 0
    got = json.loads(paths[2].read_text())["logprobs"]
    want = ref.logprobs(tiny, 0, F32, seqs, first)
    for g, w, ids in zip(got, want, top):
        assert len(g) == len(ids)
        for pos, (row, asked) in enumerate(zip(g, ids)):
            np.testing.assert_allclose(row, w[pos, asked], rtol=1e-6)
    assert all(np.isfinite(w).all() and (w < 0).all() for w in want)


# ---- the manifest, the configuration file, the shapes module


def test_the_manifest_and_the_cut_rules_find_nothing():
    assert manifest.problems() == []
    config = manifest.cell(CELL)["config"]
    assert manifest.cut_problems(config) == []
    assert config["reduced"] == [] and "published" not in config
    assert "chips_sharing_a_layer" not in config
    assert config["deployment"] and config["assumed"]


def test_the_file_holds_every_published_key_as_published():
    """Every key of the catalog row's ``config`` under the same name and
    at the same value (the driver refuses a key that differs and is not
    in ``reduced``, which is empty)."""
    config = manifest.cell(CELL)["config"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
    }
    for key, value in published.items():
        assert config[key] == value, key
    types = config["layer_types"]
    assert len(types) == 40 and types.count("attention") == 4
    assert [i for i, t in enumerate(types) if t == "attention"] == [
        5, 15, 25, 35]


def test_the_cell_and_the_lists_it_joined():
    cell = manifest.cell(CELL)
    bench, entry = cell["bench"], cell["entry"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "granite-4.0-h-micro", "decode-heavy", 1)
    assert cell["params"] == {"clients": 100, "resumed": 80}
    env = cell["config"]["server"]["env"]
    slots = int(env["VGT_TPU__MAX_BATCH_SLOTS"])
    assert slots == 80 and cell["params"]["clients"] == 1.25 * slots
    assert cell["params"]["resumed"] == slots
    assert manifest.metric_names(bench, CELL, "end_to_end") == [
        "out_tok_s", "setup_s"]
    mine = set(manifest.metric_names(bench, CELL, "per_layer"))
    theirs = set(manifest.metric_names(bench, NEMOTRON, "per_layer"))
    # what the other Mamba-2 cell reports, but its expert layers'
    assert theirs - mine == {
        "kernel.moe_experts_share.tok", "moe.held_assignment_share.tok",
        "kernel.latent_experts_roofline.tok",
        "moe.latent_load_max_over_mean.tok"}
    assert mine - theirs == {"model.dense_mlp_share.tok"}
    for name in ("kernel.ssd_step_share.tok", "kernel.ssd_step_roofline.tok",
                 "device.state_gb.tok", "model.window_decode_step_ms.tok"):
        assert name in mine
    traffic = cell["traffic"]
    assert traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] < (
        int(env["VGT_MODEL__MAX_MODEL_LEN"]))
    ref_cfg = cell["config"]["reference"]
    assert ref_cfg["prompt_tokens"] == [24, 100, 318]
    assert ref_cfg["tolerance_why"] and ref_cfg["module"].endswith(
        "granite_hybrid")
    assert 0 < ref_cfg["mean_tolerance"] < ref_cfg["tolerance"] < 1


def test_the_shapes_module_counts_what_the_program_holds():
    from vgate_tpu.models import hybrid, specs

    config = manifest.cell(CELL)["config"]
    spec = specs.spec_for_model_id(config["program"]["preset"])
    packed = spec.pack_kv_heads()
    assert shapes.attn_layers(config) == spec.attn_layers == 4
    assert shapes.linear_layers(config) == spec.linear_layers == 36
    assert shapes.head_dim(config) == spec.head_dim == 64
    assert shapes.kv_bytes_per_token(config) == 8192
    # the program's page is what the shapes module says: no padding lane
    assert 32 * shapes.kv_bytes_per_token(config) == (
        packed.kv_pools * packed.attn_layers * 32 * packed.cache_heads
        * packed.cache_head_dim * 2)
    assert shapes.state_bytes_per_slot_layer(config) == 2_097_152 == (
        spec.mamba_num_heads * spec.mamba_head_dim * spec.mamba_state_size
        * 4)
    assert shapes.tail_bytes_per_slot_layer(config) == 26_112 == (
        (spec.mamba_conv_kernel - 1) * spec.mamba_conv_dim * 2)
    assert shapes.state_bytes_per_slot(config) == 76_437_504 == (
        hybrid.state_bytes_per_slot(packed, 2, 32))
    # the arithmetic the file's ``why`` states: the state, not the
    # pages, sets the batch
    slots = int(config["server"]["env"]["VGT_TPU__MAX_BATCH_SLOTS"])
    state = slots * shapes.state_bytes_per_slot(config)
    pages = slots * 2048 * shapes.kv_bytes_per_token(config)
    assert abs(state - 6.115e9) < 1e6 and abs(pages - 1.342e9) < 1e6
    assert state / (state + pages) > 0.8
    assert 2 * spec.num_params + state + pages < 0.9 * 16.9e9
    # the tiny preset under the same functions
    tiny = config["rehearse"]["model"]
    small = specs.spec_for_model_id(config["rehearse"]["preset"])
    assert shapes.linear_layers(tiny) == small.linear_layers == 4
    assert shapes.attn_layers(tiny) == small.attn_layers == 1
    assert shapes.state_bytes_per_slot(tiny, "float32") == (
        hybrid.state_bytes_per_slot(small, 4, 16))
    assert shapes.kv_bytes_per_token(tiny, "float32") == 2 * 2 * 16 * 4


def test_the_program_has_every_checked_size_and_serve_would_hold_it():
    from vgate_tpu.models import specs

    config = manifest.cell(CELL)["config"]
    assert config["program"]["overrides"] == {}  # ``python main.py``
    spec = specs.spec_for_model_id(config["program"]["preset"])
    serve.check(dict(config, _path="granite"), spec)
    assert hash(spec) is not None  # a static jit argument
    assert max(spec.eos_token_id, spec.bos_token_id,
               *spec.extra_stop_ids, 0) < spec.vocab_size
    # a preset that lost a multiplier, or forty other layers, is refused
    for key, value in (("residual_multiplier", 1.0), ("logits_scaling", 16),
                       ("attention_multiplier", 0.125),
                       ("embedding_multiplier", 1),
                       ("layer_types", ["mamba"] * 40),
                       ("mamba_n_groups", 8),
                       ("position_embedding_type", "rope")):
        with pytest.raises(SystemExit):
            serve.check(dict(config, _path="granite", **{key: value}), spec)
    tiny, small = config["rehearse"]["model"], specs.TINY_GRANITE_HYBRID
    checked = 0
    for key, attr in serve.checked_keys(config).items():
        if key in tiny:
            assert tiny[key] == getattr(small, attr), key
            checked += 1
    assert checked >= 20


# ---- the metrics it reports, on a synthetic trace


def test_the_step_kernels_roofline_reads_this_cells_bytes(monkeypatch):
    """288 launches of the step kernel = 8 steps of 36 Mamba-2 layers;
    the decode spans say a step ran 78 rows: 8 x 78 x 36 x 2 x 2,097,152
    B = 94.2 GB, 115 ms at 819 GB/s; 164.3 ms of kernel time read 70 %.
    The same trace under Nemotron's shapes module reads its own bytes."""
    config = manifest.cell(CELL)["config"]
    kernel = "jit__decode_chunk/ssd_step_pallas.3"
    least = 8 * 78 * 36 * 2 * 2_097_152 / 819e9
    ctx = {
        "config": config,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"devices": [{"busy_s": 1.0}],
                  "op_seconds": {kernel: least / 0.7,
                                 "jit__decode_chunk/fusion.9": 0.3},
                  "op_counts": {kernel: 288, "jit__decode_chunk/fusion.9": 5}},
    }
    monkeypatch.setattr(
        gdn_step_roofline.trace_spans, "load",
        lambda ctx: {"decode": [{"steps": 8, "rows": 76},
                                {"steps": 8, "rows": 80},
                                {"steps": 8, "rows": None}]})
    args = manifest.metric("kernel.ssd_step_roofline.tok")["args"]
    assert gdn_step_roofline.reduce(ctx, **args) == pytest.approx(
        70.0, rel=1e-6)
    other = dict(ctx, config=manifest.cell(NEMOTRON)["config"])
    # 288 launches are 57.6 steps of ITS five layers of 4.19 MB
    assert gdn_step_roofline.reduce(other, **args) == pytest.approx(
        70.0 * 2, rel=1e-6)
    scopes = {"busy_s": 1.0, "scope_seconds": {"dense_mlp": 0.22,
                                               "ssm": 0.6, "ssm/conv": 0.02}}
    monkeypatch.setattr(scope_share, "summary", lambda ctx: scopes)
    assert scope_share.reduce(
        ctx, **manifest.metric("model.dense_mlp_share.tok")["args"]
    ) == pytest.approx(22.0)
