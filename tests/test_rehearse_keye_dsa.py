"""The Keye-VL-2.0 cell rehearsed end to end on the CPU:
``tiny-keye-dsa`` behind the real gateway (its prompts are 24, 2,500 and
6,014 tokens: just past the 16 picked, and far past them, with a page
boundary inside the decode steps; alone the run takes 150 s)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("keye-vl-2.0-30b-a3b-l12e32.long-agent", 4000000053)
    assert result["reference"]["compared"] == 120  # 3 x 5 x 8
    # float32 on both sides; a near-tie at the 16th pick may fall the
    # other way in one row of thousands and move that row in the fourth
    # digit (1.4e-3 at most, 3.4e-5 in the mean: the configuration's
    # ``tolerance_why``)
    assert result["reference"]["max_abs_diff"] < 1e-2
    assert result["reference"]["mean_abs_diff"] < 1e-3
    got = result["metrics"]
    assert 0 < got["dsa.selected_share.tok"]["value"] < 5  # 16 of ≈ 1,500
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    for name in ("kernel.dsa_attend_roofline.tok",  # no device metric
                 "kernel.dsa_index_roofline.tok",
                 "kernel.moe_experts_roofline.tok",
                 "model.dsa_decode_step_ms.tok",
                 # nor another configuration's
                 "model.dense_mlp_share.tok", "device.state_gb.tok"):
        assert name not in got
    assert result["in_window"]["compiled"] == 0
