"""The LFM2 cell rehearsed end to end on the CPU: ``tiny-lfm2-moe``
behind the real gateway (its prompts are 24, 200 and 1,502 tokens: under
a page of 32 on the chip, a few hundred, and one that puts a page
boundary inside the decode steps)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("lfm2-24b-a2b-e8.decode-heavy", 4000000047)
    assert result["reference"]["compared"] == 120  # 3 x 5 x 8
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    assert got["device.state_gb.tok"]["value"] > 0  # the tails
    # the tiny preset holds all 8 of its 8 experts
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0
    assert got["moe.lfm2_load_max_over_mean.tok"]["value"] > 0
    for name in ("kernel.short_conv_roofline.tok",  # no device metric
                 "kernel.short_conv_share.tok", "model.conv_mixer_share.tok",
                 "kernel.decode_attn_roofline.tok"):
        assert name not in got
    assert result["in_window"]["compiled"] == 0
