"""The K-EXAONE cell rehearsed end to end on the CPU: ``tiny-swa-moe``
behind the real gateway (its third prompt is 1,502 tokens: 47 rings of
32 tokens under the decode steps, a page boundary inside them)."""

import pytest

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("k-exaone-236b-a23b-l5e16.long-prompt", 3800000033)
    assert result["reference"]["compared"] == 120
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["moe.l5e16_load_max_over_mean.tok"]["value"] > 0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    # the rings: seven window layers x 32 tokens x K and V of 2 x 16 x 4 B
    assert got["device.state_gb.tok"]["value"] * 1e9 == pytest.approx(
        8 * 7 * 32 * 2 * 2 * 16 * 4)
    assert "kernel.swa_decode_roofline.tok" not in got  # no device metric
    assert "model.dense_mlp_share.tok" not in got
    assert result["in_window"]["compiled"] == 0
