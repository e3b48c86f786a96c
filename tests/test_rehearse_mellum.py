"""The Mellum2 cell rehearsed end to end on the CPU: ``tiny-mellum``
behind the real gateway (its prompts of 200, 1,100 and 4,222 tokens are
6, 34 and 131 rings of 32 tokens, the last with a page boundary inside
the decode steps; the full layers under YaRN, the window layers not)."""

import pytest

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("mellum2-12b-a2.5b-l8.long-agent", 2147483777)
    assert result["reference"]["compared"] == 120
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    # the rings: six window layers x 32 tokens x K and V of 2 x 16 x 4 B
    assert got["device.state_gb.tok"]["value"] * 1e9 == pytest.approx(
        4 * 6 * 32 * 2 * 2 * 16 * 4)
    assert "kernel.swa_decode_roofline.tok" not in got  # no device metric
    assert "model.dense_mlp_share.tok" not in got
    assert "moe.l5e16_load_max_over_mean.tok" not in got
    assert result["in_window"]["compiled"] == 0
