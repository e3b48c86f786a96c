"""The GLM-5.2 cell rehearsed end to end on the CPU: ``tiny-dsa-moe``
behind the real gateway (its prompts are 24, 2,500 and 6,014 tokens:
under the 16 picked, and far past them, with a page boundary inside the
decode steps; alone the run takes 260 s, most of it the reference's
6,014-token prompt)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("glm-5.2-l5e16.long-agent", 4000000040)
    assert result["reference"]["compared"] == 120
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["moe.glm52_load_max_over_mean.tok"]["value"] > 0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    # 16 of a context of a thousand or two: a percent or so is attended
    assert 0 < got["dsa.selected_share.tok"]["value"] < 5
    assert "kernel.dsa_attend_roofline.tok" not in got  # no device metric
    assert "model.dsa_prefill_share.tok" not in got
    assert result["in_window"]["compiled"] == 0
