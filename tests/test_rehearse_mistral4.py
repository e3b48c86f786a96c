"""The Mistral-Small-4 cell rehearsed end to end on the CPU:
``tiny-mla-moe`` behind the real gateway, against the reference in the
NON-absorbed form (its third prompt is 1,500 tokens: 94 latent pages
under the four absorbed decode steps, far past the tiny preset's
original maximum of 32)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("mistral-small-4-119b-l4e32.long-prompt", 3000000033)
    assert result["reference"]["compared"] == 120
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["moe.l4e32_load_max_over_mean.tok"]["value"] > 0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    assert "kernel.mla_decode_roofline.tok" not in got  # no device metric
    assert "device.state_gb.tok" not in got  # no recurrent state
    assert result["in_window"]["compiled"] == 0
