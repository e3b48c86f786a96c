"""The Nemotron-H cell rehearsed end to end on the CPU:
``tiny-nemotron-h`` behind the real gateway (its third prompt is 320
tokens: hundreds of updates of the state)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse(
        "nemotron-3-super-120b-a12b-l11e128.decode-heavy", 3000000019)
    assert result["reference"]["compared"] == 120
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["moe.latent_load_max_over_mean.tok"]["value"] > 0
    assert got["device.state_gb.tok"]["value"] > 0
    # the closed loop's ends and joins edit the decode state's rows (PR 32)
    assert 0.0 <= got["engine.drain_share.tok"]["value"] < 50.0
    assert "kernel.ssd_step_roofline.tok" not in got  # no device metric
    assert result["in_window"]["compiled"] == 0
