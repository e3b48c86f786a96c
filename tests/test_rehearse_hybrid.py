"""The Qwen3-Next cell rehearsed end to end on the CPU: ``tiny-hybrid``
behind the real gateway (``family_contract.rehearse`` has the body every
family's rehearsal shares)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("qwen3-next-80b-a3b-l8e128.decode-heavy", 3000000019)
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["device.state_gb.tok"]["value"] > 0
    # the closed loop's ends and joins edit the decode state's rows (PR 32)
    assert 0.0 <= got["engine.drain_share.tok"]["value"] < 50.0
    assert "kernel.gdn_step_roofline.tok" not in got  # no device metric
