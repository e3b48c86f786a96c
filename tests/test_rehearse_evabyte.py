"""The EvaByte cell rehearsed end to end on the CPU: ``tiny-eva`` (with
windows of 512 and chunks of 16: ``rehearse.overrides``) behind the real
gateway (its prompts are 24, 2,045 and 8,200 bytes: inside one window,
closing a window of 512 several times over, and sixteen windows through
the 16,384 bucket)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("evabyte-6.5b-l8.long-agent", 4000000044)
    assert result["reference"]["compared"] == 1536  # 3 x 64 x 8
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    assert got["device.state_gb.tok"]["value"] > 0
    # contexts of 1-2 k bytes over windows of 512: a few closed windows
    assert 5 < got["eva.chunk_read_share.tok"]["value"] < 50
    assert "kernel.eva_decode_roofline.tok" not in got  # no device metric
    assert "model.eva_summarize_share.tok" not in got
    assert result["in_window"]["compiled"] == 0
