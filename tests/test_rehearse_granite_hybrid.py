"""The Granite cell rehearsed end to end on the CPU:
``tiny-granite-hybrid`` behind the real gateway (its prompts are 24, 100
and 318 tokens: under a page of 32 on the chip, most of the 128 bucket,
and one that puts a page boundary inside the decode steps)."""

from tests.family_contract import rehearse


def test_the_cell_rehearses_correct():
    result = rehearse("granite-4.0-h-micro.decode-heavy", 4000000051)
    assert result["reference"]["compared"] == 120  # 3 x 5 x 8
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    # 8 slots x 4 Mamba-2 layers x (a [4, 16, 16] tile + a 3 x 96 tail)
    assert got["device.state_gb.tok"]["value"] == 8 * 4 * (4096 + 1152) / 1e9
    for name in ("kernel.ssd_step_roofline.tok",  # no device metric
                 "kernel.ssd_step_share.tok", "model.dense_mlp_share.tok",
                 "kernel.decode_attn_roofline.tok",
                 # nor a metric of an expert layer: the stack has none
                 "moe.held_assignment_share.tok",
                 "kernel.moe_experts_share.tok"):
        assert name not in got
    assert result["in_window"]["compiled"] == 0
