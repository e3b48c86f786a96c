"""The Granite cell rehearsed end to end on the CPU:
``tiny-granite-hybrid`` behind the real gateway, every phase of a run,
``correct: true`` against the configuration's own plain reference (its
prompts are 24, 100 and 318 tokens: under a page of 32 on the chip, most
of the 128 bucket, and one that puts a page boundary inside the decode
steps).  Kept apart from ``tests/perfbench/`` and named to run last, as
``tests/test_zz_hybrid_rehearsal.py`` is and for its reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "granite-4.0-h-micro.decode-heavy"
# alone the run takes 60 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 900
# a window of 12 s: on a loaded host a request's answer takes longer
# than 4 s and the window then closes with nothing attempted (PERF.md
# section 7, PR 43 item 2)
WINDOW_S = "12"


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "4000000051", "--seconds", WINDOW_S, "--trace", "1",
         "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=TIME_LIMIT_S,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, {
        k: result[k] for k in ("attempted", "failed", "reference",
                               "in_window")}
    assert result["attempted"] >= 1 and result["rehearsal"] is True
    assert result["reference"]["ok"]
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
