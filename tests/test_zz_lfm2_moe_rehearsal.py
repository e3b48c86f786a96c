"""The LFM2 cell rehearsed end to end on the CPU: ``tiny-lfm2-moe``
behind the real gateway, every phase of a run, ``correct: true`` against
the configuration's own plain reference (its prompts are 24, 200 and
1,502 tokens: under a page of 32 on the chip, a few hundred, and one
that puts a page boundary inside the decode steps).  Kept apart from
``tests/perfbench/`` and named to run last, as
``tests/test_zz_hybrid_rehearsal.py`` is and for its reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "lfm2-24b-a2b-e8.decode-heavy"
# alone the run takes 90 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 900
# a window of 12 s, not the older rehearsals' 4: on a loaded host a
# request's answer takes longer than 4 s and the window then closes with
# nothing attempted (PERF.md section 7, PR 43 item 2)
WINDOW_S = "12"


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "4000000047", "--seconds", WINDOW_S, "--trace", "1",
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
    assert got["device.state_gb.tok"]["value"] > 0  # the tails
    # the tiny preset holds all 8 of its 8 experts
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0
    assert got["moe.lfm2_load_max_over_mean.tok"]["value"] > 0
    for name in ("kernel.short_conv_roofline.tok",  # no device metric
                 "kernel.short_conv_share.tok", "model.conv_mixer_share.tok",
                 "kernel.decode_attn_roofline.tok"):
        assert name not in got
    assert result["in_window"]["compiled"] == 0
