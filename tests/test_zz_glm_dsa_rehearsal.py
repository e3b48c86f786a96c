"""The GLM-5.2 cell rehearsed end to end on the CPU: ``tiny-dsa-moe``
behind the real gateway, every phase of a run, ``correct: true`` against
the configuration's own plain reference (its prompts are 24, 2,500 and
6,014 tokens: under the 16 picked, and far past them, with a page
boundary inside the decode steps).  Kept apart from ``tests/perfbench/``
and named to run last, as ``tests/test_zz_hybrid_rehearsal.py`` is and
for its reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "glm-5.2-l5e16.long-agent"
# alone the run takes 260 s (the reference's 6,014-token prompt is most of
# it); beside five other workers a rehearsal has taken five times its
# time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 1400


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "4000000040", "--seconds", "4", "--trace", "1",
         "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=TIME_LIMIT_S,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, {
        k: result[k] for k in ("attempted", "failed", "reference",
                               "in_window")}
    assert result["attempted"] > 0 and result["rehearsal"] is True
    assert result["reference"]["ok"]
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
