"""The Nemotron-H cell rehearsed end to end on the CPU:
``tiny-nemotron-h`` behind the real gateway, every phase of a run,
``correct: true`` against the configuration's own plain reference (its
third prompt is 320 tokens: hundreds of updates of the state).  Kept
apart from ``tests/perfbench/`` and named to run last, as
``tests/test_zz_hybrid_rehearsal.py`` is and for its reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "nemotron-3-super-120b-a12b-l11e128.decode-heavy"
# alone the run takes 57 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 1200


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "4", "--trace", "1",
         "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=TIME_LIMIT_S,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    assert result["reference"]["ok"]
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
