"""The new cell rehearsed end to end on the CPU: ``tiny-hybrid`` behind
the real gateway, every phase of a run, ``correct: true`` against the
configuration's own plain reference.  Kept apart from
``tests/perfbench/`` and named to run last: it starts a server whose
compiles would starve the dense cells' rehearsals running beside it."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "qwen3-next-80b-a3b-l8e128.decode-heavy"


def rehearse(workload, trace, seconds):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", workload,
         "--seed", "3000000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900,
    )


def test_the_cell_rehearses_correct():
    proc = rehearse(CELL, "1", seconds="4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    assert result["reference"]["ok"]
    assert result["reference"]["max_abs_diff"] < 1e-4  # float32 both sides
    got = result["metrics"]
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0  # tiny
    assert got["device.state_gb.tok"]["value"] > 0
    # the closed loop's ends and joins edit the decode state's rows (PR 32)
    assert 0.0 <= got["engine.drain_share.tok"]["value"] < 50.0
    assert "kernel.gdn_step_roofline.tok" not in got  # no device metric


