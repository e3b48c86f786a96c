"""The K-EXAONE cell rehearsed end to end on the CPU: ``tiny-swa-moe``
behind the real gateway, every phase of a run, ``correct: true`` against
the configuration's own plain reference (its third prompt is 1,502
tokens: 47 rings of 32 tokens under the decode steps, a page boundary
inside them).  Kept apart from ``tests/perfbench/`` and named to run
last, as ``tests/test_zz_hybrid_rehearsal.py`` is and for its reason."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

CELL = "k-exaone-236b-a23b-l5e16.long-prompt"
# alone the run takes 60 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 1200


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "3800000033", "--seconds", "4", "--trace", "1",
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
    assert got["moe.l5e16_load_max_over_mean.tok"]["value"] > 0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    # the rings: seven window layers x 32 tokens x K and V of 2 x 16 x 4 B
    assert got["device.state_gb.tok"]["value"] * 1e9 == pytest.approx(
        8 * 7 * 32 * 2 * 2 * 16 * 4)
    assert "kernel.swa_decode_roofline.tok" not in got  # no device metric
    assert "model.dense_mlp_share.tok" not in got
    assert result["in_window"]["compiled"] == 0
