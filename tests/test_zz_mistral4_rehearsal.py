"""The Mistral-Small-4 cell rehearsed end to end on the CPU:
``tiny-mla-moe`` behind the real gateway, every phase of a run,
``correct: true`` against the configuration's own plain reference in the
NON-absorbed form (its third prompt is 1,500 tokens: 94 latent pages
under the four absorbed decode steps, far past the tiny preset's
original maximum of 32).  Kept apart from ``tests/perfbench/`` and named
to run last, as ``tests/test_zz_hybrid_rehearsal.py`` is and for its
reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "mistral-small-4-119b-l4e32.long-prompt"
# alone the run takes 85 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 1200


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "3000000033", "--seconds", "4", "--trace", "1",
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
    assert got["moe.l4e32_load_max_over_mean.tok"]["value"] > 0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    assert "kernel.mla_decode_roofline.tok" not in got  # no device metric
    assert "device.state_gb.tok" not in got  # no recurrent state
    assert result["in_window"]["compiled"] == 0
