"""The EvaByte cell rehearsed end to end on the CPU: ``tiny-eva`` (with
windows of 512 and chunks of 16: ``rehearse.overrides``) behind the real
gateway, every phase of a run, ``correct: true`` against the
configuration's own plain reference (its prompts are 24, 2,045 and 8,200
bytes: inside one window, closing a window of 512 several times over,
and sixteen windows through the 16,384 bucket).  Kept apart from
``tests/perfbench/`` and named to run last, as
``tests/test_zz_hybrid_rehearsal.py`` is and for its reason."""

import json
import os
import subprocess
import sys

from perfbench import manifest

CELL = "evabyte-6.5b-l8.long-agent"
# alone the run takes 85 s; beside five other workers a rehearsal has
# taken five times its time alone (CHANGES.md, PR 31)
TIME_LIMIT_S = 900
# a window of 12 s, not the other rehearsals' 4: on a loaded host a
# request's answer of ~200 bytes takes longer than 4 s and the window
# then closes with nothing attempted (PERF.md section 7, PR 43 item 2)
WINDOW_S = "12"


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "4000000044", "--seconds", WINDOW_S, "--trace", "1",
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
