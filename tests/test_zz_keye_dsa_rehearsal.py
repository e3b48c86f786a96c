"""The Keye-VL-2.0 cell rehearsed end to end on the CPU:
``tiny-keye-dsa`` behind the real gateway, every phase of a run,
``correct: true`` against the configuration's own plain reference (its
prompts are 24, 2,500 and 6,014 tokens: just past the 16 picked, and far
past them, with a page boundary inside the decode steps).

Marked ``slow``, unlike the other configurations' rehearsals: alone it
takes 150 s, and the tier-1 command already spends 1,205 s of its 1,470
(ISSUE 53) with eight rehearsals at its end; beside five other workers a
rehearsal has taken five times its time alone (CHANGES.md, PR 31).  The
builder ran it (CHANGES.md, PR 53):

    JAX_PLATFORMS=cpu python -m pytest -m slow tests/test_zz_keye_dsa_rehearsal.py
"""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

pytestmark = pytest.mark.slow

CELL = "keye-vl-2.0-30b-a3b-l12e32.long-agent"
TIME_LIMIT_S = 1400


def test_the_cell_rehearses_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "4000000053", "--seconds", "12", "--trace", "1",
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
    # float32 on both sides; a near-tie at the 16th pick may fall the
    # other way in one row of thousands and move that row in the fourth
    # digit (1.4e-3 at most, 3.4e-5 in the mean: the configuration's
    # ``tolerance_why``)
    assert result["reference"]["max_abs_diff"] < 1e-2
    assert result["reference"]["mean_abs_diff"] < 1e-3
    got = result["metrics"]
    assert 0 < got["dsa.selected_share.tok"]["value"] < 5  # 16 of ≈ 1,500
    assert got["moe.held_assignment_share.tok"]["value"] == 100.0
    assert got["scheduler.pool_fill.tok"]["value"] > 0
    for name in ("kernel.dsa_attend_roofline.tok",  # no device metric
                 "kernel.dsa_index_roofline.tok",
                 "kernel.moe_experts_roofline.tok",
                 "model.dsa_decode_step_ms.tok",
                 # nor another configuration's
                 "model.dense_mlp_share.tok", "device.state_gb.tok"):
        assert name not in got
    assert result["in_window"]["compiled"] == 0
