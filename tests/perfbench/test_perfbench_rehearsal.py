"""The harness end to end on the CPU: the tiny preset behind the real
gateway, every phase of a run, the contract's last line."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest


def rehearse(workload, trace, seconds="3", cwd=manifest.ROOT):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", workload,
         "--seed", "3000000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900,
    )


def test_rehearsal_prints_the_contracts_line():
    proc = rehearse("qwen2.5-1.5b.decode-heavy", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"  # named, never hidden
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["metrics"]["out_tok_s"]["unit"] == "tokens/s/chip"
    assert result["reference"]["ok"] and result["reference"]["compared"] > 0
    assert result["in_window"]["compiled"] == 0


def test_a_cell_the_manifest_does_not_know_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "nope.chat",
         "--seed", "1", "--seconds", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_without_a_tpu_a_measurement_fails_and_prints_no_result():
    """No --rehearse here: the server is told ``platform: tpu`` and must
    not come up on this CPU-only machine."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "qwen2.5-1.5b.chat", "--seed", "1", "--seconds", "1"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert "FAILED" in proc.stderr


@pytest.mark.parametrize("workload", ["qwen2.5-1.5b.chat",
                                      "qwen2.5-7b-l14.prefill-heavy"])
def test_without_the_program_there_is_no_result(tmp_path, workload):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths: the system under test is missing, so nothing is printed."""
    import shutil

    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = rehearse(workload, "0", cwd=str(tmp_path))
    assert proc.returncode != 0
    assert not proc.stdout.strip()
