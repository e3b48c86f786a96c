"""chip_smoke.py, checked where there is no chip: the rehearsal mode runs
the whole script on the CPU, the parent stays off JAX, and the compile
cache is placed the way the chip tool needs it."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("VGT_")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_rehearsal_runs_end_to_end(tmp_path):
    """``--rehearse-cpu`` drives every phase (server, second boot from
    the compile cache, kernels) and marks every line it writes; it never
    prints the result line a chip run ends with."""
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, SMOKE, "--rehearse-cpu"],
        cwd=REPO, env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = proc.stdout.splitlines()
    assert lines and all(
        line.startswith("REHEARSAL platform=cpu ") for line in lines
    ), proc.stdout
    assert not any('"ok"' in line for line in lines)
    for phase in ("server:", "server_boot2:", "kernels:"):
        assert any(phase in line for line in lines), phase
    # JAX_COMPILATION_CACHE_DIR was set: the entries went THERE
    assert any(cache.iterdir())


def test_without_an_accelerator_the_smoke_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, env=_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_parent_never_imports_jax():
    """A process that has touched JAX holds the chip, so everything the
    parent imports must stay off it."""
    code = (
        "import sys; import chip_smoke; chip_smoke.cache_dir(); "
        "from vgate_tpu.observability.roofline import DEVICE_PEAKS; "
        "assert 'jax' not in sys.modules, 'parent imported jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------- apply_compile_cache

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: calls.append((key, value))
    )
    return calls


def test_compile_cache_env_set_sets_no_directory_in_code(
    monkeypatch, config_updates, tmp_path
):
    from vgate_tpu.config import apply_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert apply_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_compile_cache_unset_uses_one_fixed_path(monkeypatch, config_updates):
    from vgate_tpu import config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(config, "_requested_platform", lambda: "tpu")
    fixed = os.path.join(REPO, ".jax_cache")
    assert config.apply_compile_cache() == fixed
    assert config.apply_compile_cache() == fixed
    assert dict(config_updates)["jax_compilation_cache_dir"] == fixed
    # ... and the same in another process (no tempfile, pid or clock)
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import json; from vgate_tpu.config import apply_compile_cache; "
            "print(json.dumps(apply_compile_cache()))",
        ],
        cwd=REPO, env=_env(JAX_PLATFORMS="tpu,cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.splitlines()[-1]) == fixed


def test_compile_cache_stays_off_for_a_cpu_process(
    monkeypatch, config_updates
):
    """Tier-1 runs on the CPU: engines built there must not start
    reloading executables from disk (see apply_compile_cache)."""
    from vgate_tpu import config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert config._requested_platform() == "cpu"  # conftest pins it
    assert config.apply_compile_cache() is None
    assert config_updates == []
