"""Test harness.

* Forces JAX onto a virtual 8-device CPU platform BEFORE jax import, so
  sharding/scheduler tests run without TPU hardware (SURVEY.md section 4's
  multi-node strategy: ``xla_force_host_platform_device_count``).
* Runs ``async def`` tests via a tiny pytest hook (no pytest-asyncio in the
  image).
* Resets config + tracing global singletons between tests (reference autouse
  fixture: tests/conftest.py:242-249).
"""

import asyncio
import inspect
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
for _flag in (
    "--xla_force_host_platform_device_count=8",
    # Tier-1's seconds are XLA:CPU compiles of programs that run once at
    # toy sizes: LLVM without its optimisation passes compiles them
    # sooner than the optimised code wins back.  Measured for PR 55:
    # tests/test_mla_model.py alone 210.8 -> 167.4 s (-21 %), every test
    # passing at its tolerance; the interpret-mode kernel files, which
    # run what they compile, no slower in any group of cases (717 -> 567
    # s with a file on one worker).  The v5e compiles are libtpu's and
    # read the same bytes; the rehearsals' servers strip XLA_FLAGS
    "--xla_backend_optimization_level=0",
    "--xla_llvm_disable_expensive_passes=true",
):
    if _flag.partition("=")[0] not in _flags:
        _flags = (_flags + " " + _flag).strip()
os.environ["XLA_FLAGS"] = _flags

import jax

# Tier-1 runs on the CPU whatever the environment selects.
jax.config.update("jax_platforms", "cpu")

import pytest

# The `slow` tier, by file: what Tier-1 (`-m 'not slow'`) leaves out.
# Every other file is `fast` by default, and a test's own mark wins over
# its file's.  `fast` names what Tier-1 runs, not a speed: it holds the
# family files, the AOT compiles and the rehearsals beside the gateway's
# and the scheduler's sub-second tests (ROADMAP.md D0 has the seconds).
#   pytest -m fast -q tests/        # what Tier-1 runs
#   pytest -m slow -q tests/        # the tier Tier-1 leaves out
SLOW_FILES = {
    "test_distributed",
    "test_dp_engine",
    "test_encoder",
    "test_engine",
    "test_jax_backend",
    "test_logprobs",
    "test_model_parity",
    "test_pallas_decode_kernel",
    "test_pallas_decode_trips",
    "test_pallas_kernels",
    "test_penalties",
    "test_pipeline",
    "test_prefix_cache",
    "test_quant",
    "test_recovery",
    "test_ring_attention",
    "test_sharding",
    "test_speculative",
    "test_weights_checkpoint",
}


# WHO RUNS A FILE (the one statement of the rule; README.md and
# ROADMAP.md point here).  The driver's command says `-n 6 --dist load`
# (/root/TESTS_LAST_RUN.json); the hook below hands xdist the `loadfile`
# scheduler whatever `--dist` says, so a file is ONE worker's: its
# module fixtures and its jitted programs exist once a run.  `loadfile`
# deals files to free workers in collection order, so the order is the
# schedule: the stems below go first, longest first (seconds alone, from
# `scripts/tier1_times.py` over this PR's junit), and the run ends on
# the hundreds of sub-second tests that level the workers, the
# benchmark's own (`tests/perfbench/`) last of all: three of them time a
# window of seconds, which a worker compiling beside them disturbs.  A
# file that passes 300 s alone is split by what it compiles, not listed
# here.
LONGEST_FIRST = (
    "test_rehearse_glm_dsa", "test_rehearse_keye_dsa", "test_glm_dsa_engine",
    "test_lfm2_moe", "test_hybrid_model", "test_pallas_decode_trips",
    "test_pallas_decode_kernel", "test_pallas_kernels", "test_mla_model",
    "test_nemotron_h_model", "test_glm_dsa_kernels",
    "test_rehearse_lfm2_moe", "test_tpu_aot", "test_tpu_aot_head_64",
    "test_tpu_aot_long_prompts", "test_exaone_moe", "test_keye_dsa",
    "test_evabyte", "test_lfm2_moe_engine", "test_exaone_moe_engine",
    "test_rehearse_exaone_moe", "test_rehearse_mellum",
    "test_rehearse_hybrid",
    "test_mla_attention", "test_engine", "test_rehearse_nemotron_h",
    "test_rehearse_evabyte", "test_keye_dsa_engine", "test_mellum_engine",
    "test_tpu_aot_states",
    "test_rehearse_mistral4", "test_glm_dsa", "test_pallas_decode_pipeline",
    "test_granite_hybrid_engine", "test_mellum",
    "test_granite_hybrid", "test_rehearse_granite_hybrid",
    "test_chip_smoke", "test_kv_quant", "test_evabyte_engine",
    "test_moe_share", "test_ssd", "test_moe_latent",
)


@pytest.hookimpl(optionalhook=True)  # `-p no:xdist` must still collect
def pytest_xdist_make_scheduler(config, log):
    from xdist.scheduler import LoadFileScheduling

    # xdist would deal the files with the most tests first
    config.option.loadscopereorder = False
    return LoadFileScheduling(config, log)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") or item.get_closest_marker(
            "fast"
        ):
            continue  # explicit per-test tier wins over the file default
        tier = "slow" if item.module.__name__ in SLOW_FILES else "fast"
        item.add_marker(getattr(pytest.mark, tier))
    first = {stem: at for at, stem in enumerate(LONGEST_FIRST)}

    def turn(item):
        return (first.get(item.path.stem, len(first)),
                item.path.parent.name == "perfbench")

    # a stable sort: a file's tests stay together and in their order
    items.sort(key=turn)


_last_module = [None]


@pytest.fixture(autouse=True)
def _clear_jax_caches_per_file(request):
    """Clear jax's pjit/compile caches at test-FILE boundaries.

    A single-process full-suite run accumulates ~350 tests' worth of
    compiled executables; twice (r5) the XLA CPU compiler segfaulted in
    backend_compile_and_load near the END of such runs (test_speculative,
    after ~340 prior compiles) while every file passes in isolation.
    Bounding cache growth at file granularity keeps one-invocation runs
    viable; per-file recompiles cost little since files rarely share
    program shapes.

    The `_last_module` sentinel presumes that a process sees whole
    files in order.  A serial run does; so does every xdist worker,
    since `pytest_xdist_make_scheduler` above deals whole files."""
    mod = request.module.__name__
    if _last_module[0] not in (None, mod):
        jax.clear_caches()
    _last_module[0] = mod
    yield


def pytest_pyfunc_call(pyfuncitem):
    """Run coroutine test functions on a fresh event loop."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True)
def _reset_globals(monkeypatch):
    from vgate_tpu import config as config_mod
    from vgate_tpu import faults
    from vgate_tpu import tracing as tracing_mod

    # isolate tests from the repo's sample ./config.yaml
    monkeypatch.setenv("VGT_CONFIG_PATH", "/nonexistent/vgt-test-config.yaml")
    config_mod.reset_config()
    tracing_mod.reset_tracing()
    faults.reset()
    yield
    config_mod.reset_config()
    tracing_mod.reset_tracing()
    # armed faults must never leak across tests (a leaked decode_step
    # fault would crash every later engine test)
    faults.reset()


@pytest.fixture(params=["rule", "under", "at", "over"])
def at_a_time(request, monkeypatch):
    """What the expert layer's dispatch takes at a time (ops/moe.py
    ``capacity``), set against the ``held`` pairs of the call under
    test: the layer's own rule, more than fell here, exactly as many, or
    a third of them (so the dispatch makes three trips or four).
    ``at_a_time(held)`` sets it and returns the trips beyond the first
    that a block of ``held`` pairs then makes; ``.case`` names the case."""
    from vgate_tpu.ops import moe
    from vgate_tpu.utils.math import cdiv

    def fix(held: int) -> int:
        if request.param == "rule":
            return 0
        take = {"under": held + 8, "at": held,
                "over": max(1, held // 3)}[request.param]
        monkeypatch.setattr(moe, "capacity", lambda spec, pairs: take)
        return cdiv(held, take) - 1

    fix.case = request.param
    return fix


@pytest.fixture
def dry_config():
    """A config wired for dry-run testing."""
    from vgate_tpu.config import load_config

    return load_config(
        model={"engine_type": "dry_run"},
        batch={"max_batch_size": 4, "max_wait_time_ms": 10.0},
        logging={"level": "WARNING"},
    )
