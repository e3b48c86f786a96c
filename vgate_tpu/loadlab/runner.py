"""Sweep orchestration: scenario -> arrivals -> driver -> grader ->
stamped JSONL artifact.

For each QPS cell the runner: builds the plan, snapshots the server's
``vgt_*`` histograms, optionally schedules the chaos arm, drives the
cell open-loop, re-snapshots the histograms, grades the samples, and
appends one artifact line.  The artifact carries BOTH latency views per
cell — the client-observed distributions and the server's own
TTFT/TPOT histogram deltas — so metric skew between what the server
claims and what clients experience is visible in one file (the smoke
drill asserts the two agree on an unloaded cell).

``launch_server`` boots ``python main.py`` as a subprocess with the
scenario's ``server_env`` — the path the lab's CLI (``--launch``) and
the drills share.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import re
import subprocess
import sys
import time
import urllib.request
from typing import Any, Callable, Dict, List, Optional

import aiohttp

from . import slo, workload
from .driver import drive_cell, run_serial
from .scenario import Scenario

_REPO_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# server histograms mirrored into each cell line (name -> artifact key)
_HISTOGRAMS = {
    "vgt_time_to_first_token_seconds": "ttft",
    "vgt_time_per_output_token_seconds": "tpot",
}


def git_sha() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=_REPO_DIR, capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or None
    except Exception:  # noqa: BLE001 — stamping must never fail a run
        return None


# -- prometheus text scraping --------------------------------------------

def parse_histograms(text: str) -> Dict[str, Dict[str, Any]]:
    """Extract {name: {count, sum, buckets: {le: cum_count}}} for the
    mirrored histograms from a /metrics exposition."""
    out: Dict[str, Dict[str, Any]] = {
        name: {"count": 0.0, "sum": 0.0, "buckets": {}}
        for name in _HISTOGRAMS
    }
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^(\w+)(?:\{([^}]*)\})?\s+([0-9eE+.\-]+|NaN)", line)
        if not m:
            continue
        metric, labels, value = m.group(1), m.group(2) or "", m.group(3)
        try:
            val = float(value)
        except ValueError:
            continue
        for name, acc in out.items():
            if metric == f"{name}_count":
                acc["count"] = val
            elif metric == f"{name}_sum":
                acc["sum"] = val
            elif metric == f"{name}_bucket":
                le = re.search(r'le="([^"]+)"', labels)
                if le:
                    acc["buckets"][le.group(1)] = val
    return out


def hist_delta(
    before: Dict[str, Any], after: Dict[str, Any]
) -> Dict[str, Any]:
    """Per-cell histogram delta: observation count, mean, and a bucket
    p99 estimate (upper-bound interpolation on the cumulative bucket
    counts — coarse, but honest about its granularity)."""
    dcount = after["count"] - before["count"]
    dsum = after["sum"] - before["sum"]
    result: Dict[str, Any] = {
        "count": int(dcount),
        "mean_ms": round(dsum / dcount * 1000, 1) if dcount > 0 else None,
    }
    if dcount > 0:
        deltas = []
        for le, cum in after["buckets"].items():
            if le == "+Inf":
                continue
            d = cum - before["buckets"].get(le, 0.0)
            deltas.append((float(le), d))
        deltas.sort()
        target = 0.99 * dcount
        p99 = None
        for le, cum_d in deltas:
            if cum_d >= target:
                p99 = le * 1000
                break
        result["p99_ms_le"] = round(p99, 1) if p99 is not None else None
    return result


async def _scrape(base_url: str) -> Optional[Dict[str, Dict[str, Any]]]:
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(
                f"{base_url}/metrics",
                timeout=aiohttp.ClientTimeout(total=10),
            ) as resp:
                if resp.status != 200:
                    return None
                return parse_histograms(await resp.text())
    except Exception:  # noqa: BLE001 — the server view is best-effort;
        # the client view is the ground truth the lab exists to record
        return None


async def _fetch_perf(base_url: str) -> Optional[Dict[str, Any]]:
    """One /debug/perf scrape, or None when the server has no
    attribution surface (pre-perf servers, disabled recorder)."""
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(
                f"{base_url}/debug/perf",
                timeout=aiohttp.ClientTimeout(total=10),
            ) as resp:
                if resp.status != 200:
                    return None
                body = await resp.json()
                return body if body.get("enabled") else None
    except Exception:  # noqa: BLE001 — perf attribution is an extra
        # evidence column, never a reason to fail the measurement
        return None


def _perf_totals(snap: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The monotone ``totals`` block from a /debug/perf payload —
    top-level on dp=1, under the merged aggregate on dp>1 (both shapes
    carry it top-level; the replicas list is ignored here)."""
    return snap.get("totals")


def perf_delta(
    before: Optional[Dict[str, Any]],
    after: Optional[Dict[str, Any]],
) -> Optional[Dict[str, Any]]:
    """Per-cell perf-attribution delta from two /debug/perf scrapes:
    where the server's engine time went during THIS cell (phase
    seconds, recompiles, host-overhead ratio) plus the end-of-cell
    rolling-window gauges — so every sweep artifact carries a "where
    did the time go" row next to its tok/s number."""
    if before is None or after is None:
        return None
    b, a = _perf_totals(before), _perf_totals(after)
    if b is None or a is None:
        return None
    phases = {
        name: round(
            a["phase_seconds"].get(name, 0.0)
            - b["phase_seconds"].get(name, 0.0),
            6,
        )
        for name in a.get("phase_seconds", {})
    }
    wall = round(a["wall_s"] - b["wall_s"], 6)
    recompiles = {
        prog: a["compiles"].get(prog, 0) - b["compiles"].get(prog, 0)
        for prog in set(a.get("compiles", {})) | set(b.get("compiles", {}))
    }
    window = after.get("window") or {}
    out = {
        "ticks": a["ticks"] - b["ticks"],
        "tokens": a["tokens"] - b["tokens"],
        "wall_s": wall,
        "phase_seconds": phases,
        "host_overhead_ratio": (
            round(phases.get("host", 0.0) / wall, 4) if wall > 0 else None
        ),
        "recompiles": {k: v for k, v in recompiles.items() if v},
        "compile_seconds": round(
            a["compile_seconds"] - b["compile_seconds"], 6
        ),
        # end-of-cell rolling-window gauges (the live view the server's
        # vgt_decode_mfu / vgt_host_overhead_ratio metrics export)
        "window": {
            key: window.get(key)
            for key in (
                "tokens_per_s", "mfu", "hbm_roofline_pct",
                "host_overhead_ratio",
            )
        },
    }
    # pod-mode servers stamp topology + handoff outcome counters onto
    # the merged snapshot; land the per-cell handoff outcome DELTAS so
    # a disaggregated sweep row shows how many KV transfers (and how
    # many monolithic fallbacks) this cell's tok/s actually paid for
    pod_after = after.get("pod")
    if pod_after is not None:
        ho_b = (before.get("pod") or {}).get("handoffs") or {}
        ho_a = pod_after.get("handoffs") or {}
        out["pod"] = {
            "workers": pod_after.get("workers"),
            "workers_alive": pod_after.get("workers_alive"),
            "handoffs": {
                key: ho_a.get(key, 0) - ho_b.get(key, 0)
                for key in set(ho_a) | set(ho_b)
            },
        }
    return out


async def _fetch_stats(base_url: str) -> Dict[str, Any]:
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(
                f"{base_url}/stats",
                timeout=aiohttp.ClientTimeout(total=10),
            ) as resp:
                if resp.status != 200:
                    return {}
                return await resp.json()
    except Exception:  # noqa: BLE001
        return {}


# -- chaos arm ------------------------------------------------------------

async def _chaos_task(
    base_url: str, spec, result: Dict[str, Any]
) -> None:
    """Arm the scenario's fault spec mid-cell via /debug/faults (the
    server opts in with VGT_FAULTS_HTTP=1)."""
    await asyncio.sleep(max(0.0, spec.at_s))
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"{base_url}/debug/faults",
                json={"faults": spec.faults},
                timeout=aiohttp.ClientTimeout(total=10),
            ) as resp:
                body = await resp.json()
                result["armed"] = resp.status == 200 and bool(
                    body.get("armed")
                )
                result["status"] = resp.status
                result["detail"] = body
    except Exception as exc:  # noqa: BLE001 — chaos is an optional arm;
        # failure to arm is recorded, not fatal to the measurement
        result["armed"] = False
        result["error"] = repr(exc)


async def _chaos_disarm(base_url: str) -> None:
    with contextlib.suppress(Exception):
        async with aiohttp.ClientSession() as session:
            await session.delete(
                f"{base_url}/debug/faults",
                timeout=aiohttp.ClientTimeout(total=10),
            )


# -- the sweep ------------------------------------------------------------

async def run_scenario_async(
    scenario: Scenario,
    base_url: str,
    *,
    out_path: Optional[str] = None,
    platform: Optional[str] = None,
    device: Optional[str] = None,
    cells: Optional[List[float]] = None,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict[str, Any]:
    """Run the full QPS sweep; returns {lines, summary, out_path}."""
    say = progress or (lambda s: print(s, file=sys.stderr, flush=True))
    base_url = base_url.rstrip("/")
    stats = await _fetch_stats(base_url)
    cfg = stats.get("config") or {}
    import hashlib

    meta: Dict[str, Any] = {
        "kind": "meta",
        "schema": slo.SCHEMA,
        "scenario": scenario.name,
        "scenario_hash": scenario.content_hash(),
        "seed": scenario.seed,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": platform
        or os.environ.get("VGT_LOADLAB_PLATFORM")
        or (os.environ.get("JAX_PLATFORMS") or "unknown").split(",")[0]
        or "unknown",
        "device": device or os.environ.get("VGT_LOADLAB_DEVICE")
        or "unknown",
        "git_sha": git_sha(),
        "config_fingerprint": hashlib.sha256(
            json.dumps(cfg, sort_keys=True).encode()
        ).hexdigest()[:16] if cfg else None,
        "server_config": cfg or None,
        "server_model": (stats.get("engine") or {}).get("model"),
        "base_url": base_url,
        "arrival": scenario.arrival.to_dict(),
        "duration_s": scenario.duration_s,
        "slos": {t: s.to_dict() for t, s in scenario.slos.items()},
    }
    lines: List[Dict[str, Any]] = [meta]

    if scenario.warmup_requests > 0:
        say(f"loadlab: warmup x{scenario.warmup_requests}")
        await run_serial(
            base_url,
            workload.warmup_requests(scenario, scenario.warmup_requests),
            timeout_s=scenario.request_timeout_s,
        )

    sweep = list(cells) if cells is not None else list(scenario.qps_cells)
    cell_lines: List[Dict[str, Any]] = []
    for idx, qps in enumerate(sweep):
        plan = workload.build_plan(scenario, idx, qps)
        say(
            f"loadlab: cell {idx + 1}/{len(sweep)} qps={qps:g} "
            f"({len(plan)} arrivals over {scenario.duration_s:g}s)"
        )
        before = await _scrape(base_url)
        perf_before = await _fetch_perf(base_url)
        chaos_result: Dict[str, Any] = {}
        extra = []
        armed_here = scenario.chaos is not None and (
            scenario.chaos.cell_index is None
            or scenario.chaos.cell_index == idx
        ) and scenario.chaos.faults
        if armed_here:
            extra.append(
                _chaos_task(base_url, scenario.chaos, chaos_result)
            )
        samples = await drive_cell(
            base_url, plan,
            timeout_s=scenario.request_timeout_s,
            extra_tasks=extra,
        )
        if armed_here and scenario.chaos.disarm_at_end:
            await _chaos_disarm(base_url)
        # let stragglers' histogram observations land before the
        # post-cell scrape (the driver already awaited every sample)
        after = await _scrape(base_url)
        perf_after = await _fetch_perf(base_url)
        line = slo.grade_cell(
            samples, scenario.slos,
            qps=qps, duration_s=scenario.duration_s,
        )
        if before is not None and after is not None:
            line["server"] = {
                key: hist_delta(before[name], after[name])
                for name, key in _HISTOGRAMS.items()
            }
        else:
            line["server"] = None
        # the attribution delta lands next to the two TTFT views: every
        # future perf PR's sweep carries a "where did the time go" row,
        # not just a tok/s number
        line["perf"] = perf_delta(perf_before, perf_after)
        if armed_here:
            line["chaos"] = {
                "faults": scenario.chaos.faults,
                "at_s": scenario.chaos.at_s,
                **chaos_result,
            }
        cell_lines.append(line)
        lines.append(line)
        say(json.dumps(line))

    summary = slo.summarize(cell_lines)
    lines.append(summary)
    say(json.dumps(summary))
    if out_path:
        slo.write_artifact(out_path, lines)
        say(f"loadlab: artifact -> {out_path}")
    return {"lines": lines, "summary": summary, "out_path": out_path}


def run_scenario(scenario: Scenario, base_url: str, **kwargs: Any):
    """Sync wrapper (the lab's CLI)."""
    return asyncio.run(run_scenario_async(scenario, base_url, **kwargs))


# -- local server launch --------------------------------------------------

def scenario_server_env(scenario: Scenario) -> Dict[str, str]:
    """The scenario's server_env as DEFAULTS: any variable the operator
    already exported wins (a sweep re-points the same scenario at another
    model or KV dtype by exporting over it)."""
    return {
        k: str(v)
        for k, v in scenario.server_env.items()
        if k not in os.environ
    }


@contextlib.contextmanager
def launch_server(
    env_overrides: Dict[str, str],
    port: int = 8790,
    ready_timeout_s: float = 300.0,
    log_path: Optional[str] = None,
):
    """Boot ``python main.py`` on ``port`` with ``env_overrides`` and
    yield its base URL once /health/ready answers; always tears the
    process down.  The scenario's ``server_env`` plus the caller's env
    decide platform/model — the lab itself never imports jax.  The
    server's output goes to ``log_path`` when given: without it a
    server that dies before ready leaves an exit code and no reason."""
    env = dict(os.environ)
    env.update(env_overrides)
    env["VGT_SERVER__PORT"] = str(port)
    log = open(log_path, "wb") if log_path else subprocess.DEVNULL
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_DIR, "main.py")],
        env=env, cwd=_REPO_DIR, stdout=log, stderr=subprocess.STDOUT,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + ready_timeout_s
        last_err: Optional[str] = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={proc.returncode} before ready"
                    + (f"; see {log_path}" if log_path else "")
                )
            try:
                with urllib.request.urlopen(
                    f"{base}/health/ready", timeout=2
                ) as resp:
                    if resp.status == 200:
                        break
            except Exception as exc:  # noqa: BLE001 — poll until deadline
                last_err = repr(exc)
            time.sleep(0.3)
        else:
            raise TimeoutError(
                f"server on :{port} never became ready "
                f"({ready_timeout_s:.0f}s); last error: {last_err}"
            )
        yield base
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
        if log_path:
            log.close()
