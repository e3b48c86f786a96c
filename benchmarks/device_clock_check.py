"""A builder's check of the device clock against the device trace (PR
49): a tool beside ``pause_probe.py``, not a cell.

    chiprun -- python benchmarks/device_clock_check.py --workload NAME \
        --seed N [--seconds 51] [--out chiprun_out/NAME.json] [--rehearse]

runs ``perfbench.run --trace 1`` unchanged (same server, same load, its
result line printed first) and then one more JSON line,
``{"device_clock_check": ...}``:

* ``capture``: inside the 4 s profile, between the device's first and
  last operation, the device's SELF seconds by XLA module (through
  ``perfbench/trace.py``'s reader) beside the seconds of the clock's
  ``vgt.device.<program>`` spans by program, with their difference in
  percent of the module's seconds.  The trace cannot tell a suffix
  group from a long prompt's chunk (both run
  ``jit__suffix_prefill_step``), so those two programs are compared as
  one.  ``busy_outside_spans_share``: the share of the device's busy
  time that lies under no posted launch's span (work launched outside
  ``EngineCore._launch``, and the stamp's lag);
* ``window``: the growth of ``totals.device_clock`` over the 51 s
  window as the shares and paces the new per-layer metrics read, each
  beside the 4 s sample's reading of the same thing from the result
  line, and ``dropped``.

``--reduce TRACE_DIR`` is the child this tool starts for the trace (it
imports jax, which the parent, like the harness, stays off).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SPAN = "vgt.device."
# the clock's programs by the XLA module that runs them
MODULES = {
    "jit__decode_chunk": "decode",
    "jit__prefill_step": "prefill",
    "jit__suffix_prefill_step": "suffix+chunked_prefill",
    "jit__spec_verify_step": "spec_verify",
}
PROGRAM_ROWS = {
    "decode": "decode", "prefill": "prefill", "spec_verify": "spec_verify",
    "suffix_prefill": "suffix+chunked_prefill",
    "chunked_prefill": "suffix+chunked_prefill",
}
PROMPT = ("prefill", "suffix_prefill", "chunked_prefill")


def clipped(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def uncovered(busy: List[Tuple[float, float]],
              spans: List[Tuple[float, float]]) -> float:
    """Seconds of the disjoint sorted intervals ``busy`` that no interval
    of ``spans`` covers."""
    from perfbench.trace import merged

    cover = merged(spans)
    out, i = 0.0, 0
    for a, b in busy:
        while i < len(cover) and cover[i][1] <= a:
            i += 1
        inside, j = 0.0, i
        while j < len(cover) and cover[j][0] < b:
            inside += clipped(cover[j][0], cover[j][1], a, b)
            j += 1
        out += (b - a) - inside
    return out


def reduce_capture(trace_dir: str) -> Dict[str, Any]:
    from perfbench import trace

    path = trace.newest_xplane(trace_dir)
    if path is None:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    devices, host = trace.read_planes(path)
    module_s: Dict[str, float] = defaultdict(float)
    busy: List[Tuple[float, float]] = []
    for lines in devices.values():
        ops = lines.get(trace.OPS_LINE, [])
        find = trace.module_of(lines.get(trace.MODULES_LINE, []))
        for _, start, self_s in trace.self_times(ops):
            module_s[find(start)] += self_s
        busy += trace.merged((a, b) for _, a, b in ops)
    if not busy:
        return {"error": "no device operation in the capture"}
    busy = trace.merged(busy)
    t0, t1 = busy[0][0], busy[-1][1]
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    spans: List[Tuple[float, float]] = []
    threads = 0
    for events in host.values():
        own = [(n[len(SPAN):], a, b) for n, a, b in events
               if n.startswith(SPAN)]
        threads += bool(own)
        for program, a, b in own:
            inside = clipped(a, b, t0, t1)
            if inside > 0:
                row = PROGRAM_ROWS.get(program, program)
                span_s[row] += inside
                span_n[row] += 1
                spans.append((max(a, t0), min(b, t1)))
    busy_s = sum(b - a for a, b in busy)
    # where the clock's line stood open between two waits: the longest
    # of those holes, (ms after the window's start, ms long)
    cover = trace.merged(spans)
    holes = sorted(((y[0] - x[1], x[1] - t0) for x, y in zip(cover, cover[1:])),
                   reverse=True)
    rows = {}
    for module, seconds in sorted(module_s.items(), key=lambda kv: -kv[1]):
        row = MODULES.get(module)
        rows[module or "(no module)"] = {
            "program": row, "trace_self_s": round(seconds, 6),
            "clock_span_s": (round(span_s.get(row, 0.0), 6)
                             if row else None),
            "spans": span_n.get(row, 0) if row else None,
            "diff_pct": (round(100 * (span_s.get(row, 0.0) - seconds)
                               / seconds, 2) if row and seconds else None),
        }
    prompt_s = sum(s for m, s in module_s.items()
                   if MODULES.get(m, "").endswith("prefill"))
    return {
        "trace_window_s": round(t1 - t0, 6), "trace_busy_s": round(busy_s, 6),
        "trace_idle_share_pct": round(100 * (1 - busy_s / (t1 - t0)), 4),
        "trace_prefill_share_pct": round(100 * prompt_s / busy_s, 4),
        "clock_span_s_total": round(sum(span_s.values()), 6),
        "clock_prefill_share_pct": round(
            100 * sum(s for p, s in span_s.items() if p.endswith("prefill"))
            / max(sum(span_s.values()), 1e-12), 4),
        "span_lines": threads,  # the clock's ONE thread
        "span_holes": len(holes),
        "span_holes_ms": [[round(1e3 * at, 3), round(1e3 * hole, 3)]
                          for hole, at in holes[:8]],
        "busy_outside_spans_share_pct": round(
            100 * uncovered(busy, spans) / busy_s, 4),
        "modules": rows,
    }


def window_readings(grown: Any, result: Dict[str, Any]) -> Dict[str, Any]:
    """The window's growth of the clock, as the new metrics reduce it,
    beside the 4 s sample's metrics of the result line."""
    def g(path: str) -> Optional[float]:
        return grown("device_clock." + path)

    busy, idle = g("busy_s"), g("idle_s")
    if busy is None or not busy:
        return {"error": "no totals.device_clock growth in the window"}
    program_s = {p: g(f"programs.{p}.s") for p in PROGRAM_ROWS}
    program_n = {p: g(f"programs.{p}.n") for p in PROGRAM_ROWS}
    prompt_s = sum(program_s[p] for p in PROMPT)
    steps, tokens = g("decode_steps"), g("prompt_tokens")

    def sample(*names: str) -> Dict[str, float]:
        return {n: m["value"] for n, m in result["metrics"].items()
                if n.rsplit(".", 1)[0] in names}

    return {
        "busy_s": busy, "idle_s": idle, "dropped": g("dropped"),
        "program_s": program_s, "program_n": program_n,
        "idle_share_pct": 100 * idle / (idle + busy),
        "sample_idle_share": sample("device.idle_share"),
        "prefill_share_pct": 100 * prompt_s / busy,
        "sample_prefill_share": sample("model.prefill_share"),
        "decode_step_ms": 1e3 * program_s["decode"] / steps if steps else None,
        "sample_decode_step_ms": sample(
            "model.decode_step_ms", "model.mla_decode_step_ms",
            "model.dsa_decode_step_ms"),
        "prompt_ms_per_ktok": 1e6 * prompt_s / tokens if tokens else None,
        "prefill_device_queue_ms": (
            1e3 * g("programs.prefill.queued_s") / program_n["prefill"]
            if program_n["prefill"] else None),
        "host_wait_ms_per_step": sample("engine.device_wait_ms_per_step"),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduce", metavar="TRACE_DIR", default=None)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="on a CPU: the window's part alone, no capture")
    args = ap.parse_args(argv)
    if args.reduce:
        print(json.dumps(reduce_capture(args.reduce)), flush=True)
        return 0
    if args.workload is None or args.seed is None:
        ap.error("--workload and --seed are required")

    from benchmarks import pause_probe
    from perfbench import run as bench

    pause_probe.probing(None, 0.0)  # keeps the window's snapshots
    args.trace = 1
    try:
        result = bench.run(args)
    except bench.BenchFailure as exc:
        print(f"device_clock_check: FAILED: {exc}", file=sys.stderr,
              flush=True)
        return 1
    window = pause_probe.KEPT["window"]
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--reduce",
         window.trace_dir],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900,
    )
    if proc.returncode == 0:
        capture = json.loads(proc.stdout.strip().splitlines()[-1])
    else:
        capture = {"error": proc.stderr[-2000:]}
    check = {
        "workload": args.workload, "seed": args.seed,
        "capture": capture,
        "window": window_readings(
            lambda path: pause_probe.grown(window, path), result),
    }
    lines = [json.dumps(result), json.dumps({"device_clock_check": check})]
    for line in lines:
        print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
