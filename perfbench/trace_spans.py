"""Device idle time charged to the engine's own spans.

    JAX_PLATFORMS=cpu python -m perfbench.trace_spans TRACE_DIR OUT.json

The program brackets its engine tick with profiler annotations while a
capture runs (``vgate_tpu/observability/perf.py``): ``vgt.engine.tick``
and, inside it, the leaf spans ``schedule``, ``state``,
``prefill_dispatch``, ``decode_dispatch``, ``device_wait``, ``readback``,
``emit``; ``idle_wait`` between ticks.  All on ONE host thread, the
engine's.  This module reads the newest ``.xplane.pb`` under TRACE_DIR
and writes what the ``span_*`` reducers read:

* ``gap_seconds``: every pause of the device between two operations,
  cut at the span boundaries and charged piece by piece to the
  INNERMOST ``vgt.engine.*`` span that covers the piece on the engine
  thread (``tick`` = inside a tick and under no leaf span, ``outside`` =
  under no span at all).  ``perfbench.trace`` charges a pause to the
  shortest event on any thread, which would hand every pause to the
  C++ runtime events nested inside our spans.  Pauses under 20 us are
  launch latency and are summed apart as ``short_gaps``;
* ``window_s`` and ``busy_s`` of the device, as ``perfbench.trace``
  defines them (first operation's start to last operation's end; the
  union of the operations), summed over the chips;
* ``engine_cover``: the share of the engine thread's time inside the
  device's window that some ``vgt.engine.*`` span covers;
* ``decode``: the ``decode_dispatch`` spans up to the window's end (a
  chunk dispatched before the device's first traced operation still
  runs inside the window), as the program saw them: ``steps`` (chunk length), ``rows``,
  ``ctx_tokens`` (live context tokens of all rows when the chunk was
  dispatched) and ``lead`` (steps already in flight).

* ``emit_tokens`` over ``capture_s``: the tokens the engine emitted
  while the capture ran (the ``emit`` spans' ``tokens``), to set beside
  the window's ``out_tok_s``: what the profiler costs while it is on.

Against a program without the spans (an older commit) the file holds
``"engine_thread": null`` and every reducer returns None.  The interval
arithmetic works on plain tuples; only ``read`` touches the profiler,
and ``load`` runs it in a child process so that the harness's own
process stays off JAX.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .trace import DEVICE_PLANE, MIN_GAP_S, OPS_LINE, merged, newest_xplane

Span = Tuple[str, float, float]  # name, start_s, end_s
PREFIX = "vgt.engine."
OUTSIDE = "outside"
SHORT = "short_gaps"


def innermost(spans: Iterable[Span]) -> List[Tuple[float, float, str]]:
    """The spans of one thread (properly nested, as a ``with`` makes
    them) flattened into disjoint pieces ``(start, end, name)``, each
    named after the innermost span that covers it."""
    order = sorted(spans, key=lambda s: (s[1], -(s[2] - s[1])))
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []  # the open spans, outermost first
    t = 0.0  # everything before t is already handed out

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][2] <= limit:
            name, _, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for span in order:
        close_until(span[1])
        if stack and span[1] > t:
            out.append((t, span[1], stack[-1][0]))
        t = max(t, span[1]) if stack else span[1]
        stack.append(span)
    close_until(float("inf"))
    return out


def charge(gaps: Iterable[Tuple[float, float]],
           pieces: List[Tuple[float, float, str]],
           min_gap_s: float = MIN_GAP_S) -> Dict[str, float]:
    """Seconds of ``gaps`` under each piece's name; what no piece covers
    goes to ``outside``, gaps shorter than ``min_gap_s`` to
    ``short_gaps`` whole."""
    out: Dict[str, float] = defaultdict(float)
    pieces = sorted(pieces)
    i = 0
    for a, b in sorted(gaps):
        if b - a < min_gap_s:
            out[SHORT] += b - a
            continue
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        covered = 0.0
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            lo, hi = max(a, pieces[j][0]), min(b, pieces[j][1])
            if hi > lo:
                out[pieces[j][2]] += hi - lo
                covered += hi - lo
            j += 1
        if b - a > covered:
            out[OUTSIDE] += (b - a) - covered
    return dict(out)


def cover(pieces: List[Tuple[float, float, str]], t0: float, t1: float
          ) -> Optional[float]:
    """Share of ``[t0, t1]`` that the pieces cover."""
    if t1 <= t0:
        return None
    inside = sum(max(0.0, min(b, t1) - max(a, t0)) for a, b, _ in pieces)
    return inside / (t1 - t0)


def summarize(device_ops: Dict[str, List[Tuple[float, float]]],
              engine_spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``device_ops``: plane -> (start, end) of every operation;
    ``engine_spans``: the engine thread's ``vgt.engine.*`` events as
    ``{"name", "start", "end", "args"}`` (name without the prefix)."""
    pieces = innermost((s["name"], s["start"], s["end"])
                       for s in engine_spans)
    gap_seconds: Dict[str, float] = defaultdict(float)
    window_s = busy_s = 0.0
    t0 = t1 = None
    for plane in sorted(device_ops):
        busy = merged(device_ops[plane])
        if not busy:
            continue
        a, b = busy[0][0], busy[-1][1]
        t0 = a if t0 is None else min(t0, a)
        t1 = b if t1 is None else max(t1, b)
        window_s += b - a
        busy_s += sum(y - x for x, y in busy)
        gaps = [(x[1], y[0]) for x, y in zip(busy, busy[1:])]
        for name, seconds in charge(gaps, pieces).items():
            gap_seconds[name] += seconds
    decode = [
        {k: s["args"].get(k) for k in ("steps", "rows", "ctx_tokens", "lead")}
        for s in engine_spans
        if s["name"] == "decode_dispatch" and t0 is not None
        and s["start"] <= t1 and s["args"].get("steps")
    ]
    span_seconds: Dict[str, float] = defaultdict(float)
    for s in engine_spans:
        span_seconds[s["name"]] += s["end"] - s["start"]
    return {
        "engine_thread": bool(engine_spans),
        # what the engine emitted while the capture ran, and for how
        # long it ran: the throughput UNDER the profiler
        "emit_tokens": sum(s["args"].get("tokens") or 0
                           for s in engine_spans if s["name"] == "emit"),
        "capture_s": (max(s["end"] for s in engine_spans)
                      - min(s["start"] for s in engine_spans)
                      if engine_spans else 0.0),
        "window_s": window_s, "busy_s": busy_s,
        "gap_seconds": dict(gap_seconds),
        "engine_cover": (cover(pieces, t0, t1)
                         if t0 is not None and pieces else None),
        "decode": decode,
        "span_seconds": dict(span_seconds),
    }


def read(path: str) -> Tuple[Dict[str, List[Tuple[float, float]]],
                             List[Dict[str, Any]]]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[float, float]]] = {}
    threads: List[List[Dict[str, Any]]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [
                    {"name": ev.name[len(PREFIX):],
                     "start": ev.start_ns * 1e-9,
                     "end": (ev.start_ns + ev.duration_ns) * 1e-9,
                     "args": dict(ev.stats)}
                    for ev in line.events if ev.name.startswith(PREFIX)
                ]
                if spans:
                    threads.append(spans)
    # the engine thread is the one that ticks (thread lines are all
    # named after the process, and a supervised rebuild starts another)
    ticks = lambda spans: sum(1 for s in spans if s["name"] == "tick")
    engine = max(threads, key=ticks) if threads else []
    return device_ops, engine if ticks(engine) else []


def load(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The summary for the run's profile, made once in a child process;
    None when the run took no profile (``--trace 0``, a rehearsal), the
    trace is missing, or the program opened no ``vgt.engine.*`` span."""
    prof = ctx.get("profile")
    trace_dir = prof.get("trace_dir") if prof else None
    if not trace_dir or not os.path.isdir(trace_dir):
        return None
    out = os.path.join(os.path.dirname(os.path.abspath(trace_dir)),
                       "trace_spans.json")
    if not (os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(trace_dir)):
        proc = subprocess.run(
            [sys.executable, "-m", "perfbench.trace_spans", trace_dir, out],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600,
        )
        if proc.returncode != 0:
            return None
    with open(out) as fh:
        summary = json.load(fh)
    return summary if summary.get("engine_thread") else None


def main(argv: List[str]) -> int:
    trace_dir, out_path = argv
    path = newest_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    summary = summarize(*read(path))
    with open(out_path, "w") as fh:
        json.dump(summary, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
