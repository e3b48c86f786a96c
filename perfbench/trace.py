"""From a profiler trace (``.xplane.pb``) to numbers.

    JAX_PLATFORMS=cpu python -m perfbench.trace TRACE_DIR OUT.json

reads the newest ``.xplane.pb`` under TRACE_DIR with
``jax.profiler.ProfileData`` and writes a summary the reducers read:

* per device: busy seconds (the union of the intervals in which an
  operation ran), the traced window (first operation start to last
  operation end), and every operation's SELF time (its duration minus the
  operations nested inside it, so a ``while`` does not count its body
  twice), named ``<module>/<op>`` after the XLA module whose execution
  contains it (``jit__decode_chunk/paged_decode_attention_pallas.8``);
* the idle gaps between operations, charged to what the host was doing:
  each gap is sampled at up to 16 points, and a point's share of the gap
  goes to the innermost (shortest) host event ``<thread>:<event>`` that
  covers it on any thread.

The interval arithmetic works on plain tuples so that it can be tested
without a trace; only ``read_planes`` touches the profiler.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_s, end_s

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME = re.compile(r"^%?([^\s=(]+)")  # "%fusion.3 = bf16[...] ..." -> fusion.3
MIN_GAP_S = 20e-6  # shorter pauses are launch latency, not idleness
GAP_POINTS = 16
TOP = 10


def merged(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: List[Event]) -> List[Tuple[str, float, float]]:
    """(name, start, self seconds) per event of one line: duration minus
    the time covered by events nested directly inside it."""
    order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    out: List[List[Any]] = []
    stack: List[int] = []  # indices into out of the open ancestors
    ends: List[float] = []
    for name, a, b in order:
        while stack and a >= ends[-1]:
            stack.pop()
            ends.pop()
        if stack:
            out[stack[-1]][2] -= (min(b, ends[-1]) - a)
        out.append([name, a, b - a])
        stack.append(len(out) - 1)
        ends.append(b)
    return [(n, a, max(0.0, s)) for n, a, s in out]


def module_of(modules: List[Event]) -> Any:
    """Function start_s -> name of the module execution that contains it
    (trailing ``(id)`` stripped), or ''."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= mods[i][2]:
            return re.sub(r"\(\d+\)$", "", mods[i][0])
        return ""
    return find


class HostIndex:
    """Host events of every thread, for 'what was the host doing at t'."""

    def __init__(self, threads: Dict[str, List[Event]]) -> None:
        self.threads = {}
        for thread, events in threads.items():
            order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
            parent: List[int] = []
            stack: List[int] = []
            for i, (_, a, _b) in enumerate(order):
                while stack and a >= order[stack[-1]][2]:
                    stack.pop()
                parent.append(stack[-1] if stack else -1)
                stack.append(i)
            self.threads[thread] = (order, [e[1] for e in order], parent)

    def active(self, t: float) -> Optional[str]:
        """The shortest event covering ``t`` over all threads:
        ``<thread>:<event>``."""
        best: Optional[Tuple[float, str]] = None
        for thread, (order, starts, parent) in self.threads.items():
            i = bisect.bisect_right(starts, t) - 1
            while i >= 0:
                name, a, b = order[i]
                if b >= t:
                    if best is None or b - a < best[0]:
                        best = (b - a, f"{thread}:{name}")
                    break
                i = parent[i]
        return None if best is None else best[1]


def summarize(devices: Dict[str, Dict[str, List[Event]]],
              host: Dict[str, List[Event]]) -> Dict[str, Any]:
    """``devices``: plane name -> line name -> events."""
    index = HostIndex(host)
    per_device = []
    op_seconds: Dict[str, float] = defaultdict(float)
    op_counts: Dict[str, int] = defaultdict(int)
    gap_seconds: Dict[str, float] = defaultdict(float)
    for plane, lines in sorted(devices.items()):
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        find = module_of(lines.get(MODULES_LINE, []))
        for name, start, self_s in self_times(ops):
            module = find(start)
            full = f"{module}/{name}" if module else name
            op_seconds[full] += self_s
            op_counts[full] += 1
        busy = merged((a, b) for _, a, b in ops)
        t0, t1 = busy[0][0], busy[-1][1]
        busy_s = sum(b - a for a, b in busy)
        for (_, gap_a), (gap_b, _) in zip(busy, busy[1:]):
            gap = gap_b - gap_a
            if gap < MIN_GAP_S:
                gap_seconds["short_gaps"] += gap
                continue
            k = min(GAP_POINTS, max(1, int(gap / (2 * MIN_GAP_S))))
            for i in range(k):
                who = index.active(gap_a + (i + 0.5) * gap / k)
                gap_seconds[who or "unattributed"] += gap / k
        per_device.append({"plane": plane, "busy_s": busy_s,
                           "window_s": t1 - t0, "t0_s": t0, "t1_s": t1})
    n = max(1, len(per_device))
    return {
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "window_s": sum(d["window_s"] for d in per_device) / n,
        # summed over devices; shares divide by the summed busy time
        "op_seconds": dict(op_seconds),
        "op_counts": dict(op_counts),
        "gap_seconds": dict(gap_seconds),
    }


def breakdown(summary: Dict[str, Any]) -> Dict[str, List[List[Any]]]:
    def top(d: Dict[str, float]) -> List[List[Any]]:
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(summary["op_seconds"]),
            "idle_gaps": top(summary["gap_seconds"])}


def read_planes(path: str) -> Tuple[Dict[str, Dict[str, List[Event]]],
                                    Dict[str, List[Event]]]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
            ]
            if is_device and line.name == OPS_LINE:
                # the TPU names an op by its whole HLO text
                events = [(OP_NAME.match(n).group(1) if n else n, a, b)
                          for n, a, b in events]
            if is_device:
                devices.setdefault(plane.name, {})[line.name] = events
            elif events:
                # "python3/1234" -> "python3": thread ids change per run
                thread = re.sub(r"/\d+$", "", line.name)
                if plane.name != "/host:CPU":
                    thread = f"{plane.name}:{thread}"
                host.setdefault(thread, []).extend(events)
    return devices, host


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def main(argv: List[str]) -> int:
    trace_dir, out_path = argv
    path = newest_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    devices, host = read_planes(path)
    summary = summarize(devices, host)
    summary["file_bytes"] = os.path.getsize(path)
    with open(out_path, "w") as fh:
        json.dump(summary, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
