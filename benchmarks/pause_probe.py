"""A builder's run of one benchmark cell that also brings back what the
program itself recorded about delivery gaps and pauses (PR 35): a tool
beside ``bench_kernels.py``, not a cell.

    chiprun -- python benchmarks/pause_probe.py --workload NAME \
        --seed N --seconds 51 [--trace 1] [--stall-at 20 --stall-s 1.0]

runs ``perfbench.run`` unchanged (same server, same load, same result
line, printed first) and then one more JSON line, ``{"probe": ...}``:
``/debug/perf -> pauses`` as the window's closing snapshot and a last
fetch after the load held them, the growth inside the window of
``totals.pauses`` / ``delivery_gaps`` / ``gc`` / the hand-off's
counters, the collector's callbacks a second, ``totals.moe`` and
``totals.dsa`` at the end (the expert layers' counters, ``overflow``
among them; the learned selection's), the growth of ``decode_steps``
beside ``decode_steps_fused_head`` (PR 50: the steps whose program kept
its logits on the chip) and of ``totals.eva`` (PR 52), after a traced
run the device's seconds under the scopes ``head`` / ``logits`` /
``sample`` (``head_scopes``), and the server log's ``engine_pause`` lines (but the warm-up's, whose cause is ``compile``).
``--stall-at S`` arms the ``stall`` fault's ``delay`` once, S seconds
into the window, through ``POST /debug/faults`` (the server is started
with ``VGT_FAULTS_HTTP=1``): the provoked pause of the issue's
acceptance.  ``--out`` also writes both lines to a file
(``chiprun_out/...`` comes back from the chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402
from perfbench.reducers.stats_delta import dig  # noqa: E402
from perfbench.server import get_json  # noqa: E402

KEPT: Dict[str, Any] = {}


def grown(window: bench.Window, path: str) -> Any:
    """Growth of ``totals.<path>`` between the window's two ends: a
    number, or a dict of numbers key by key."""
    a = dig(window.perf.get("open"), "totals." + path)
    b = dig(window.perf.get("close"), "totals." + path)
    if a is None or b is None:
        return None
    if isinstance(b, dict):
        return {k: round(v - a.get(k, 0), 6) for k, v in b.items()}
    return round(b - a, 6)


def probing(stall_at: Optional[float], stall_s: float) -> None:
    """Wrap the harness's window and server so that they keep what the
    probe reports; nothing the harness measures is changed."""

    class ProbeWindow(bench.Window):
        def __init__(self, *args: Any, **kwargs: Any) -> None:
            super().__init__(*args, **kwargs)
            KEPT["window"] = self

        async def arm_stall(self, session: Any) -> None:
            spec = f"stall:delay:delay={stall_s}:times=1"
            async with session.post(self.base + "/debug/faults",
                                    json={"faults": spec}) as resp:
                KEPT["armed"] = {"status": resp.status,
                                 "body": await resp.json()}

        def schedule(self, with_polls: bool) -> List[tuple]:
            at = super().schedule(with_polls)
            if stall_at is not None:
                at.append((stall_at, self.arm_stall))
            return at

    class ProbeServer(bench.Server):
        def stop(self) -> Optional[int]:
            if self.proc.poll() is None and "last" not in KEPT:
                try:
                    KEPT["last"] = get_json(self.base, "/debug/perf")
                except Exception as exc:  # the run's result stands
                    KEPT["last"] = {"error": repr(exc)}
            rc = super().stop()
            with open(self.log_path, "rb") as fh:
                KEPT["log"] = [
                    line for line in
                    fh.read().decode("utf-8", "replace").splitlines()
                    if "engine_pause" in line and '"compile"' not in line
                ]
            return rc

    bench.Window, bench.Server = ProbeWindow, ProbeServer
    if stall_at is not None:
        os.environ["VGT_FAULTS_HTTP"] = "1"


def head_scopes() -> Optional[Dict[str, Any]]:
    """Seconds and share of busy time a traced run's device spent ending
    its steps: the scopes ``head`` (the fused pass), ``logits`` and
    ``sample`` (the head's array, the edits and the sampler; the prompt
    programs' few rows among them), through the harness's own reading
    of ``perfbench.trace_scopes`` (its ``scope_share`` reducer's)."""
    from perfbench.reducers import scope_share

    data = scope_share.summary({"trace": 1})
    if not data or data["busy_s"] <= 0:
        return None
    seconds = {
        scope: sum(s for name, s in data["scope_seconds"].items()
                   if scope in name.split("/"))
        for scope in ("head", "logits", "sample")}
    return {"busy_s": data["busy_s"], "seconds": seconds,
            "share_pct": 100 * sum(seconds.values()) / data["busy_s"]}


def report(seconds: float, trace: int = 0) -> Dict[str, Any]:
    window = KEPT["window"]
    close = window.perf.get("close") or {}
    gc_n = grown(window, "gc.gc_collections")
    return {
        "pauses_at_close": close.get("pauses"),
        "pauses_at_end": (KEPT.get("last") or {}).get("pauses"),
        # the expert layers' counters over the whole run (PR 39:
        # ``overflow``, the dispatch trips beyond a block's first)
        "moe_at_end": dig(KEPT.get("last"), "totals.moe"),
        # the learned selection's (PR 41: ``rows_fetched`` over
        # ``rows_attended`` is the fetch's amplification)
        "dsa_at_end": dig(KEPT.get("last"), "totals.dsa"),
        "in_window": {
            name: grown(window, name) for name in (
                "wall_s", "deliveries", "delivery_gap_s", "delivery_gaps",
                "pauses", "gc.gc_s", "gc.gc_collections", "gc.gc_seconds",
                "gateway.stream_handoffs", "gateway.handoff_wait_s",
                "gateway.handoff_waits", "decode_steps",
                "decode_steps_fused_head",
                # an EVA spec's rows and windows (PR 52: the summary
                # rows a decode step writes are 128 a layer of every
                # window it closes, ``chunk_rows_written`` over
                # ``windows_closed``)
                "eva",
                # the prompt kernel's tiles a head (PR 54:
                # ``interior_tiles`` over ``tiles`` is how often the body
                # without position tests runs)
                "prefill_attn",
            )
        },
        "head_scopes": head_scopes() if trace else None,
        "gc_callbacks_per_s": (
            None if gc_n is None else sum(gc_n.values()) / seconds),
        "gc_max_s": dig(close, "totals.gc.gc_max_s"),
        "armed": KEPT.get("armed"),
        "engine_pause_log_lines": KEPT.get("log"),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--stall-at", type=float, default=None)
    ap.add_argument("--stall-s", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    probing(args.stall_at, args.stall_s)
    try:
        result = bench.run(args)
    except bench.BenchFailure as exc:
        print(f"pause_probe: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    lines = [json.dumps(result),
             json.dumps({"probe": report(args.seconds, args.trace)})]
    for line in lines:
        print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
