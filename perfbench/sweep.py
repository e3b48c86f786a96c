"""Find an open-loop cell's knee, once, when the cell is defined.

    python -m perfbench.sweep --workload NAME --rates 12,16,20,24,28
        [--seconds 20] [--seed N]

One server boot; for each rate a lead-in and a window of ``--seconds``
of the cell's traffic, no drain.  A rate is SUSTAINED when the number of
requests in flight is no higher at the window's end than at its middle,
within a tenth (above the knee the queue grows all through the window).  The cell then
runs at four fifths of the highest sustained rate, rounded to 0.5, as a
number in ``perfbench/workloads/<name>.json``; the table goes to PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from . import manifest, stats, traffic
from .loadgen import Driver, visible_bias
from .run import boot, device_of, say, settings, wait_idle, warm_up
from .server import BenchFailure, get_json


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--lead-in", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.cell(args.workload)
    config, tr = cell["config"], dict(cell["traffic"], drain_s=0.0)
    if args.lead_in is not None:
        tr["lead_in_s"] = args.lead_in
    if tr["loop"] != "open":
        raise SystemExit("only an open-loop cell has a knee")
    if args.rehearse:
        from .run import shrink
        cell["traffic"] = tr
        shrink(cell)
    env, defaults = settings(config, args.rehearse)
    rates = [float(r) for r in args.rates.split(",")]
    bias = visible_bias(tr)
    plans = [traffic.build_plan(tr, {"rate": r}, args.seed, args.seconds)
             for r in rates]
    server = boot(config, args.rehearse)
    rows = []
    try:
        server.wait_ready()
        device = device_of(server.base, args.rehearse,
                           cell["entry"]["chips"])
        model = get_json(server.base, "/stats")["config"]["model"]
        warm = traffic.warmup_plan(
            plans[-1], defaults["prefill_buckets"],
            defaults["prefill_wave_sizes"], defaults["decode_chunk"],
            int(env["VGT_MODEL__MAX_MODEL_LEN"]), args.seed)
        asyncio.run(warm_up(Driver(server.base, model, bias), warm))
        for rate, plan in zip(rates, plans):
            wait_idle(server.base, timeout_s=300.0)
            driver = Driver(server.base, model, bias)

            def mark(name):
                async def take(session):
                    driver.mark_in_flight(name)
                return take
            asyncio.run(driver.run(plan, at=[
                (args.seconds / 2, mark("middle")),
                (args.seconds - 0.05, mark("end"))]))
            scored = stats.scored(driver.samples, "open", 0, 0)
            done = [s for s in scored if s.ok]
            row = {
                "rate": rate, "sent": len(scored), "finished": len(done),
                "in_flight_middle": driver.in_flight_marks.get("middle"),
                "in_flight_end": driver.in_flight_marks.get("end"),
                "ttft_p50_ms": _ms(stats.percentile(
                    [s.ttft_s for s in done], 50)),
                "ttft_p90_ms": _ms(stats.percentile(
                    [s.ttft_s for s in done], 90)),
                "tpot_p90_ms": _ms(stats.percentile(
                    [s.tpot_s for s in done if s.tpot_s], 90)),
                "send_lag_p99_ms": _ms(stats.percentile(
                    driver.send_lags, 99)),
            }
            # a tenth of slack: the count in flight wanders by about its
            # square root even when nothing grows
            row["sustained"] = (
                row["in_flight_end"] <= 1.1 * row["in_flight_middle"])
            rows.append(row)
            say(json.dumps(row))
    except BenchFailure as exc:
        print(f"perfbench.sweep: FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        server.stop()
    print(json.dumps({"workload": args.workload, "device": device,
                      "seconds": args.seconds, "rows": rows}))
    return 0


def _ms(x):
    return None if x is None else round(x * 1000.0, 2)


if __name__ == "__main__":
    sys.exit(main())
