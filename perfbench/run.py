"""One command runs one cell once:

    python -m perfbench.run --workload NAME --seed N --seconds S --trace 0|1

boots the served path (``python main.py`` as a child that owns the chip),
checks it against the plain reference, warms exactly the cell's program
variants, runs lead-in / window / drain from this process (the load
generator; it never imports JAX), stops the server and prints ONE JSON
object as the last line of stdout.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a run that
also profiles four seconds right after the window, under the same load.

``--rehearse`` runs the same data files end to end on the CPU with the
tiny preset and a shrunken mix: a check of the harness, not a
measurement.  Without it a run that finds no TPU fails.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

T_START = time.monotonic()  # set-up runs from here

if __package__ in (None, ""):  # ``python perfbench/run.py``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "perfbench"

from . import check, manifest, shapes, stats, traffic  # noqa: E402
from .loadgen import Driver, visible_bias  # noqa: E402
from .server import ROOT, BenchFailure, Server, get_json  # noqa: E402

TMP = os.path.join(ROOT, ".perfbench_tmp")
PROFILE_S = 4.0
PROFILE_AFTER_S = 0.25  # the profile starts this long after the close
SNAPSHOT_GRACE_S = 0.5
DEVICE_SOURCES = ("device_trace",)


def say(msg: str) -> None:
    print(f"perfbench[{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------ rehearsal

def shrink(cell: Dict[str, Any]) -> None:
    """CPU rehearsal: the same files, lengths / clients / rate scaled
    down by the configuration's ``rehearse.scale`` so that the tiny
    preset on a CPU gets through them in seconds."""
    scale = cell["config"]["rehearse"]["scale"]
    tr, params = cell["traffic"], cell["params"]
    floor = {"prompt_tokens": traffic.min_prompt_tokens() + 2,
             "output_tokens": 4}
    for key, low in floor.items():
        dist = tr[key]
        for field in ("lo", "hi", "median", "value"):
            if field in dist:
                dist[field] = max(low, round(dist[field] * scale["tokens"]))
        if "hi" in dist and dist["hi"] <= dist["lo"]:
            dist["hi"] = dist["lo"] + 1
    if "clients" in params:
        params["clients"] = max(2, round(params["clients"] * scale["clients"]))
        params["resumed"] = max(1, round(
            params.get("resumed", params["clients"]) * scale["clients"]))
    if "rate" in params:
        params["rate"] = params["rate"] * scale["rate"]
    tr["lead_in_s"] = min(tr.get("lead_in_s", 0.0), 3.0)
    tr["drain_s"] = min(tr.get("drain_s", 0.0), 15.0)
    tr["requests_per_client"] = 64


def model_of(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """Sizes the reference runs at (HF-style keys)."""
    if not rehearse:
        return config
    layers = config["rehearse"]["overrides"].get("num_layers", 2)
    return {  # vgate_tpu/models/specs.py TINY_DENSE
        "name": config["name"], "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "torch_dtype": "float32",
    }


# --------------------------------------------------------------- set-up

def settings(config: Dict[str, Any], rehearse: bool) -> tuple:
    """(server env, what the warm-up must know of the program's
    defaults) for a measurement or for the CPU rehearsal."""
    if rehearse:
        return (config["rehearse"]["env"],
                config["rehearse"]["program_defaults"])
    return config["server"]["env"], config["program_defaults"]


def boot(config: Dict[str, Any], rehearse: bool) -> Server:
    program = config["rehearse" if rehearse else "program"]
    env = dict(settings(config, rehearse)[0])
    env["VGT_MODEL__MODEL_ID"] = (
        program["model_id"] if program["overrides"] else program["preset"]
    )
    env["TMPDIR"] = TMP  # the profile endpoint writes under the temp dir
    if rehearse:
        env["PERFBENCH_REHEARSE"] = "1"
    shutil.rmtree(TMP, ignore_errors=True)
    os.makedirs(TMP)
    return Server(config, env, free_port(), os.path.join(TMP, "server.log"))


def device_of(base: str, rehearse: bool, chips: int) -> Dict[str, Any]:
    health = get_json(base, "/health").get("device") or {}
    platform = health.get("platform")
    if not health.get("alive"):
        raise BenchFailure(f"device is not alive: {health}")
    if not rehearse and platform != "tpu":
        raise BenchFailure(
            f"no TPU: the server runs on {platform!r} (a measurement never "
            "falls back to the CPU; use --rehearse to check the harness)"
        )
    if not rehearse and health.get("num_devices") != chips:
        raise BenchFailure(
            f"the cell needs {chips} chip(s), the server has "
            f"{health.get('num_devices')}"
        )
    return {"platform": platform, "kind": health.get("device_kind"),
            "count": health.get("num_devices")}


def prefill_variants(base: str) -> set:
    """Signatures of the prefill programs compiled so far, from the
    compile ledger: ``(bucket, rows, ...)`` as the engine keys them."""
    ledger = get_json(base, "/debug/perf").get("compile_ledger") or []
    return {e["signature"] for e in ledger if e["program"] == "prefill"}


async def warm_up(driver: Driver, plan: Dict[str, Any]) -> None:
    """Resident request, then each burst until the compile ledger shows
    its (bucket, rows) variant -- a burst that straddles an engine tick
    splits into smaller groups, and is then tried again with fresh text
    (the same text would hit the prefix cache and run another program).
    Should the ledger's format change, every burst is simply sent twice."""
    import aiohttp

    base = driver.base_url
    before = prefill_variants(base)
    async with aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0)
    ) as session:
        loop = asyncio.get_running_loop()
        resident = asyncio.ensure_future(
            driver.fire(session, plan["resident"], loop.time()))
        while not driver.samples or driver.samples[0].first_t is None:
            if resident.done():
                raise BenchFailure(
                    f"warm-up resident ended early: {driver.samples[0]}")
            await asyncio.sleep(0.02)
        retried = 0
        for burst in plan["bursts"]:
            want = f"({burst['bucket']}, {burst['size']}, "
            for attempt, requests in enumerate(burst["tries"]):
                got = await asyncio.gather(*[
                    driver.fire(session, r, loop.time()) for r in requests])
                bad = [s for s in got if not s.ok]
                if bad:
                    raise BenchFailure(f"warm-up request failed: {bad[0]}")
                new = await loop.run_in_executor(
                    None, prefill_variants, base)
                if any(sig.startswith(want) for sig in new - before):
                    break
                if attempt >= 1 and not any(
                        sig.startswith("(") for sig in new):
                    break  # unreadable ledger: twice has to do
                retried += 1
        if retried:
            say(f"warm-up: {retried} burst(s) sent again")
        resident.cancel()
        await asyncio.gather(resident, return_exceptions=True)
        ladder = await driver.fire(session, plan["ladder"], loop.time())
        if not ladder.ok:
            raise BenchFailure(f"warm-up ladder request failed: {ladder}")


def wait_idle(base: str, timeout_s: float = 60.0) -> None:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        sched = get_json(base, "/stats")["engine"]["scheduler"]
        if sched["running"] == 0 and sched["waiting"] == 0:
            return
        time.sleep(0.1)
    raise BenchFailure("the engine did not go idle after the warm-up")


# --------------------------------------------------------------- window

class Window:
    """Snapshots and the profile, taken at offsets from the opening."""

    def __init__(self, base: str, seconds: float, trace: bool,
                 rehearse: bool) -> None:
        self.base, self.seconds = base, seconds
        self.stats: Dict[str, Any] = {}
        self.perf: Dict[str, Any] = {}
        self.polls: List[Dict[str, Any]] = []
        self.profile: Optional[Dict[str, Any]] = None
        self.trace_dir = os.path.join(TMP, "trace")
        self.trace = trace and not rehearse

    async def _get(self, session: Any, path: str) -> Dict[str, Any]:
        async with session.get(self.base + path) as resp:
            return await resp.json()

    def snapshot(self, name: str) -> Any:
        async def take(session: Any) -> None:
            self.stats[name] = await self._get(session, "/stats")
            self.perf[name] = await self._get(session, "/debug/perf")
            self.polls.append(self.stats[name])
        return take

    async def poll(self, session: Any) -> None:
        self.polls.append(await self._get(session, "/stats"))

    async def take_profile(self, session: Any) -> None:
        loop = asyncio.get_running_loop()
        duration = PROFILE_S
        t0 = loop.time()
        async with session.post(self.base + "/v1/profile", json={
            "duration_ms": duration * 1000.0, "out_dir": self.trace_dir,
        }) as resp:
            body = await resp.json()
            if resp.status != 200:
                raise BenchFailure(f"/v1/profile -> {resp.status}: {body}")
        self.profile = {"t0": t0, "t1": t0 + duration,
                        "returned_after_s": loop.time() - t0, **body}

    def schedule(self, with_polls: bool) -> List[tuple]:
        at = [(0.0, self.snapshot("open")),
              (self.seconds, self.snapshot("close"))]
        if with_polls:
            at += [(float(k), self.poll)
                   for k in range(1, int(self.seconds))]
        if self.trace:
            at.append((self.seconds + PROFILE_AFTER_S, self.take_profile))
        return at

    @property
    def extra_s(self) -> float:
        """How long the load has to go on after the window.  Always a
        moment, so that the closing snapshots see the engine as it was
        inside the window and not half-way through the cancellations
        that end a closed loop.  A traced run's profile is taken there
        too, under the same load, so that the tracer (it slows the host,
        and writing the trace stalls it) disturbs no number taken inside
        the window."""
        if self.trace:
            return PROFILE_AFTER_S + PROFILE_S + 0.5
        return SNAPSHOT_GRACE_S


def reduce_trace(trace_dir: str) -> Optional[Dict[str, Any]]:
    out = os.path.join(TMP, "trace_summary.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.trace", trace_dir, out],
        cwd=ROOT, env=env, timeout=600,
    )
    if proc.returncode != 0:
        raise BenchFailure(f"the trace reduction exited {proc.returncode}")
    with open(out) as fh:
        return json.load(fh)


# ----------------------------------------------------------------- main

def run(args: argparse.Namespace) -> Dict[str, Any]:
    cell = manifest.cell(args.workload)
    config, bench = cell["config"], cell["bench"]
    chips = cell["entry"]["chips"]
    if args.rehearse:
        shrink(cell)
    tr, params = cell["traffic"], cell["params"]
    env, defaults = settings(config, args.rehearse)
    max_len = int(env["VGT_MODEL__MAX_MODEL_LEN"])
    if args.trace and not args.rehearse and tr["loop"] == "open":
        # the profile is taken in the drain: arrivals must last that long
        tr["drain_s"] = max(tr.get("drain_s", 0.0), PROFILE_S + 1.0)
    plan = traffic.build_plan(tr, params, args.seed, float(args.seconds))
    longest = max(r.prompt_tokens + r.max_tokens
                  for r in plan.all_requests())
    if longest > max_len:
        raise BenchFailure(f"a request needs {longest} > {max_len} tokens")
    warm = traffic.warmup_plan(
        plan, defaults["prefill_buckets"], defaults["prefill_wave_sizes"],
        defaults["decode_chunk"], max_len, args.seed,
    )
    bias = visible_bias(tr)
    say(f"{args.workload}: {len(plan.all_requests())} planned requests, "
        f"warm-up buckets {warm['buckets']}")

    server = boot(config, args.rehearse)
    reference: Optional[check.Reference] = None
    try:
        ready_s = server.wait_ready()
        base = server.base
        device = device_of(base, args.rehearse, chips)
        say(f"server ready after {ready_s:.1f}s on {device}")
        first = get_json(base, "/stats")
        model, engine = first["config"]["model"], first["engine"]
        model_cfg = model_of(config, args.rehearse)
        kv_dtype = model_cfg.get("torch_dtype", "bfloat16")
        kv_token = shapes.kv_bytes_per_token(model_cfg, kv_dtype)
        page = int(env["VGT_TPU__KV_PAGE_SIZE"])
        if engine["kv_page_bytes"] != page * kv_token:
            raise BenchFailure(
                f"the program's KV page holds {engine['kv_page_bytes']} B, "
                f"the configuration file implies {page * kv_token} B"
            )
        job = check.ask(base, model, config)
        say("reference prompts answered")
        reference = check.Reference(
            config, job, model_cfg if args.rehearse else None)
        asyncio.run(warm_up(Driver(base, model, bias), warm))
        wait_idle(base)
        say("warm-up done")
        verdict = reference.compare()
        say(f"reference: {verdict}")

        driver = Driver(base, model, bias)
        window = Window(base, float(args.seconds), bool(args.trace),
                        args.rehearse)
        marks: Dict[str, float] = {}
        asyncio.run(driver.run(
            plan, at=window.schedule(with_polls=bool(args.trace)),
            on_start=lambda t_open: marks.update(
                setup_s=time.monotonic() - T_START + plan.lead_in_s,
                t_open_wall=time.time() + plan.lead_in_s),
            extra_s=window.extra_s,
        ))
        last = get_json(base, "/stats")
        window.polls.append(last)
    finally:
        if reference is not None:
            reference.stop()
        rc = server.stop()
    say(f"server stopped rc={rc}")

    trace_summary = None
    if window.trace:
        trace_summary = reduce_trace(window.trace_dir)
    scored = stats.scored(driver.samples, plan.loop, driver.t_open,
                          driver.t_close)
    lags = driver.lag_samples(plan.loop)
    ctx = {
        "loop": plan.loop, "samples": driver.samples, "scored": scored,
        "seconds": float(args.seconds), "chips": chips,
        "window_tokens": driver.window_tokens,
        "window_prompt_tokens": driver.window_prompt_tokens,
        "lags": lags, "setup_s": marks["setup_s"],
        "stats": window.stats, "perf": window.perf,
        "stats_polls": window.polls, "trace": trace_summary,
        "profile": window.profile, "config": config, "traffic": tr,
        "max_slots": int(env["VGT_TPU__MAX_BATCH_SLOTS"]),
        "num_layers": model_cfg["num_hidden_layers"],
        "kv_bytes_per_token": kv_token,
        "peaks": (None if args.rehearse
                  else shapes.peaks_for(device["kind"])),
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics: Dict[str, Any] = {}
    for name in manifest.metric_names(bench, args.workload, kind):
        spec = manifest.metric(name)
        if args.rehearse and spec["source"] in DEVICE_SOURCES:
            continue  # a CPU run prints no device metric
        value = manifest.reducer(spec["reducer"])(ctx, **spec["args"])
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}

    n_compiled = manifest.reducer("perf_compiles")(ctx)
    if n_compiled:
        ledger = (window.perf.get("close") or {}).get("compile_ledger", [])
        late = [f"{e['program']}{e['signature']}" for e in ledger
                if e.get("first_t", 0) >= marks["t_open_wall"]]
        print(f"perfbench: {n_compiled:.0f} program variant(s) compiled "
              f"inside the window: {late}", flush=True)
    lag_p99 = stats.percentile(lags, 99) or 0.0
    if lag_p99 > stats.SEND_LAG_BOUND_S:
        # reported, never judged: the machine froze under the generator
        # (and the server), which says nothing of the program's outputs
        say(f"the generator's p99 lateness was {lag_p99 * 1e3:.0f} ms: the "
            "host stalled in this run, and its latencies read high")
    failed = [s for s in scored if not s.ok]
    for s in failed[:3]:
        say(f"failed request: {s}")
    mem = [m.get("bytes_in_use", 0) for p in window.polls
           for m in (p.get("engine", {}).get("device_memory") or [])]
    device["memory_peak_bytes"] = max(mem) if mem else 0
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
    result = {
        "workload": args.workload, "seed": args.seed,
        "correct": bool(scored and not failed and verdict["ok"]),
        "attempted": len(scored), "failed": len(failed),
        "metrics": metrics, "device": device,
        "reference": verdict, "send_lag_p99_s": lag_p99,
        "profile": window.profile,
        # every run carries the client's percentiles, whichever of them
        # the manifest judges: the spread tables are made from these
        "extras": {
            f"{field[:-2]}_p{q}_ms": (stats.percentile(
                [getattr(s, field) for s in scored
                 if s.ok and getattr(s, field) is not None], q) or 0.0) * 1e3
            for field, qs in (("ttft_s", (50, 90, 95)),
                              ("tpot_s", (50, 90)), ("max_gap_s", (95,)))
            for q in qs
        },
        "in_window": {"output_tokens": driver.window_tokens,
                      "compiled": n_compiled,
                      "engine_host_share": manifest.reducer(
                          "perf_host_share")(ctx),
                      "slots_running_at_ends": [
                          (window.stats.get(k) or {}).get("engine", {}).get(
                              "scheduler", {}).get("running")
                          for k in ("open", "close")],
                      "in_flight_at_close": sum(
                          1 for s in driver.samples
                          if s.end_t is None or s.end_t >= driver.t_close)},
    }
    if args.rehearse:
        result["rehearsal"] = True
    if trace_summary is not None:
        from .trace import breakdown
        result["breakdown"] = breakdown(trace_summary)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchFailure as exc:
        print(f"perfbench: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
