"""Per-tick perf attribution for the engine loop: where did the time go?

The ROADMAP's decode-roofline item (13.2% -> >=40%) rests on the claim
that per-token host dispatch + readback + Python scheduler overhead
dominates the loss.  This module is the measurement that proves or
sizes that claim — and the evidence base every later perf PR (the
tick -> megatick refactor first) is judged against.

Three pieces, all owned by one :class:`PerfRecorder` that lives next to
the engine's FlightRecorder (engine-thread hot path takes no locks;
snapshot readers copy defensively under the GIL):

* **TickProfile** — every engine tick decomposed into phases:

  - ``host_s``     whatever no bracket covers (derived: tick wall minus
                   the measured phases, so the phases sum to the tick
                   wall by construction);
  - ``schedule_s`` evacuations/handoffs/aborts, admission and decode
                   scheduling up to the dispatch;
  - ``state_s``    host-side input preparation (``_build_decode_state``,
                   prefill group arrays, logit-bias/min-token arrays);
  - ``dispatch_s`` jitted-call return, i.e. trace + enqueue (a FIRST
                   dispatch of a program variant includes its XLA
                   compile — the compile ledger records that share);
  - ``device_s``   host blocked on device execution, measured at the
                   readback boundary the hot path already has
                   (``block_until_ready`` before the existing
                   ``device_get`` — no new sync is added, the one sync
                   is split into wait-for-compute + transfer);
  - ``readback_s`` the device->host transfer (``device_get``);
  - ``detok_s``    token append, stop detection, stream callbacks.

  Host-side KV swap traffic (runtime/kv_swap.py) is currently left in
  ``host_s`` — it is host-paid recovery work, not steady-state decode.

  The engine measures a phase with ONE bracket, :meth:`PerfRecorder.span`
  (a context manager).  It accrues the phase seconds and, only while a
  ``POST /v1/profile`` capture runs (:func:`set_capturing`), also opens
  a ``jax.profiler.TraceAnnotation`` named ``vgt.engine.<span>`` on the
  engine thread, so the device trace carries the engine's own clock.
  With no capture a bracket costs one flag test.  :class:`GatewayPerf`
  (the ``GATEWAY`` singleton) does the same on the gateway's event-loop
  thread with begin/end pairs (two of its three sites run once a
  token): ``vgt.gateway.ingress`` / ``stream_detok`` / ``sse_write``,
  with monotone window counters.

* **Compile ledger** — one entry per compiled program variant
  (program family, signature, trigger, count, seconds), hooked exactly
  where the engine already stamps ``compiling=True`` heartbeats.  In
  steady state the ledger is frozen; entries appearing under load are a
  recompile storm (``VgtRecompileStorm``).

* **Rolling window** — live tok/s, MFU and %-of-HBM-roofline computed
  from the engine's own geometry (observability/roofline.py — the same
  peak table the benches use) plus the host-overhead ratio
  ((host_s + schedule_s + state_s) / wall over the window — the
  engine's own Python outside the jitted call and the device): the
  single number the megatick refactor exists to drive down.

* **Delivery gaps and pauses** — the silence a running stream sees,
  measured where it is made.  Every decode readback that handed tokens
  to a running stream (:meth:`PerfRecorder.note_delivery`) closes a
  *delivery gap*: the time since the readback before it.  All gaps go
  into one monotone histogram; a gap of :data:`PAUSE_S` or more is a
  *pause*, and is charged to exactly ONE cause (:data:`PAUSE_CAUSES`)
  from clocks differenced between the two readbacks.  (``stall`` stays
  the flight recorder's word for a loop the watchdog declared wedged.)
  :class:`GatewayPerf` times the hand-off of each readback to the event
  loop the same way, and :class:`GcClock` (``GC``) the process's
  garbage collector.

* **The device clock** (:class:`DeviceClock`) — the device's seconds
  by program.  ``device_s`` above is the time the HOST is blocked, which
  says nothing of the device once the host is hidden behind it.  The
  device runs launches in order, so the time each launch FINISHED,
  stamped by one thread that waits for one launch after another, tiles
  the device's time by program with no profiler: ``totals.device_clock``
  (busy and idle seconds, seconds / launches / queued seconds a
  program), the roofline gauge's denominator, the step-time histogram,
  and the launches a pause record names.

Surfaces: ``GET /debug/perf`` (auth-gated, drain-uncounted), the
``/stats`` engine block (``perf``), metrics
``vgt_tick_phase_seconds{phase}`` / ``vgt_recompiles_total{variant}`` /
``vgt_decode_mfu`` / ``vgt_decode_hbm_roofline_pct`` /
``vgt_host_overhead_ratio`` / ``vgt_device_seconds_total{program}``,
and the loadlab artifact's per-cell
``perf`` block (loadlab/runner.py scrapes ``/debug/perf`` around every
QPS cell).
"""

from __future__ import annotations

import bisect
import contextvars
import gc
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from vgate_tpu import metrics
from vgate_tpu.observability.roofline import EngineRoofline

# per-request phase sums the flight recorder keeps (flight.py)
REQUEST_TOTALS = ("admitted", "queue_wait_s", "first_tokens", "prefill_s")
# scalar totals() keys that add across dp replicas (merge_snapshots)
ADDITIVE_TOTALS = (
    "decode_device_s", "decode_ctx_token_steps",
    "engine_cpu_s", "engine_cpu_in_wait_s",
    "membership_changes", "pipeline_drains",
    "deliveries", "delivery_gap_s",
) + REQUEST_TOTALS

# the fixed phase taxonomy (docs/observability.md "Perf attribution")
PHASES = (
    "host", "schedule", "state", "dispatch", "device", "readback", "detok",
)
# all but ``host``, which is what no bracket covers
MEASURED_PHASES = PHASES[1:]
# the phase each engine span accrues into; spans not listed here
# (``idle_wait``) only annotate the trace
SPAN_PHASE = {
    "schedule": "schedule",
    "state": "state",
    "prefill_dispatch": "dispatch",
    "decode_dispatch": "dispatch",
    "device_wait": "device",
    "readback": "readback",
    "emit": "detok",
}
# spans in which the engine thread is blocked on the device: their CPU
# time is ``engine_cpu_in_wait_s`` (time.thread_time, per chunk)
WAIT_SPANS = frozenset(("device_wait", "readback"))

# A delivery gap this long is a PAUSE: it gets a record and one cause.
PAUSE_S = 0.5
# first match wins (PerfRecorder._pause); the causes partition the
# pauses' seconds
PAUSE_CAUSES = ("compile", "prefill", "gc", "off_cpu", "device", "host")
PAUSES_KEPT = 32
# an admission wave explains a pause only if the wait for the device is
# within this factor of what the gap's prompt tokens take at the pace
# of the waits on waves so far (a 182-token wave does not take 2.9 s)
WAVE_SLACK = 4.0
# upper edges of the gap histograms (delivery gaps, hand-off waits): a
# factor sqrt(2) apart from 8 ms to 8,192 ms, then an overflow.  The
# keys are the edges in ms, as /debug/perf and the reducers read them.
_GAP_EDGES_MS = tuple(8.0 * 2 ** (k / 2) for k in range(21))
GAP_EDGES_S = tuple(ms / 1e3 for ms in _GAP_EDGES_MS)
GAP_KEYS = tuple(f"{round(ms, 2):g}" for ms in _GAP_EDGES_MS) + ("inf",)
# what the engine counts between two deliveries (PerfRecorder.count)
GAP_COUNTS = ("prompt_programs", "prompt_tokens", "decode_steps", "swap_ins")

# the step programs, as EngineCore._PROGRAMS names them: the device
# clock's rows.  The first three are the prompt programs.
DEVICE_PROGRAMS = (
    "prefill", "suffix_prefill", "chunked_prefill", "decode", "spec_verify",
)
PROMPT_PROGRAMS = frozenset(DEVICE_PROGRAMS[:3])
# launches the device clock holds at most, the one it waits for among
# them (an engine has two chunks and one wave in flight); a post past
# that is dropped and counted
DEVICE_QUEUE_MAX = 64
# launches a pause record names at most (the last of its gap)
PAUSE_PROGRAMS_KEPT = 8

# True only while EngineCore.capture_profile runs a profiler session:
# the one flag every bracket tests before building a TraceAnnotation
_capturing = False


def set_capturing(flag: bool) -> None:
    global _capturing
    _capturing = bool(flag)


def capturing() -> bool:
    return _capturing


def _open_annotation(name: str, args: Optional[Callable[[], dict]]):
    """An entered ``TraceAnnotation`` (capture running) — the arguments
    become the trace event's stats."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name, **(args() if args is not None else {}))
    ann.__enter__()
    return ann


class _Bracket:
    """``with perf.span(...) as b:`` — times the block on the recorder's
    clock, hands ``(name, seconds)`` to the recorder, and mirrors the
    block into the profiler trace while a capture runs."""

    __slots__ = ("_sink", "name", "_args", "t0", "_ann", "seconds")

    def __init__(self, sink: "PerfRecorder", name: str, args) -> None:
        self._sink = sink
        self.name = name
        self._args = args
        self._ann = None
        self.seconds = 0.0

    def __enter__(self) -> "_Bracket":
        if _capturing:
            self._ann = _open_annotation(
                "vgt.engine." + self.name, self._args
            )
        # when the block began, on the recorder's clock
        self.t0 = self._sink._span_begin(self.name)
        return self

    def note(self, **args: Any) -> None:
        """Arguments known only at the block's end (tokens emitted)."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        self.seconds = self._sink._span_end(self.name, self.t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class GapHistogram:
    """Monotone counts of durations on the fixed edges ``GAP_EDGES_S``
    (a duration equal to an edge falls under it), served as {upper edge
    in ms: count}."""

    __slots__ = ("counts",)

    def __init__(self) -> None:
        self.counts = [0] * len(GAP_KEYS)

    def add(self, seconds: float) -> None:
        self.counts[bisect.bisect_left(GAP_EDGES_S, seconds)] += 1

    def to_dict(self) -> Dict[str, int]:
        return dict(zip(GAP_KEYS, self.counts))


class GcClock:
    """The process's garbage collector on the recorder's clock: ONE
    ``gc.callbacks`` entry (:meth:`install`, at app start-up) that times
    every collection, whichever thread runs it.  A collection holds the
    GIL from start to stop and none starts inside another, so one stamp
    is enough.  ``thread_s`` keeps a running sum for each thread that
    asked for one (:meth:`watch`: an engine thread, so that a pause can
    tell its own collections from the event loop's).  While a profile
    capture runs a collection is also a ``vgt.host.gc`` trace span on
    the thread that collects.  ``enabled`` false: one flag test."""

    def __init__(self, clock: Any = time.perf_counter) -> None:
        self._clock = clock
        self.enabled = True
        self.installed = False
        self.gc_s = 0.0
        self.gc_max_s = 0.0  # the longest single collection
        self.collections = [0, 0, 0]  # by generation
        self.seconds = [0.0, 0.0, 0.0]
        self.thread_s: Dict[int, float] = {}
        self._t0: Optional[float] = None
        self._ann = None

    def install(self) -> None:
        if not self.installed:
            gc.callbacks.append(self._on_gc)
            self.installed = True

    def remove(self) -> None:
        if self.installed:
            gc.callbacks.remove(self._on_gc)
            self.installed = False
            self._t0 = None

    def watch(self) -> int:
        """Keep a sum of the calling thread's collections; returns the
        key of ``thread_s`` it is kept under."""
        ident = threading.get_ident()
        self.thread_s.setdefault(ident, 0.0)
        return ident

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        if phase == "start":
            if _capturing:
                self._ann = _open_annotation(
                    "vgt.host.gc", lambda: {"gen": info["generation"]}
                )
            self._t0 = self._clock()
            return
        if self._t0 is None:
            return  # installed between a start and its stop
        seconds = self._clock() - self._t0
        self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.gc_s += seconds
        self.gc_max_s = max(self.gc_max_s, seconds)
        self.collections[info["generation"]] += 1
        self.seconds[info["generation"]] += seconds
        ident = threading.get_ident()
        if ident in self.thread_s:
            self.thread_s[ident] += seconds

    def totals(self) -> Dict[str, Any]:
        return {
            "gc_s": round(self.gc_s, 6),
            "gc_max_s": round(self.gc_max_s, 6),
            "gc_collections": {
                str(gen): n for gen, n in enumerate(self.collections)
            },
            "gc_seconds": {
                str(gen): round(s, 6) for gen, s in enumerate(self.seconds)
            },
        }


GC = GcClock()


# boot phases of the process (seconds; the program's part of setup_s):
# weights / digest / ready — first boot only, a supervised rebuild does
# not overwrite them
BOOT_SECONDS: Dict[str, float] = {}


def note_boot(phase: str, seconds: float) -> None:
    BOOT_SECONDS.setdefault(phase, round(float(seconds), 3))


def process_age_s() -> Optional[float]:
    """Seconds since this process started (``/proc``), None elsewhere."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None

# gauges + ledger-size trims run at most this often (engine thread)
_FLUSH_INTERVAL_S = 0.5


class DeviceClock:
    """The device's seconds by program, from the times its launches
    FINISHED.  The engine thread posts every launch that goes through
    ``EngineCore._launch`` (:meth:`post`: the program, the time the
    jitted call began, what it carried and one small output that nothing
    donates); ONE daemon thread takes them in order, waits for each
    one's output and stamps ``t_done``.  The device runs launches in
    order, so for launch i::

        start    = max(t_done[i-1], t_call[i])
        seconds  = t_done[i] - start               the device's, on i
        idle     = max(0, t_call[i] - t_done[i-1])   the device's, before i
        queued_s = start - t_call[i]     i's wait behind the launch ahead

    and busy + idle seconds tile the time from the first call to the
    last stamp exactly.  A late stamp (the thread wants the GIL back: at
    most the switch interval) moves a boundary between two neighbours
    and loses nothing.

    What it cannot see: device work launched outside ``_launch`` (the
    joining rows' edit, page-table and sampling-row uploads, swap and
    copy-on-write copies, a first token's sampling) falls into the
    seconds of the next launch; a launch's seconds begin at its CALL, so
    where the device had nothing queued ahead the call's own time (trace
    and enqueue; a fresh variant's compile) counts as the device's; and
    what happens INSIDE a program, by kernel and by scope, is the
    profile's to say.

    Bounded and mortal: at most ``DEVICE_QUEUE_MAX`` launches are held
    (a post past that is ``dropped``), the thread starts at the first
    post and ends at :meth:`shutdown` (the core's ``stop``), which also
    lets go of every output held; a core started again (``warmup`` stops
    the core it started) gets a fresh thread at its next post.  A wait
    that raises (a poisoned launch after a device fault) is counted
    under ``dropped`` and skipped, its seconds going to the next launch.
    ``enabled`` false: a post tests one flag and no thread ever
    starts."""

    def __init__(
        self,
        clock: Any = time.perf_counter,
        wait: Optional[Callable[[Any], Any]] = None,
        enabled: bool = True,
    ) -> None:
        self._clock = clock
        # None: jax.block_until_ready, which releases the GIL (imported
        # on the thread; tests hand in a fake)
        self._wait = wait
        self.enabled = enabled
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        # launches posted and not stamped yet, the one waited for first
        self._open: "deque[tuple]" = deque()
        # the last launches stamped, (t_done, record): for a pause record
        self._done: "deque[tuple]" = deque(maxlen=PAUSE_PROGRAMS_KEPT)
        self._thread: Optional[threading.Thread] = None
        self._last_done: Optional[float] = None
        self.busy_s = 0.0
        self.idle_s = 0.0
        self.decode_steps = 0
        self.prompt_tokens = 0
        self.dropped = 0
        self._programs = {
            name: {"n": 0, "s": 0.0, "queued_s": 0.0}
            for name in DEVICE_PROGRAMS
        }
        self._seconds = {
            name: metrics.DEVICE_SECONDS.labels(program=name)
            for name in DEVICE_PROGRAMS + ("idle",)
        }
        self._step_time = {
            name: metrics.ENGINE_STEP_TIME.labels(
                kind="prefill" if name in PROMPT_PROGRAMS else "decode"
            )
            for name in DEVICE_PROGRAMS
        }

    def post(
        self, program: str, t_call: float, output: Any,
        trace_id: Optional[str] = None, **carried: int,
    ) -> None:
        """THE post (engine thread, after the jitted call returned):
        ``output`` is an array of the launch that no later launch takes
        as a donated argument, never a pool, a ring or a state (it would
        be deleted under the waiter); ``carried`` is what the launch
        worked on (a decode chunk's ``steps`` and ``rows``; a prompt
        program's ``prompt_tokens``, ``rows`` and ``bucket``);
        ``trace_id`` the step-time histogram's exemplar."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._open) >= DEVICE_QUEUE_MAX:
                self.dropped += 1
                return
            self._open.append((program, t_call, carried, trace_id, output))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="vgt-device-clock", daemon=True
                )
                self._thread.start()
            self._wake.notify()

    def _run(self) -> None:
        me = threading.current_thread()
        wait = self._wait
        if wait is None:
            import jax

            wait = jax.block_until_ready
        while True:
            with self._lock:
                while self._thread is me and not self._open:
                    self._wake.wait()
                if self._thread is not me:
                    return  # shut down
                program, t_call, carried, trace_id, output = self._open[0]
            # while a capture runs the wait is a trace span that should
            # lie on top of the device's module event.  NOT under
            # ``vgt.engine.``: the benchmark charges device pauses to
            # the engine thread's spans of that prefix
            ann = (
                _open_annotation("vgt.device." + program, lambda: carried)
                if _capturing else None
            )
            raised = False
            try:
                wait(output)
            except Exception:
                raised = True
            t_done = self._clock()  # the stamp comes first
            del output
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                if self._thread is not me:
                    return  # shut down during the wait: nothing to stamp
                self._open.popleft()
                if raised:
                    self.dropped += 1
                    continue
                seconds, idle = self._book(program, t_call, carried, t_done)
            self._seconds[program].inc(seconds)
            if idle:
                self._seconds["idle"].inc(idle)
            metrics.observe_with_exemplar(
                self._step_time[program], seconds, trace_id=trace_id
            )

    def _book(
        self, program: str, t_call: float, carried: Dict[str, int],
        t_done: float,
    ) -> tuple:
        last = self._last_done
        start = t_call if last is None else max(last, t_call)
        idle = 0.0 if last is None else max(0.0, t_call - last)
        seconds, queued = t_done - start, start - t_call
        self._last_done = t_done
        self.busy_s += seconds
        self.idle_s += idle
        row = self._programs[program]
        row["n"] += 1
        row["s"] += seconds
        row["queued_s"] += queued
        if program == "decode":
            self.decode_steps += carried.get("steps", 0)
        self.prompt_tokens += carried.get("prompt_tokens", 0)
        self._done.append((t_done, {
            "program": program, **carried, "queued_s": round(queued, 6),
            "device_s": round(seconds, 6),
        }))
        return seconds, idle

    @property
    def decode_s(self) -> float:
        """The device's seconds on decode chunks and verify rounds: what
        the roofline gauge divides the modelled decode bytes by."""
        rows = self._programs
        return rows["decode"]["s"] + rows["spec_verify"]["s"]

    def launches_since(self, t0: float) -> List[Dict[str, Any]]:
        """The launches stamped at ``t0`` or later and those still open
        (``open``: seconds so far), oldest first, the last
        ``PAUSE_PROGRAMS_KEPT`` of them: what a pause record names."""
        now = self._clock()
        with self._lock:
            out = [launch for t_done, launch in self._done if t_done >= t0]
            last = self._last_done
            for program, t_call, carried, _, _ in self._open:
                start = min(now, t_call if last is None
                            else max(last, t_call))
                out.append({
                    "program": program, **carried,
                    "queued_s": round(start - t_call, 6),
                    "device_s": round(now - start, 6), "open": True,
                })
                last = now  # the launches behind it have not started
        return out[-PAUSE_PROGRAMS_KEPT:]

    def totals(self) -> Dict[str, Any]:
        """Monotone, every key there from boot (the benchmark's ratios
        read a missing path as no reading)."""
        with self._lock:
            return {
                "busy_s": round(self.busy_s, 6),
                "idle_s": round(self.idle_s, 6),
                "decode_steps": self.decode_steps,
                "prompt_tokens": self.prompt_tokens,
                "dropped": self.dropped,
                "programs": {
                    name: {
                        "n": row["n"], "s": round(row["s"], 6),
                        "queued_s": round(row["queued_s"], 6),
                    }
                    for name, row in self._programs.items()
                },
            }

    def shutdown(self, timeout_s: float = 1.0) -> None:
        """The thread ends with its core and no output outlives it (a
        thread stuck in a wait on a wedged device is a daemon, and holds
        the one array it waits for)."""
        with self._lock:
            self._open.clear()
            thread, self._thread = self._thread, None
            self._wake.notify_all()
        if thread is not None:
            thread.join(timeout=timeout_s)


class TickProfile:
    """One engine tick's phase decomposition (mutable accumulator while
    the tick runs; frozen by :meth:`PerfRecorder.tick_end`)."""

    __slots__ = (
        "t", "wall", "host", "schedule", "state", "dispatch", "device",
        "readback", "detok", "tokens", "decode_steps", "decode_bytes",
        "fused_head_steps", "clock_decode_s", "cpu0", "cpu_in_wait",
    )

    def __init__(self, t: float, clock_decode_s: float) -> None:
        self.t = t
        self.wall = 0.0
        self.host = 0.0
        self.schedule = 0.0
        self.state = 0.0
        self.dispatch = 0.0
        self.device = 0.0
        self.readback = 0.0
        self.detok = 0.0
        self.tokens = 0
        self.decode_steps = 0
        self.fused_head_steps = 0
        self.decode_bytes = 0
        # the device clock's running decode seconds at tick begin: the
        # window's decode seconds are the growth since its oldest tick
        self.clock_decode_s = clock_decode_s
        # engine-thread CPU clock at tick begin, and the CPU seconds
        # burnt inside the device_wait/readback brackets
        self.cpu0 = time.thread_time()
        self.cpu_in_wait = 0.0

    def measured(self) -> float:
        return self.schedule + self.state + self.device_work()

    def device_work(self) -> float:
        """Seconds in the phases only a tick that touched the device
        has (an idle poll still runs its schedule bracket)."""
        return self.dispatch + self.device + self.readback + self.detok

    def phases(self) -> Dict[str, float]:
        return {
            "host": self.host,
            "schedule": self.schedule,
            "state": self.state,
            "dispatch": self.dispatch,
            "device": self.device,
            "readback": self.readback,
            "detok": self.detok,
        }


class PerfRecorder:
    """Owned by one EngineCore, rebuilt fresh on supervised restart like
    the flight recorder.  All mutation happens on the engine thread; the
    per-0.5s flush keeps gauge math off the per-tick path."""

    def __init__(
        self,
        cfg: Optional[Any] = None,
        roofline: Optional[EngineRoofline] = None,
        clock: Any = time.perf_counter,
    ) -> None:
        # injectable clock (tests pin window math on a fake clock; the
        # engine always uses perf_counter)
        self._clock = clock
        if cfg is None:
            from vgate_tpu.config import ObservabilityConfig

            cfg = ObservabilityConfig()
        self.enabled = bool(cfg.enabled) and bool(cfg.perf_enabled)
        self.window_s = max(1.0, float(cfg.perf_window_s))
        self.roofline = roofline
        self._ring: "deque[TickProfile]" = deque(
            maxlen=max(16, int(cfg.perf_ticks))
        )
        self._ledger_max = max(16, int(cfg.perf_compile_ledger_max))
        # (program, signature) -> ledger entry, insertion-ordered
        self._ledger: Dict[tuple, Dict[str, Any]] = {}
        self._cur: Optional[TickProfile] = None
        self._next_flush = 0.0
        self._last_profile: Optional[Dict[str, Any]] = None
        # lifetime totals (snapshot deltas drive the loadlab artifact)
        self.total_ticks = 0
        self.total_idle_ticks = 0
        self.total_tokens = 0
        self.total_decode_steps = 0
        # those of them whose program kept the step's logits on the
        # chip (models/decoder.py decode_head_impl == "fused")
        self.total_decode_steps_fused_head = 0
        self.total_wall_s = 0.0
        self.total_compile_s = 0.0
        self._phase_totals = {name: 0.0 for name in PHASES}
        # window counters measured where the work happens (monotone;
        # perfbench differences two /debug/perf scrapes)
        self.total_decode_device_s = 0.0
        self.total_decode_ctx_token_steps = 0
        # chunk length (steps) -> decode chunks read back
        self._chunks_by_steps: Dict[int, int] = {}
        # ticks whose decode membership differed from the last
        # dispatch's, and those of them that drained the pipeline and
        # rebuilt the device state (the others edited its rows)
        self.total_membership_changes = 0
        self.total_pipeline_drains = 0
        self._drain_reasons: "deque[str]" = deque(maxlen=PAUSES_KEPT)
        # delivery gaps (note_delivery): the histogram of all of them,
        # the pauses by cause, and the clocks as they stood at the last
        # delivery (None = no stream has been running since)
        self._gaps = GapHistogram()
        self.total_deliveries = 0
        self.total_delivery_gap_s = 0.0
        self._pause_totals: Dict[str, Any] = {
            f"{cause}_{unit}": 0 for cause in PAUSE_CAUSES
            for unit in ("n", "s")
        }
        self._pauses: "deque[Dict[str, Any]]" = deque(maxlen=PAUSES_KEPT)
        self._mark: Optional[Dict[str, float]] = None
        self._counts = dict.fromkeys(GAP_COUNTS, 0)
        self.total_slept_s = 0.0
        self._gc_thread: Optional[int] = None
        self._pause_counters = {
            cause: metrics.ENGINE_PAUSES.labels(cause=cause)
            for cause in PAUSE_CAUSES
        }
        # expert-layer and recurrent-state counters (a hybrid spec's
        # decode chunks; empty otherwise and then left out of totals())
        self._moe: Dict[str, int] = {}
        self._state: Dict[str, int] = {}
        self._mla: Dict[str, int] = {}
        self._swa: Dict[str, int] = {}
        self._eva: Dict[str, int] = {}
        self._dsa: Dict[str, int] = {}
        # what a spec of gated short convolutions keeps a slot (the
        # engine's: layers, a slot's tail bytes, the state's GB); its
        # steps' rows ride ``_state`` as every state's do
        self.conv_block: Dict[str, Any] = {}
        # rows of the prompt programs read back (every family)
        self._prefill = dict.fromkeys(
            ("rows_worked", "rows_real", "rows_padding"), 0)
        # tiles of the Pallas prompt attention launches (every family)
        self._prefill_attn = dict.fromkeys(
            ("programs", "tiles", "interior_tiles"), 0)
        self.total_engine_cpu_s = 0.0
        self.total_engine_cpu_in_wait_s = 0.0
        # the device's seconds by program (EngineCore._launch posts)
        self.device = DeviceClock(clock=clock, enabled=self.enabled)
        # the chips' memory_stats(), for a pause the device caused (the
        # engine's; None in a recorder that stands alone)
        self.device_memory: Optional[Callable[[], List[Dict[str, int]]]] = None
        # the flight recorder's per-request phase sums (admitted /
        # queue_wait_s / first_tokens / prefill_s), folded into totals()
        self.request_totals: Optional[Callable[[], Dict[str, Any]]] = None
        self._tick_ann = None
        # monotone per-program compile counters — NOT derived from the
        # evicting ledger, so a recompile storm (which evicts old
        # entries) can never make the loadlab delta go negative
        self._compile_counts: Dict[str, int] = {}
        # label children resolved once: .labels() takes the registry
        # lock per call, and this runs on the loop this module measures
        self._phase_counters = {
            name: metrics.TICK_PHASE_SECONDS.labels(phase=name)
            for name in PHASES
        }

    # ------------------------------------------------- engine hot path

    def tick_begin(self) -> None:
        if _capturing:
            n = self.total_ticks + self.total_idle_ticks
            self._tick_ann = _open_annotation(
                "vgt.engine.tick", lambda: {"tick": n}
            )
        if not self.enabled:
            return
        if self._gc_thread is None:
            self._gc_thread = GC.watch()
        self._cur = TickProfile(self._clock(), self.device.decode_s)

    def span(
        self, name: str, args: Optional[Callable[[], dict]] = None
    ) -> _Bracket:
        """THE bracket (engine thread): ``with perf.span("emit"): ...``
        accrues the block's seconds into the span's phase (SPAN_PHASE)
        and, while a profile capture runs, records it in the trace as
        ``vgt.engine.<name>`` with ``args()`` as its arguments (``args``
        is only called then).  ``.seconds`` holds the duration after
        the block."""
        return _Bracket(self, name, args)

    def _span_begin(self, name: str) -> float:
        if name in WAIT_SPANS and self._cur is not None:
            self._cur.cpu_in_wait -= time.thread_time()
        return self._clock()

    def _span_end(self, name: str, t0: float) -> float:
        seconds = self._clock() - t0
        cur = self._cur
        if cur is not None:
            if name in WAIT_SPANS:
                cur.cpu_in_wait += time.thread_time()
            phase = SPAN_PHASE.get(name)
            if phase is not None:
                self.phase(phase, seconds)
        return seconds

    def phase(self, name: str, seconds: float) -> None:
        """Accrue measured time into the current tick's ``name`` phase
        (everything in PHASES but host, which is derived)."""
        cur = self._cur
        if cur is None or seconds <= 0:
            return
        setattr(cur, name, getattr(cur, name) + seconds)

    def note_tokens(self, n: int) -> None:
        """Tokens delivered to sequences this tick (decode appends,
        prefill first tokens, accepted speculative runs)."""
        cur = self._cur
        if cur is not None and n > 0:
            cur.tokens += n

    def note_decode(
        self, steps: int, ctx_tokens: int, device_s: float,
        chunk: bool = True, fused_head: bool = False,
    ) -> None:
        """One decode-chunk (or spec-verify, ``chunk=False``) readback:
        ``steps`` fused steps over sequences holding ``ctx_tokens``
        resident context tokens in all, with ``device_s`` of the host's
        wait for it (``totals.decode_device_s``; the DEVICE's seconds
        are the device clock's) — feeds the modeled HBM traffic of the
        roofline gauge, and the chunk-length / live-context window
        counters.  ``fused_head``: the chunk's program kept each step's
        logits on the chip (``totals.decode_steps_fused_head``)."""
        cur = self._cur
        if cur is None:
            return
        cur.decode_steps += steps
        if fused_head:
            cur.fused_head_steps += steps
        self.total_decode_device_s += device_s
        self.total_decode_ctx_token_steps += steps * ctx_tokens
        if chunk:
            self._chunks_by_steps[steps] = (
                self._chunks_by_steps.get(steps, 0) + 1
            )
        if self.roofline is not None:
            cur.decode_bytes += steps * self.roofline.step_bytes(
                ctx_tokens
            )

    def note_membership_change(
        self, drained: bool, reason: Optional[str] = None
    ) -> None:
        """A tick found the decode batch's membership changed; it either
        ``drained`` the pipeline and rebuilt the device state (``reason``
        says why, ``_drain_reason``) or edited the state's rows with
        every chunk left in flight."""
        self.total_membership_changes += 1
        if drained:
            self.total_pipeline_drains += 1
            self._drain_reasons.append(reason or "?")

    # ------------------------------------- delivery gaps and pauses

    def count(self, **adds: int) -> None:
        """What the engine dispatched since the last delivery
        (``GAP_COUNTS``): a pause record says what stood in its way."""
        if not self.enabled:
            return
        counts = self._counts
        for name, n in adds.items():
            counts[name] += n

    def note_sleep(self, seconds: float) -> None:
        """The engine thread slept on purpose (an armed fault's delay):
        not CPU time, and not CPU time it lost either."""
        self.total_slept_s += seconds

    def clear_delivery_clock(self) -> None:
        """A tick found no stream running: nobody is waiting, so the
        time until the next delivery is no gap."""
        self._mark = None

    def _clocks(self, now: float, cur: TickProfile, preemptions: int
                ) -> Dict[str, float]:
        """Every clock and count a pause is differenced over, as of
        ``now``: the closed ticks' sums plus the open tick's part."""
        mark = {
            name: self._phase_totals[name] + getattr(cur, name)
            for name in MEASURED_PHASES
        }
        mark.update(self._counts)
        mark.update(
            t=now,
            decode_device_s=self.total_decode_device_s,
            cpu_s=time.thread_time(),
            cpu_in_wait_s=(
                self.total_engine_cpu_in_wait_s + cur.cpu_in_wait
            ),
            slept_s=self.total_slept_s,
            gc_s=GC.thread_s.get(self._gc_thread, 0.0),
            gc_process_s=GC.gc_s,
            compile_s=self.total_compile_s,
            membership_changes=self.total_membership_changes,
            drains=self.total_pipeline_drains,
            preemptions=preemptions,
        )
        return mark

    def note_delivery(
        self, steps: int, rows: int, queue_depth: int = 0,
        preemptions: int = 0,
    ) -> Optional[Dict[str, Any]]:
        """A decode readback (``steps`` steps over ``rows`` sequences)
        handed tokens to at least one running stream, its ``emit`` span
        just closed.  The time since the last such call is a delivery
        gap; one of ``PAUSE_S`` or more is a pause, whose record is
        returned (the engine puts it on the flight recorder and in the
        log).  ``preemptions`` is the scheduler's running count,
        ``queue_depth`` the prompts waiting now.  Costs two clock reads
        and one small dict; disabled, one test."""
        cur = self._cur
        if cur is None:
            return None
        now = self._clock()
        mark = self._clocks(now, cur, preemptions)
        last, self._mark = self._mark, mark
        if last is None:
            return None
        gap = now - last["t"]
        self._gaps.add(gap)
        self.total_deliveries += 1
        self.total_delivery_gap_s += gap
        metrics.DELIVERY_GAP_SECONDS.observe(gap)
        if gap < PAUSE_S:
            return None
        delta = {key: value - last[key] for key, value in mark.items()}
        # seconds of device wait a prompt token has cost so far, the
        # decode chunks' own waits left out
        pace = (
            (last["device"] - last["decode_device_s"])
            / last["prompt_tokens"]
            if last["prompt_tokens"] else None
        )
        return self._pause(gap, delta, pace, steps, rows, queue_depth)

    def _pause(
        self, gap: float, d: Dict[str, float], pace: Optional[float],
        steps: int, rows: int, queue_depth: int,
    ) -> Dict[str, Any]:
        """The record of one pause from the clocks' growth ``d`` over
        it, and its ONE cause, first match:

        * ``compile``  a program variant compiled for half of it;
        * ``prefill``  a prompt program was dispatched in it, the wait
          for the device plus the dispatches took half of it, and that
          wait is no more than ``WAVE_SLACK`` times what the gap's
          prompt tokens take at ``pace`` (an admission wave between two
          decode readbacks; without a pace yet, any wave will do);
        * ``gc``       collections took half of it: the engine thread's
          own, and another thread's (which holds the GIL) as far as the
          engine thread was off the CPU;
        * ``off_cpu``  for half of it the engine thread neither ran,
          waited for the device nor slept on purpose: the GIL behind
          another thread, or the operating system (``off_cpu_share``);
        * ``device``   the wait for the device took half of it and no
          wave explains it: the chip or its runtime stood still;
        * ``host``     otherwise: the engine's own Python (``phases``
          says in which bracket).

        Beside the cause, not part of its rule: ``programs``, the
        launches that finished or stood open in the gap by the device
        clock (:meth:`DeviceClock.launches_since`), and for the cause
        ``device`` the chips' ``memory`` as the pause closed.
        """
        phases = {name: d[name] for name in MEASURED_PHASES}
        host = max(0.0, gap - sum(phases.values()))
        on_cpu = d["cpu_s"] - d["cpu_in_wait_s"]
        off_cpu = max(
            0.0,
            gap - d["device"] - d["readback"] - on_cpu - d["slept_s"],
        )
        gc_other = d["gc_process_s"] - d["gc_s"]
        wave = None if pace is None else d["prompt_tokens"] * pace
        half = gap / 2
        if d["compile_s"] >= half:
            cause = "compile"
        elif (
            d["prompt_programs"]
            and d["device"] + d["dispatch"] >= half
            and (wave is None or d["device"] <= WAVE_SLACK * wave)
        ):
            cause = "prefill"
        elif d["gc_s"] + min(gc_other, off_cpu) >= half:
            cause = "gc"
        elif off_cpu >= half:
            cause = "off_cpu"
        elif d["device"] >= half:
            cause = "device"
        else:
            cause = "host"
        drains = int(d["drains"])
        record: Dict[str, Any] = {
            "t": time.time(),
            "gap_s": round(gap, 6),
            "cause": cause,
            "phases": {
                "host": round(host, 6),
                **{k: round(v, 6) for k, v in phases.items()},
            },
            "decode_device_s": round(d["decode_device_s"], 6),
            # the rest of ``device``: the wait for prompt programs'
            # first tokens (_read_first_tokens)
            "first_token_wait_s": round(
                max(0.0, d["device"] - d["decode_device_s"]), 6
            ),
            # what the gap's prompt tokens take at the pace so far
            "wave_at_pace_s": None if wave is None else round(wave, 6),
            "cpu_s": round(d["cpu_s"], 6),
            "cpu_in_wait_s": round(d["cpu_in_wait_s"], 6),
            "off_cpu_s": round(off_cpu, 6),
            "slept_s": round(d["slept_s"], 6),
            "gc_s": round(d["gc_s"], 6),
            "gc_other_threads_s": round(gc_other, 6),
            "compile_s": round(d["compile_s"], 6),
            **{name: int(d[name]) for name in GAP_COUNTS},
            "membership_changes": int(d["membership_changes"]),
            "drains": list(self._drain_reasons)[-drains:] if drains else [],
            "preemptions": int(d["preemptions"]),
            "queue_depth": queue_depth,
            "steps": steps,
            "rows": rows,
            "programs": self.device.launches_since(self._clock() - gap),
        }
        if cause == "device" and self.device_memory is not None:
            record["memory"] = self.device_memory()
        self._pauses.append(record)
        self._pause_totals[cause + "_n"] += 1
        self._pause_totals[cause + "_s"] += gap
        self._pause_counters[cause].inc()
        return record

    def pauses(self) -> List[Dict[str, Any]]:
        """The last ``PAUSES_KEPT`` pause records, oldest first."""
        return list(self._pauses)

    def note_moe(self, stats, rows: int, moe_layers: int,
                 linear_layers: int) -> None:
        """One decode chunk's device counters, booked once per readback.
        ``stats`` is ``[steps, 5]``: per step, over its expert layers,
        the (row, choice) assignments made, those that fell on experts
        held here, the held experts hit (summed over layers), the
        largest load of a held expert (the max over layers) and the
        dispatch trips beyond a block's first (held pairs past the
        capacity, ops/moe.py ``capacity``).  ``rows``
        sequences rode the chunk: each step updated that many rows of
        the recurrent state in each linear layer."""
        steps = int(stats.shape[0])
        sums = stats.sum(axis=0)
        for name, add in (
            ("assignments", int(sums[0])),
            ("held_assignments", int(sums[1])),
            ("experts_hit", int(sums[2])),
            ("load_max_sum", int(sums[3])),
            ("overflow", int(sums[4])),
            ("layer_steps", steps * moe_layers),
            ("steps", steps),
        ):
            self._moe[name] = self._moe.get(name, 0) + add
        for name, add in (
            ("rows_updated", steps * rows * linear_layers),
            ("layer_steps", steps * linear_layers),
        ):
            self._state[name] = self._state.get(name, 0) + add

    def note_prefill_rows(self, worked: int, real: int) -> None:
        """One prompt program, booked at its readback: ``worked`` rows
        went through its position-wise sub-blocks (the whole ``[B, S]``
        or, of a long prompt's or a packed group's, the blocks of rows
        that hold a real one: models/hybrid.py ``prompt_rows``),
        ``real`` of them held a prompt's token; the others were
        padding."""
        for name, add in (("rows_worked", worked), ("rows_real", real),
                          ("rows_padding", worked - real)):
            self._prefill[name] += add

    def note_prefill_attn(self, tiles: int, interior: int) -> None:
        """One whole-prompt program whose attention is the Pallas prompt
        kernel, booked at its dispatch: a query head computed ``tiles``
        tiles of keys in its layers' launches, ``interior`` of them
        wholly under the diagonal and inside the length, where the
        kernel makes no position test (models/decoder.py
        ``prefill_attn_tiles``, by the kernel's own predicate)."""
        for name, add in (("programs", 1), ("tiles", tiles),
                          ("interior_tiles", interior)):
            self._prefill_attn[name] += add

    def note_mla_decode(self, steps: int, rows: int, ctx_tokens: int,
                        layers: int) -> None:
        """One decode chunk of a latent-attention spec, booked once per
        readback: ``rows`` sequences holding ``ctx_tokens`` tokens in
        all rode ``steps`` steps, and step k read each row's context so
        far (its length at dispatch + k) in each of ``layers`` layers.
        Only real rows of real lengths are counted: what the decode
        kernel has to read, and no padding."""
        reads = steps * ctx_tokens + rows * steps * (steps - 1) // 2
        for name, add in (
            ("decode_token_reads", reads * layers),
            ("decode_steps", steps),
            ("latent_rows_written", steps * rows * layers),
        ):
            self._mla[name] = self._mla.get(name, 0) + add

    def note_mla_prefill(self, tokens: int, cached: int,
                         layers: int) -> None:
        """One prompt of a latent-attention spec, booked at its
        readback: positions ``cached .. tokens - 1`` went through the
        prompt pass, position i attending to i + 1 keys, in each of
        ``layers`` layers (no padding, nothing above the diagonal)."""
        pairs = (tokens * (tokens + 1) - cached * (cached + 1)) // 2
        for name, add in (
            ("prefill_pairs", pairs * layers),
            ("prefill_prompts", 1),
            ("latent_rows_written", (tokens - cached) * layers),
        ):
            self._mla[name] = self._mla.get(name, 0) + add

    def note_dsa_decode(self, steps: int, lens, layers: int,
                        index_layers: int, topk: int,
                        fetch_chunk: int = 0, pair_tokens: int = 2) -> None:
        """One decode chunk of a spec that attends under a learned
        selection, booked once per readback: the sequences of lengths
        ``lens`` (at dispatch) rode ``steps`` steps; step k scored a
        row's whole context so far (its length + k) in each of
        ``index_layers`` picking layers, wrote one index key there, and
        attended to ``min(context, topk)`` latent rows in each of
        ``layers`` layers, ``layers - index_layers`` of which reused a
        pick.  What the kernels have to read, and no padding.
        ``rows_fetched`` is what the attention MOVED for those rows: the
        kernel a pair of token rows a pick, whole chunks of
        ``fetch_chunk`` picks (ops/pallas/dsa.py; ``pair_tokens`` 1: a
        pick's pair is ONE token's K over its V, nothing beside it); the
        jnp twin (``fetch_chunk`` 0) the picked rows themselves."""
        ctx = sum(steps * n + steps * (steps - 1) // 2 for n in lens)
        picks = [min(n + k, topk) for n in lens for k in range(steps)]
        attended = sum(picks)
        fetched = (sum(pair_tokens * fetch_chunk * -(-m // fetch_chunk)
                       for m in picks)
                   if fetch_chunk else attended)
        for name, add in (
            ("index_layer_steps", steps * index_layers),
            ("rows_scored", ctx * index_layers),
            ("rows_attended", attended * layers),
            ("rows_fetched", fetched * layers),
            ("rows_in_context", ctx * layers),
            ("selections_reused", steps * (layers - index_layers)),
            ("index_rows_written", steps * len(lens) * index_layers),
            ("decode_steps", steps),
        ):
            self._dsa[name] = self._dsa.get(name, 0) + add

    def note_dsa_prefill(self, tokens: int, cached: int,
                         index_layers: int) -> None:
        """One prompt of such a spec, booked at its readback: positions
        ``cached .. tokens - 1`` went through the prompt pass, position
        i scored against i + 1 index keys in each of ``index_layers``
        picking layers, and left one index key there."""
        pairs = (tokens * (tokens + 1) - cached * (cached + 1)) // 2
        for name, add in (
            ("prefill_pairs_scored", pairs * index_layers),
            ("index_rows_written", (tokens - cached) * index_layers),
            ("prefill_prompts", 1),
        ):
            self._dsa[name] = self._dsa.get(name, 0) + add

    def note_swa_decode(self, steps: int, lens, layers: int,
                        window: int) -> None:
        """One decode chunk of a spec with window layers, booked once
        per readback: the sequences of lengths ``lens`` (at dispatch)
        rode ``steps`` steps, and step k read the last ``window`` rows
        of each one's ring (its whole length so far while that is
        shorter) in each of ``layers`` window layers, one kernel launch
        a layer a step, and wrote one row."""
        reads = sum(
            steps * window if n >= window
            else sum(min(n + k, window) for k in range(steps))
            for n in lens)
        for name, add in (
            ("decode_launches", steps * layers),
            ("decode_row_reads", reads * layers),
            ("decode_steps", steps),
            ("ring_rows_written_decode", steps * len(lens) * layers),
        ):
            self._swa[name] = self._swa.get(name, 0) + add

    def note_eva_decode(self, steps: int, lens, layers: int, window: int,
                        chunk: int) -> None:
        """One decode chunk of a spec of EVA layers, booked once per
        readback from the sequences' real lengths ``lens`` (at
        dispatch): step k of a sequence is the token at ``t = n - 1 +
        k``, which reads the ``t mod window + 1`` live rows of its open
        window and one summary row for every chunk of the ``t //
        window`` closed ones and writes its own row, in each of
        ``layers`` layers; the step that fills a window's last row
        closes it, and writes its ``window / chunk`` summary rows a
        layer (ops/eva.py ``decode_close``): ``chunk_rows_written ==
        window / chunk x layers x windows_closed``."""
        per = window // chunk
        win = ch = closed = 0
        for n in lens:
            for t in range(n - 1, n - 1 + steps):
                win += t % window + 1
                ch += per * (t // window)
                closed += (t + 1) % window == 0
        for name, add in (
            ("decode_steps", steps),
            ("window_rows_read", win * layers),
            ("chunk_rows_read", ch * layers),
            ("window_rows_written", steps * len(lens) * layers),
            ("chunk_rows_written", per * closed * layers),
            ("windows_closed", closed),
        ):
            self._eva[name] = self._eva.get(name, 0) + add

    def note_eva_prefill(self, tokens: int, cached: int, layers: int,
                         window: int, chunk: int) -> None:
        """One prompt pass of such a spec, booked at its readback:
        positions ``cached .. tokens - 1`` went through it in each of
        ``layers`` layers and left a summary row for every chunk they
        touch; ``prompt_windows_closed`` of their windows closed inside
        the pass (the ones a decode step closes are
        ``windows_closed``)."""
        for name, add in (
            ("prompt_rows", (tokens - cached) * layers),
            ("prompt_chunk_rows_written",
             (-(-tokens // chunk) - cached // chunk) * layers),
            ("prompt_windows_closed", tokens // window - cached // window),
            ("prompts", 1),
        ):
            self._eva[name] = self._eva.get(name, 0) + add

    def note_swa_prefill(self, tokens: int, cached: int, layers: int,
                         ring_tokens: int, page_size: int) -> None:
        """One prompt pass of a spec with window layers, booked at its
        readback: positions ``cached .. tokens - 1`` went through it;
        the rows of the last ``ring_tokens / page_size`` pages that hold
        a real token went to the slot's ring in each of ``layers``
        window layers, the earlier rows to the trash page; position i
        attended to ``min(i + 1, window)`` keys (counted by the
        benchmark's shapes module from ``prefill_rows``)."""
        last = (tokens - 1) // page_size
        first_kept = max(cached, (last + 1) * page_size - ring_tokens, 0)
        kept = max(0, tokens - first_kept)
        rows = tokens - cached
        for name, add in (
            ("prefill_launches", layers),
            ("prefill_rows", rows * layers),
            ("prefill_prompts", 1),
            ("ring_rows_written_prefill", kept * layers),
            ("prefill_rows_to_trash", (rows - kept) * layers),
        ):
            self._swa[name] = self._swa.get(name, 0) + add

    def tick_end(self, worked: bool) -> None:
        """Close the tick: derive ``host_s`` as the unexplained wall
        remainder (clamped at 0 — the explained phases can overshoot
        the wall only by clock noise), push the profile into the
        rolling ring, and feed the phase counters."""
        if self._tick_ann is not None:
            self._tick_ann.__exit__(None, None, None)
            self._tick_ann = None
        cur = self._cur
        self._cur = None
        if cur is None:
            return
        now = self._clock()
        cur.wall = now - cur.t
        if not worked and cur.device_work() == 0.0 and cur.tokens == 0:
            # no-work ticks are idle polls, not attribution evidence
            # (their schedule bracket found nothing to schedule) —
            # but the gauge flush still runs on cadence, so an engine
            # going idle decays its window gauges instead of freezing
            # them at the last loaded value
            self.total_idle_ticks += 1
            if now >= self._next_flush:
                self._next_flush = now + _FLUSH_INTERVAL_S
                self._flush_gauges(now)
            return
        cur.host = max(0.0, cur.wall - cur.measured())
        self._ring.append(cur)
        self.total_ticks += 1
        self.total_tokens += cur.tokens
        self.total_decode_steps += cur.decode_steps
        self.total_decode_steps_fused_head += cur.fused_head_steps
        self.total_wall_s += cur.wall
        # one thread_time per worked tick: wall - device - readback -
        # (cpu - cpu_in_wait) is the time the engine thread neither ran
        # nor waited on the device (GIL, or the OS)
        self.total_engine_cpu_s += time.thread_time() - cur.cpu0
        self.total_engine_cpu_in_wait_s += cur.cpu_in_wait
        for name, value in cur.phases().items():
            self._phase_totals[name] += value
            if value > 0:
                self._phase_counters[name].inc(value)
        if now >= self._next_flush:
            self._next_flush = now + _FLUSH_INTERVAL_S
            self._flush_gauges(now)

    def record_compile(
        self,
        program: str,
        signature: Any,
        seconds: float,
        trigger: str,
    ) -> None:
        """One XLA compile observed at a fresh-variant first dispatch
        (the dispatch's duration IS the trace+compile cost — jit
        compiles synchronously at call).  The engine's compiled-variant
        sets gate the call, so each variant lands here exactly once per
        core incarnation; ``count`` > 1 therefore means the SAME
        signature compiled again (it should not, short of a rebuild)."""
        if not self.enabled:
            return
        key = (program, str(signature))
        entry = self._ledger.get(key)
        now = time.time()
        if entry is None:
            if len(self._ledger) >= self._ledger_max:
                # bound the ledger: drop the oldest entry (insertion
                # order ~ compile order; steady state never gets here)
                self._ledger.pop(next(iter(self._ledger)))
            entry = {
                "program": program,
                "signature": str(signature),
                "trigger": trigger,
                "count": 0,
                "seconds": 0.0,
                "first_t": now,
            }
            self._ledger[key] = entry
        entry["count"] += 1
        entry["seconds"] = round(entry["seconds"] + seconds, 6)
        entry["last_t"] = now
        self.total_compile_s += seconds
        self._compile_counts[program] = (
            self._compile_counts.get(program, 0) + 1
        )
        metrics.RECOMPILES_BY_VARIANT.labels(variant=program).inc()

    def note_profile(self, info: Dict[str, Any]) -> None:
        """Link a ``POST /v1/profile`` JAX trace capture to this layer:
        /debug/perf reports the last capture so operators can correlate
        attribution windows with device timelines."""
        self._last_profile = {
            **info, "ts": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
        }

    # ------------------------------------------------------ aggregates

    def _window_profiles(self, now: float) -> List[TickProfile]:
        # copy before iterating: reader threads (/stats, /debug/perf)
        # walk this while the engine thread appends, and a deque
        # iterator raises on concurrent mutation (list() is atomic
        # enough under the GIL)
        profs = list(self._ring)
        cutoff = now - self.window_s
        out: List[TickProfile] = []
        for prof in reversed(profs):
            if prof.t < cutoff:
                break
            out.append(prof)
        out.reverse()
        return out

    def window(self) -> Dict[str, Any]:
        """Rolling-window aggregates: live tok/s, MFU, %-of-HBM-roofline
        (the modelled bytes of the decode readbacks in the window over
        the device clock's decode seconds in it) and the host-overhead
        ratio.  Safe from any thread."""
        now = self._clock()
        profs = self._window_profiles(now)
        phases = {name: 0.0 for name in PHASES}
        wall = 0.0
        tokens = 0
        decode_steps = 0
        decode_bytes = 0
        for prof in profs:
            for name, value in prof.phases().items():
                phases[name] += value
            wall += prof.wall
            tokens += prof.tokens
            decode_steps += prof.decode_steps
            decode_bytes += prof.decode_bytes
        # the DEVICE's seconds on the window's decode launches
        device_decode_s = (
            self.device.decode_s - profs[0].clock_decode_s if profs else 0.0
        )
        # offered span: from the oldest in-window tick to now (the
        # engine may have gone idle — tok/s decays over real time)
        span = (now - profs[0].t) if profs else 0.0
        tok_s = tokens / span if span > 0 else 0.0
        mfu = hbm_pct = None
        if self.roofline is not None:
            mfu = self.roofline.mfu(tok_s)
            hbm_pct = self.roofline.hbm_roofline_pct(
                decode_bytes, device_decode_s
            )
        return {
            "window_s": self.window_s,
            "span_s": round(span, 3),
            "ticks": len(profs),
            "tokens": tokens,
            "tokens_per_s": round(tok_s, 2),
            "decode_steps": decode_steps,
            "device_decode_s": round(device_decode_s, 6),
            "phase_seconds": {
                k: round(v, 6) for k, v in phases.items()
            },
            "wall_s": round(wall, 6),
            # everything the engine's own Python spends outside the
            # jitted call and the device: the unbracketed remainder plus
            # scheduling/admission and state building (what ``host`` was
            # before those two got brackets of their own)
            "host_overhead_ratio": (
                round(
                    (phases["host"] + phases["schedule"]
                     + phases["state"]) / wall, 4
                ) if wall > 0 else None
            ),
            "mfu": None if mfu is None else round(mfu, 4),
            "hbm_roofline_pct": (
                None if hbm_pct is None else round(hbm_pct, 2)
            ),
        }

    def _flush_gauges(self, now: float) -> None:
        # None (no in-window work / device off the peak table) exports
        # as 0 so an engine going idle decays the gauges instead of
        # freezing them at the last loaded value
        win = self.window()
        metrics.HOST_OVERHEAD_RATIO.set(
            win["host_overhead_ratio"] or 0.0
        )
        metrics.DECODE_MFU.set(win["mfu"] or 0.0)
        metrics.DECODE_HBM_ROOFLINE_PCT.set(
            win["hbm_roofline_pct"] or 0.0
        )

    def compile_ledger(self) -> List[Dict[str, Any]]:
        return [dict(entry) for entry in list(self._ledger.values())]

    def totals(self) -> Dict[str, Any]:
        """Lifetime counters — monotone, so the loadlab runner can
        difference two scrapes into a per-cell attribution delta.
        ``compiles`` comes from the dedicated counters, NOT the ledger:
        ledger eviction under a recompile storm must never make a
        delta go negative."""
        compiles = dict(self._compile_counts)
        out = {
            "ticks": self.total_ticks,
            "idle_ticks": self.total_idle_ticks,
            "tokens": self.total_tokens,
            "decode_steps": self.total_decode_steps,
            "decode_steps_fused_head": self.total_decode_steps_fused_head,
            "wall_s": round(self.total_wall_s, 6),
            "phase_seconds": {
                k: round(v, 6) for k, v in self._phase_totals.items()
            },
            "compiles": compiles,
            "compile_seconds": round(self.total_compile_s, 6),
            "chunks_by_steps": {
                str(k): v for k, v in self._chunks_by_steps.items()
            },
            "membership_changes": self.total_membership_changes,
            "pipeline_drains": self.total_pipeline_drains,
            "decode_device_s": round(self.total_decode_device_s, 6),
            "decode_ctx_token_steps": self.total_decode_ctx_token_steps,
            "engine_cpu_s": round(self.total_engine_cpu_s, 6),
            "engine_cpu_in_wait_s": round(
                self.total_engine_cpu_in_wait_s, 6
            ),
            "deliveries": self.total_deliveries,
            "delivery_gap_s": round(self.total_delivery_gap_s, 6),
            "delivery_gaps": self._gaps.to_dict(),
            "pauses": {
                k: round(v, 6) for k, v in self._pause_totals.items()
            },
            **{name: 0 for name in REQUEST_TOTALS},
            "boot_seconds": dict(BOOT_SECONDS),
            "gc": GC.totals(),
            "prefill": dict(self._prefill),
            "prefill_attn": dict(self._prefill_attn),
            "device_clock": self.device.totals(),
        }
        if self.request_totals is not None:
            out.update(self.request_totals())
        if self._moe:
            out["moe"] = dict(self._moe)
            out["state"] = dict(self._state)
        if self._mla:
            out["mla"] = dict(self._mla)
        if self._swa:
            out["swa"] = dict(self._swa)
        if self._eva:
            out["eva"] = dict(self._eva)
        if self._dsa:
            out["dsa"] = dict(self._dsa)
        if self.conv_block:
            out["conv"] = {
                **self.conv_block,
                "tails_moved": self._state.get("rows_updated", 0),
                "layer_steps": self._state.get("layer_steps", 0),
            }
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The full /debug/perf payload for one engine core."""
        if not self.enabled:
            return {"enabled": False}
        return {
            "enabled": True,
            "window": self.window(),
            "totals": self.totals(),
            "pauses": self.pauses(),
            "compile_ledger": self.compile_ledger(),
            "roofline": (
                self.roofline.to_dict()
                if self.roofline is not None
                else None
            ),
            "last_profile": self._last_profile,
        }

    def get_stats(self) -> Dict[str, Any]:
        """The compact /stats ``perf`` block."""
        if not self.enabled:
            return {"enabled": False}
        win = self.window()
        return {
            "enabled": True,
            "tokens_per_s": win["tokens_per_s"],
            "mfu": win["mfu"],
            "hbm_roofline_pct": win["hbm_roofline_pct"],
            "host_overhead_ratio": win["host_overhead_ratio"],
            "phase_seconds": self.totals()["phase_seconds"],
            "ticks": self.total_ticks,
            "compiles": self.totals()["compiles"],
            "compile_seconds": round(self.total_compile_s, 6),
        }


# --------------------------------------------------- the gateway's side

class _StreamClock:
    """One streamed request's stamps, carried by a context variable from
    the handler's entry to the stream's writes (same asyncio task)."""

    __slots__ = ("t_in", "ann", "t_first_token", "first_written",
                 "tokens")

    def __init__(self, t_in: Optional[float], ann: Any) -> None:
        self.t_in = t_in
        self.ann = ann
        # tokens detokenised since the stream's last write: the next
        # write's delivery carries them
        self.tokens = 0
        # stamped by the ENGINE thread at the stream's first delivery
        self.t_first_token: Optional[float] = None
        self.first_written = False


_stream_clock: "contextvars.ContextVar[Optional[_StreamClock]]" = (
    contextvars.ContextVar("vgt_stream_clock", default=None)
)


class GatewayPerf:
    """Window counters and trace spans of the gateway's event-loop
    thread (server/app.py, backends/jax_backend.py).  Every counter is
    monotone and is only written on the event loop; ``/debug/perf``
    serves them as ``totals.gateway``.  With ``enabled`` false
    (observability off) no stream gets a clock and nothing is timed."""

    def __init__(self, clock: Any = time.perf_counter) -> None:
        self._clock = clock
        self.enabled = True
        self.ingress_n = 0
        self.ingress_s = 0.0
        self.first_chunk_n = 0
        self.first_chunk_s = 0.0
        self.stream_tokens = 0
        self.stream_detok_s = 0.0
        self.stream_write_s = 0.0
        # how often the hand-off engages: SSE content writes, the
        # tokens they carried, and the wake-ups that brought them
        self.stream_deliveries = 0
        self.stream_tokens_delivered = 0
        self.stream_handoffs = 0
        # how long those wake-ups waited for the event loop
        self.handoff_wait_s = 0.0
        self._handoff_waits = GapHistogram()
        self._detok_ann = None

    def ingress_begin(self) -> None:
        """Handler entry of a chat request: opens
        ``vgt.gateway.ingress`` (closed by :meth:`ingress_end` when the
        request is streamed, else by :meth:`ingress_close`)."""
        if not self.enabled:
            _stream_clock.set(None)
            return
        ann = (
            _open_annotation("vgt.gateway.ingress", None)
            if _capturing else None
        )
        _stream_clock.set(_StreamClock(self._clock(), ann))

    @staticmethod
    def stream_clock() -> Optional[_StreamClock]:
        """This task's stream clock, for the ``on_token`` stamp (None
        when the stream did not enter through the gateway)."""
        return _stream_clock.get()

    def ingress_end(self) -> None:
        """``submit_prompt`` returned: the request is the engine's now."""
        clock = _stream_clock.get()
        if clock is None or clock.t_in is None:
            return
        self.ingress_n += 1
        self.ingress_s += self._clock() - clock.t_in
        clock.t_in = None
        if clock.ann is not None:
            clock.ann.__exit__(None, None, None)
            clock.ann = None

    def ingress_close(self) -> None:
        """Handler exit, in a ``finally``: a request that never reached
        ``submit_prompt`` (not streamed, rejected) or was cancelled
        inside a write leaves no annotation open, and the task's next
        request (keep-alive) no stale clock."""
        clock = _stream_clock.get()
        if clock is not None:
            if clock.ann is not None:
                clock.ann.__exit__(None, None, None)
                clock.ann = None
            _stream_clock.set(None)

    # The two per-delivery sites are begin/end pairs, not ``with``
    # blocks: the event loop is the contended thread, and a bracket
    # object per token once cost decode-heavy 2-3 % of its throughput
    # (PERF.md, PR 24).  A delivery is what one readback appended to
    # one stream, several times rarer than a token, so EVERY delivery
    # is timed; ``stream_tokens`` counts the tokens of the timed
    # deliveries, which keeps the sums over it means per token.

    def detok_begin(
        self, clock: Optional[_StreamClock]
    ) -> Optional[float]:
        """Before stream_async detokenises one delivery; None = the
        stream has no clock (it did not enter through the gateway, or
        observability is off).  No await until :meth:`detok_end`, so
        the open annotation lives on ``self``."""
        if clock is None:
            return None
        if _capturing:
            self._detok_ann = _open_annotation(
                "vgt.gateway.stream_detok", None
            )
        return self._clock()

    def detok_end(
        self, clock: _StreamClock, t0: float, n_tokens: int
    ) -> None:
        self.stream_detok_s += self._clock() - t0
        self.stream_tokens += n_tokens
        clock.tokens += n_tokens
        if self._detok_ann is not None:
            self._detok_ann.__exit__(None, None, None)
            self._detok_ann = None

    def write_begin(self) -> Optional[float]:
        """Before one SSE chunk's JSON + ``resp.write``; None = the
        stream has no clock.  The write may await, so the open
        annotation lives on the stream's clock."""
        clock = _stream_clock.get()
        if clock is None:
            return None
        if _capturing:
            clock.ann = _open_annotation("vgt.gateway.sse_write", None)
        return self._clock()

    def write_end(self, t0: float) -> None:
        now = self._clock()
        self.stream_write_s += now - t0
        clock = _stream_clock.get()
        if clock.ann is not None:
            clock.ann.__exit__(None, None, None)
            clock.ann = None
        self.stream_deliveries += 1
        self.stream_tokens_delivered += clock.tokens
        clock.tokens = 0
        if not clock.first_written and clock.t_first_token is not None:
            # engine's first delivery -> the stream's first chunk on
            # the wire (both perf_counter, one process)
            clock.first_written = True
            self.first_chunk_n += 1
            self.first_chunk_s += now - clock.t_first_token

    def handoff_armed(self) -> Optional[float]:
        """The engine thread armed a wake-up of the event loop: the
        stamp :meth:`note_handoff` takes (None = nothing is timed)."""
        return self._clock() if self.enabled else None

    def note_handoff(self, t_armed: Optional[float]) -> None:
        """One cross-thread wake-up (engine thread -> event loop) was
        served: a readback's deliveries fanned out to their streams,
        this long after the wake-up was armed.  That wait is the event
        loop's lag on the path the tokens take, once per readback."""
        if t_armed is None:
            return
        wait = self._clock() - t_armed
        self.stream_handoffs += 1
        self.handoff_wait_s += wait
        self._handoff_waits.add(wait)

    def totals(self) -> Dict[str, Any]:
        return {
            "ingress_n": self.ingress_n,
            "ingress_s": round(self.ingress_s, 6),
            "first_chunk_n": self.first_chunk_n,
            "first_chunk_s": round(self.first_chunk_s, 6),
            "stream_tokens": self.stream_tokens,
            "stream_detok_s": round(self.stream_detok_s, 6),
            "stream_write_s": round(self.stream_write_s, 6),
            "stream_deliveries": self.stream_deliveries,
            "stream_tokens_delivered": self.stream_tokens_delivered,
            "stream_handoffs": self.stream_handoffs,
            "handoff_wait_s": round(self.handoff_wait_s, 6),
            "handoff_waits": self._handoff_waits.to_dict(),
        }


GATEWAY = GatewayPerf()


# ------------------------------------------------------- dp aggregation

def _sum_dicts(dicts: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Key-wise sum over the union of the keys, first-seen order."""
    out: Dict[str, Any] = {}
    for d in dicts:
        for key, value in d.items():
            out[key] = out.get(key, 0) + value
    return out


def _sum_device_clocks(clocks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The replicas' ``totals.device_clock`` as one: chip-seconds."""
    flat = _sum_dicts([
        {k: v for k, v in c.items() if k != "programs"} for c in clocks
    ])
    return {
        **{k: round(v, 6) for k, v in flat.items()},
        "programs": {
            name: {
                k: round(v, 6) for k, v in _sum_dicts(
                    [c["programs"][name] for c in clocks]
                ).items()
            }
            for name in DEVICE_PROGRAMS
        },
    }


def _weighted_ratio(parts: List[tuple]) -> Optional[float]:
    """Weighted mean of (value, weight) pairs, None-tolerant."""
    num = den = 0.0
    for value, weight in parts:
        if value is None or weight <= 0:
            continue
        num += value * weight
        den += weight
    return round(num / den, 4) if den > 0 else None


def merge_snapshots(
    snaps: List[Dict[str, Any]]
) -> Dict[str, Any]:
    """Fold per-replica /debug/perf snapshots into one pod view
    (runtime/dp_engine.py — the _MergedFlight pattern): additive fields
    sum, ratios average weighted by each replica's measured wall, and
    the per-replica payloads stay attached under ``replicas`` with
    their index."""
    enabled = [s for s in snaps if s.get("enabled")]
    out: Dict[str, Any] = {
        "enabled": bool(enabled),
        "replicas": [
            {"replica": i, **s} for i, s in enumerate(snaps)
        ],
    }
    if not enabled:
        return out
    windows = [s["window"] for s in enabled]
    totals = [s["totals"] for s in enabled]
    agg_window: Dict[str, Any] = {
        "window_s": max(w["window_s"] for w in windows),
        "ticks": sum(w["ticks"] for w in windows),
        "tokens": sum(w["tokens"] for w in windows),
        "tokens_per_s": round(
            sum(w["tokens_per_s"] for w in windows), 2
        ),
        "decode_steps": sum(w["decode_steps"] for w in windows),
        "phase_seconds": {
            name: round(
                sum(w["phase_seconds"][name] for w in windows), 6
            )
            for name in PHASES
        },
        "wall_s": round(sum(w["wall_s"] for w in windows), 6),
        "host_overhead_ratio": _weighted_ratio(
            [(w["host_overhead_ratio"], w["wall_s"]) for w in windows]
        ),
        # replicas are symmetric meshes: fleet MFU/roofline is the
        # token-weighted mean of the per-replica fractions
        "mfu": _weighted_ratio(
            [(w["mfu"], max(1, w["tokens"])) for w in windows]
        ),
        "hbm_roofline_pct": _weighted_ratio(
            [
                (w["hbm_roofline_pct"], w["device_decode_s"])
                for w in windows
            ]
        ),
    }
    agg_totals = {
        "ticks": sum(t["ticks"] for t in totals),
        "idle_ticks": sum(t["idle_ticks"] for t in totals),
        "tokens": sum(t["tokens"] for t in totals),
        "decode_steps": sum(t["decode_steps"] for t in totals),
        "decode_steps_fused_head": sum(
            t.get("decode_steps_fused_head", 0) for t in totals),
        "wall_s": round(sum(t["wall_s"] for t in totals), 6),
        "phase_seconds": {
            name: round(
                sum(t["phase_seconds"][name] for t in totals), 6
            )
            for name in PHASES
        },
        "compiles": _sum_dicts([t["compiles"] for t in totals]),
        "compile_seconds": round(
            sum(t["compile_seconds"] for t in totals), 6
        ),
        "chunks_by_steps": {
            n: sum(t.get("chunks_by_steps", {}).get(n, 0) for t in totals)
            for n in sorted(
                {n for t in totals for n in t.get("chunks_by_steps", {})},
                key=int,
            )
        },
        **{
            name: round(sum(t.get(name, 0) for t in totals), 6)
            for name in ADDITIVE_TOTALS
        },
        "delivery_gaps": _sum_dicts(
            [t.get("delivery_gaps", {}) for t in totals]
        ),
        "pauses": {
            k: round(v, 6) for k, v in _sum_dicts(
                [t.get("pauses", {}) for t in totals]
            ).items()
        },
        # one process, one boot, one collector: replicas share them
        "boot_seconds": dict(totals[0].get("boot_seconds", {})),
        "gc": dict(totals[0].get("gc", {})),
        "prefill": _sum_dicts([t.get("prefill", {}) for t in totals]),
        "prefill_attn": _sum_dicts(
            [t.get("prefill_attn", {}) for t in totals]),
        "device_clock": _sum_device_clocks(
            [t["device_clock"] for t in totals if "device_clock" in t]
        ),
    }
    out["window"] = agg_window
    out["totals"] = agg_totals
    out["pauses"] = sorted(
        (
            {"replica": i, **pause}
            for i, s in enumerate(snaps)
            for pause in s.get("pauses", ())
        ),
        key=lambda pause: pause["t"],
    )[-PAUSES_KEPT:]
    return out


def merge_stats(blocks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The dp /stats ``perf`` aggregate from per-replica get_stats
    blocks (additive sums; ratio gauges wall/token-weighted like
    merge_snapshots)."""
    enabled = [b for b in blocks if b.get("enabled")]
    if not enabled:
        return {"enabled": False}
    compiles = _sum_dicts([b.get("compiles", {}) for b in enabled])
    wall_of = [
        sum(b["phase_seconds"].values()) for b in enabled
    ]
    # efficiency ratios weight by each replica's live throughput so a
    # near-idle replica cannot drag the pod number — the same weighting
    # family merge_snapshots uses for /debug/perf, keeping the two
    # surfaces consistent
    tok_of = [max(b["tokens_per_s"], 1e-9) for b in enabled]
    return {
        "enabled": True,
        "tokens_per_s": round(
            sum(b["tokens_per_s"] for b in enabled), 2
        ),
        "mfu": _weighted_ratio(
            [(b["mfu"], w) for b, w in zip(enabled, tok_of)]
        ),
        "hbm_roofline_pct": _weighted_ratio(
            [
                (b["hbm_roofline_pct"], w)
                for b, w in zip(enabled, tok_of)
            ]
        ),
        "host_overhead_ratio": _weighted_ratio(
            [
                (b["host_overhead_ratio"], w)
                for b, w in zip(enabled, wall_of)
            ]
        ),
        "phase_seconds": {
            name: round(
                sum(b["phase_seconds"][name] for b in enabled), 6
            )
            for name in PHASES
        },
        "ticks": sum(b["ticks"] for b in enabled),
        "compiles": compiles,
        "compile_seconds": round(
            sum(b["compile_seconds"] for b in enabled), 6
        ),
    }
