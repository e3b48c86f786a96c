"""Process-isolated worker pod (ISSUE 16): wire helpers, fencing,
client plumbing, backend seam selection — and (slow tier) the real
2-worker CPU pod: serve, health/stats shapes, and the acceptance
scenario of a SIGKILLed worker mid-decode with token-identical output.
"""

import os
import signal
import socket
import threading
import time
from types import SimpleNamespace

import pytest

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.errors import (
    PoisonRequestError,
    RetryableError,
    WorkerFencedError,
    WorkerLostError,
)
from vgate_tpu.runtime import rpc
from vgate_tpu.runtime.pod_engine import PodEngine, _Worker
from vgate_tpu.runtime.worker import (
    params_from_wire,
    params_to_wire,
    unwire_error,
    wire_error,
)
from vgate_tpu.runtime.worker_client import WorkerClient


def greedy(max_tokens=8, **kw):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0, **kw)


# ------------------------------------------------------- wire helpers


def test_params_wire_round_trip():
    p = SamplingParams(
        max_tokens=12,
        min_tokens=4,
        temperature=0.0,
        top_p=0.9,
        logprobs=True,
        top_logprobs=3,
        logit_bias={7: -2.5},
    )
    q = params_from_wire(params_to_wire(p))
    assert q.max_tokens == 12
    assert q.min_tokens == 4
    assert q.temperature == 0.0
    assert q.logprobs is True
    # JSON forces dict keys to strings; the wire decode restores ints
    assert q.logit_bias == {7: -2.5}


def test_params_wire_ignores_unknown_fields():
    raw = params_to_wire(greedy(5))
    raw["from_the_future"] = 1
    assert params_from_wire(raw).max_tokens == 5


@pytest.mark.parametrize(
    "exc",
    [
        WorkerLostError("w0 gone", retry_after=3.0),
        WorkerFencedError("stale epoch"),
        RetryableError("busy", retry_after=0.5),
        PoisonRequestError("quarantined"),
        ValueError("bad dtype"),
    ],
)
def test_error_wire_round_trip(exc):
    back = unwire_error(wire_error(exc))
    assert str(exc) in str(back)
    if isinstance(exc, RetryableError):
        assert isinstance(back, RetryableError)
        assert back.reason == exc.reason
        assert back.retry_after == exc.retry_after


def test_unwire_error_degrades_on_unknown_type():
    back = unwire_error({"type": "NoSuchError", "message": "boom"})
    assert isinstance(back, Exception)
    assert "boom" in str(back)


# ------------------------------------------------------------ fencing


def _bare_pod(current_epoch=3):
    """A PodEngine shell with just enough state for frame dispatch."""
    pod = object.__new__(PodEngine)
    pod._lock = threading.RLock()
    pod._inflight = {}
    pod._handoffs = {}
    pod.fenced_frames = 0
    w = _Worker(0)
    w.epoch = current_epoch
    pod.workers = [w]
    return pod


def test_stale_epoch_frame_discarded_and_counted():
    pod = _bare_pod(current_epoch=3)
    for stale in (1, 2, 4, None, "2"):
        pod._on_frame(0, 2, {"op": "tok", "sid": 1, "t": 5, "e": stale})
    assert pod.fenced_frames == 5
    assert pod._inflight == {}  # nothing acted on


def test_current_epoch_frame_dispatched():
    pod = _bare_pod(current_epoch=3)
    seq = SimpleNamespace(
        _worker_idx=0,
        params=greedy(4),
        logprob_data=[],
        generated_ids=[],
        tokens=[],
        append_token=lambda t: seq.tokens.append(t),
        deliver=lambda: None,
    )
    pod._inflight[9] = seq
    pod._on_frame(0, 3, {"op": "tok", "sid": 9, "t": 42, "e": 3})
    assert seq.tokens == [42]
    assert pod.fenced_frames == 0


def test_frame_for_resubmitted_sequence_ignored():
    # sequence moved to worker 1 after a loss; worker 0's late frame
    # carries the CURRENT epoch (same incarnation) but the wrong owner
    pod = _bare_pod(current_epoch=3)
    seq = SimpleNamespace(_worker_idx=1, tokens=[])
    pod._inflight[9] = seq
    pod._on_frame(0, 3, {"op": "tok", "sid": 9, "t": 42, "e": 3})
    assert seq.tokens == []


# ------------------------------------------------------- worker client


class _FakeWorker:
    """Minimal frame-speaking server on a UDS for WorkerClient tests."""

    def __init__(self, path, behavior):
        self.path = path
        self.behavior = behavior  # fn(conn, frame) -> bool continue
        self.listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.listener.bind(path)
        self.listener.listen(1)
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        conn, _ = self.listener.accept()
        try:
            while True:
                frame = rpc.recv_frame(conn)
                if frame is None:
                    break
                if not self.behavior(conn, frame):
                    break
        except (rpc.FrameError, OSError):
            pass
        finally:
            conn.close()
            self.listener.close()


def _client(path, lost, notes=None, call_timeout=5.0):
    return WorkerClient(
        path,
        epoch=1,
        max_frame_bytes=1 << 20,
        connect_timeout_s=2.0,
        call_timeout_s=call_timeout,
        on_notify=(notes.append if notes is not None else lambda f: None),
        on_lost=lambda exc: lost.append(exc),
        label="t",
    )


def test_client_call_round_trip_and_epoch_stamp(tmp_path):
    seen = {}

    def behavior(conn, frame):
        seen.update(frame)
        rpc.send_frame(
            conn,
            {"op": "reply", "id": frame["id"], "e": 1, "ok": True,
             "data": {"pong": True}},
        )
        return True

    srv = _FakeWorker(str(tmp_path / "w.sock"), behavior)
    lost = []
    c = _client(srv.path, lost)
    assert c.call("ping")["pong"] is True
    assert seen["e"] == 1  # every outbound frame carries the epoch
    assert seen["deadline_s"] == 5.0
    c.close()
    assert lost == []  # deliberate close never fires on_lost


def test_client_typed_error_reply(tmp_path):
    def behavior(conn, frame):
        rpc.send_frame(
            conn,
            {"op": "reply", "id": frame["id"], "e": 1, "ok": False,
             "error": wire_error(WorkerFencedError("stale"))},
        )
        return True

    srv = _FakeWorker(str(tmp_path / "w.sock"), behavior)
    c = _client(srv.path, [])
    with pytest.raises(WorkerFencedError):
        c.call("submit")
    c.close()


def test_client_death_fails_pending_and_fires_on_lost_once(tmp_path):
    def behavior(conn, frame):
        return False  # hang up instead of replying

    srv = _FakeWorker(str(tmp_path / "w.sock"), behavior)
    lost = []
    c = _client(srv.path, lost)
    with pytest.raises(WorkerLostError):
        c.call("ping")
    c.join()
    assert len(lost) == 1
    assert c.dead
    # post-mortem sends are refused typed, not hung
    with pytest.raises(WorkerLostError):
        c.notify("abort", sid=1)


def test_client_call_timeout(tmp_path):
    def behavior(conn, frame):
        return True  # swallow the request, never reply

    srv = _FakeWorker(str(tmp_path / "w.sock"), behavior)
    c = _client(srv.path, [], call_timeout=0.2)
    with pytest.raises(TimeoutError):
        c.call("ping")
    c.close()


def test_client_notifications_routed(tmp_path):
    def behavior(conn, frame):
        rpc.send_frame(conn, {"op": "tok", "sid": 1, "t": 9, "e": 1})
        rpc.send_frame(
            conn,
            {"op": "reply", "id": frame["id"], "e": 1, "ok": True,
             "data": {}},
        )
        return True

    srv = _FakeWorker(str(tmp_path / "w.sock"), behavior)
    notes = []
    c = _client(srv.path, [], notes=notes)
    c.call("ping")
    assert notes and notes[0]["op"] == "tok"
    c.close()


# -------------------------------------------------------- backend seam


class _StubEngine:
    def __init__(self, *a, **k):
        self.spec = SimpleNamespace(name="stub")
        self.mesh = SimpleNamespace(shape={"dp": 1})
        self.geometry = SimpleNamespace(num_pages=1)

    def start(self):
        pass


def _seam_config(workers):
    return load_config(
        model={"model_id": "tiny-dense", "engine_type": "jax_tpu"},
        pod={"workers": workers},
        recovery={"enabled": False},
    )


def test_seam_workers_zero_keeps_inprocess_path(monkeypatch):
    from vgate_tpu.backends import jax_backend

    monkeypatch.setattr(jax_backend, "EngineCore", _StubEngine)
    backend = jax_backend.JaxTPUBackend()
    backend.load_model(_seam_config(workers=0))
    assert isinstance(backend.core, _StubEngine)


def test_seam_workers_selects_pod_engine(monkeypatch):
    from vgate_tpu.backends import jax_backend
    from vgate_tpu.runtime import pod_engine

    monkeypatch.setattr(pod_engine, "PodEngine", _StubEngine)
    backend = jax_backend.JaxTPUBackend()
    backend.load_model(_seam_config(workers=2))
    assert isinstance(backend.core, _StubEngine)


def test_pod_engine_refuses_zero_workers():
    with pytest.raises(ValueError):
        PodEngine(_seam_config(workers=0))


# ------------------------------------------- real pod on CPU (slow tier)


def pod_config(workers=2):
    return load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 128, "kv_page_size": 4, "max_batch_slots": 8,
            "prefill_buckets": [8, 16, 32], "use_pallas": False,
        },
        pod={
            "workers": workers,
            "heartbeat_interval_s": 0.2,
            "heartbeat_timeout_s": 5.0,
        },
        recovery={
            "enabled": True, "max_restarts": 6, "restart_window_s": 120.0,
            "backoff_base_s": 0.02, "backoff_cap_s": 0.2,
            "step_stall_s": 120.0, "compile_grace_s": 600.0,
        },
        scheduler={"max_queue_size": 32},
        logging={"level": "ERROR"},
    )


def wait_for(pred, timeout=60.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


@pytest.mark.slow
def test_pod_serves_and_reports():
    """2-worker pod: boot through the canary gate, serve greedy decodes,
    and present dp-shaped health/stats/pressure with per-worker detail."""
    pod = PodEngine(pod_config())
    pod.start()
    try:
        seqs = [
            pod.submit_tokens([5, 9, 13 + i, 17, 21], greedy(8))
            for i in range(4)
        ]
        for s in seqs:
            assert s.done_event.wait(120)
            assert s.error is None
            assert len(s.generated_ids) == 8
        h = pod.health()
        assert h["state"] == "serving"
        assert h["replicas_alive"] == 2
        assert h["fenced_frames"] == 0
        assert [r["replica"] for r in h["replicas"]] == [0, 1]
        assert all(r["epoch"] == 1 for r in h["replicas"])
        assert all(r["pid"] for r in h["replicas"])
        st = pod.get_stats()
        assert st["decode_tokens"] >= 32
        assert st["mesh"]["workers"] == 2
        assert st["pod"]["transport"] == "uds"
        sig = pod.pressure_signals()
        assert 0.0 < sig["kv_free_ratio"] <= 1.0
    finally:
        pod.stop()


@pytest.mark.slow
def test_worker_sigkill_token_identical():
    """Acceptance: SIGKILL one worker mid-decode → every request
    completes (zero failures), resumed on the survivor, token-identical
    to an undisturbed run; pod goes DEGRADED then back to SERVING after
    the canary-gated respawn."""

    def run(kill):
        pod = PodEngine(pod_config())
        pod.start()
        try:
            seqs = [
                pod.submit_tokens(
                    [5, 9, 13 + i, 17, 21],
                    greedy(16, min_tokens=16),
                )
                for i in range(8)
            ]
            if kill:
                time.sleep(1.0)
                os.kill(pod.workers[0].proc.pid, signal.SIGKILL)
            outs = []
            for s in seqs:
                assert s.done_event.wait(180)
                assert s.error is None, f"5xx-equivalent: {s.error}"
                outs.append(list(s.generated_ids))
            if kill:
                h = pod.health()
                assert h["failovers"] == 1
                assert h["resumed"] >= 1
                assert wait_for(lambda: pod.state.value == "serving", 90)
                h = pod.health()
                assert h["restarts"] == 1
                assert h["replicas"][0]["epoch"] > 1  # new incarnation
            return outs
        finally:
            pod.stop()

    assert run(kill=False) == run(kill=True)
