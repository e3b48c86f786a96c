"""Engine observability: flight recorder, cross-thread request tracing
and the in-memory span recorder (docs/observability.md).

Three pieces, all degrading to no-ops when disabled or when the OTel API
is absent:

* :mod:`~vgate_tpu.observability.flight` — a lock-cheap ring buffer of
  engine ticks plus bounded per-request records, dumped as a structured
  snapshot on every crash and served live via ``/debug``;
* :mod:`~vgate_tpu.observability.reqtrace` — per-request phase spans
  (``queue`` → ``prefill`` → ``decode`` → ``detokenize``) parented on
  the HTTP request span across the batcher/engine thread boundary;
* :mod:`~vgate_tpu.observability.memtrace` — a minimal recording tracer
  provider built on the OTel *API* alone, so span trees are testable
  (and debuggable in dev) without the OTel SDK installed;
* :mod:`~vgate_tpu.observability.perf` — per-tick phase attribution
  (host/dispatch/device/readback/detok), the compile ledger, and the
  rolling-window MFU / HBM-roofline / host-overhead gauges served via
  ``/debug/perf``;
* :mod:`~vgate_tpu.observability.roofline` — the device peak table and
  roofline/MFU math behind the live gauges.
"""

from vgate_tpu.observability.flight import FlightRecorder
from vgate_tpu.observability.perf import PerfRecorder
from vgate_tpu.observability.reqtrace import RequestMeta, RequestTrace

__all__ = [
    "FlightRecorder", "PerfRecorder", "RequestMeta", "RequestTrace",
]
