"""The system under test as a child process: ``python main.py``, driven
over HTTP.  This process never imports JAX; the child owns the chip.

Origin: ``chip_smoke.py``'s ``Server`` and ``loadlab/runner.py
launch_server``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READY_TIMEOUT_S = 900.0  # a cold boot draws and digests the weights
STOP_TIMEOUT_S = 30.0


class BenchFailure(Exception):
    """The run cannot produce a result; the harness exits non-zero."""


def http(method: str, url: str, body: Any = None,
         timeout: float = 300.0) -> Tuple[int, bytes]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def get_json(base: str, path: str, timeout: float = 30.0) -> Dict[str, Any]:
    status, raw = http("GET", base + path, timeout=timeout)
    if status != 200:
        raise BenchFailure(f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def post_json(base: str, path: str, body: Dict[str, Any],
              timeout: float = 600.0) -> Dict[str, Any]:
    status, raw = http("POST", base + path, body, timeout=timeout)
    if status != 200:
        raise BenchFailure(f"POST {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def server_command(config: Dict[str, Any]) -> list:
    """``python main.py`` as shipped; a configuration that changes the
    program's preset (a depth cut) goes through ``perfbench/serve.py``,
    which registers the changed preset and then calls the same main."""
    if config.get("program", {}).get("overrides"):
        return [sys.executable, os.path.join(ROOT, "perfbench", "serve.py")]
    entry = os.path.join(ROOT, "main.py")
    if not os.path.exists(entry):
        raise BenchFailure(f"the program is not here: {entry} is missing")
    return [sys.executable, entry]


class Server:
    def __init__(self, config: Dict[str, Any], env: Dict[str, str],
                 port: int, log_path: str) -> None:
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = log_path
        full = dict(os.environ)
        full.pop("BENCH_RUN", None)  # the driver's own; not the program's
        full.update({k: str(v) for k, v in env.items()})
        full["VGT_SERVER__HOST"] = "127.0.0.1"
        full["VGT_SERVER__PORT"] = str(port)
        full["PERFBENCH_CONFIG"] = config["_path"]
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            server_command(config), env=full, cwd=ROOT,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path, "rb") as fh:
            lines = fh.read().decode("utf-8", "replace").splitlines()
        return "\n".join(lines[-n:])

    def wait_ready(self) -> float:
        start = time.monotonic()
        while time.monotonic() - start < READY_TIMEOUT_S:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited rc={self.proc.returncode} before ready; "
                    f"{self.log_path} ends:\n{self.log_tail()}"
                )
            try:
                status, _ = http("GET", self.base + "/health/ready", timeout=2)
                if status == 200:
                    return time.monotonic() - start
            except OSError:
                pass  # not listening yet
            time.sleep(0.25)
        raise BenchFailure(
            f"server not ready after {READY_TIMEOUT_S:.0f}s; "
            f"{self.log_path} ends:\n{self.log_tail()}"
        )

    def stop(self) -> Optional[int]:
        """SIGTERM (the program drains), then SIGKILL; always waits."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=STOP_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()
        return self.proc.returncode
