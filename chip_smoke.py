"""Does the serving path still start on the chip?  The quickest proof.

    python chip_smoke.py                # one chip: server, reboot, kernels
    python chip_smoke.py --chips 4      # adds Qwen2.5-7B at tp=4
    python chip_smoke.py --rehearse-cpu # debug THIS SCRIPT on the CPU

Drives the normal entry points only: ``python main.py`` as a child
process, answered over HTTP.  The parent never imports JAX — a process
that has touched JAX holds the chip — and runs its phases as children,
one after another, each exited before the next starts:

1. **server**: Qwen2.5-1.5B at full width and depth, bf16, random weights
   from a seed, byte tokenizer (no network, no checkpoint), Pallas on,
   ``kv_num_pages: 0`` at a slots x context whose cap exceeds what the
   chip holds, so the pool is sized by the chip's own ``bytes_limit``.
   Chats (plain, streamed, eight concurrent), completions sharing a
   prefix (radix hit, multi-token suffix kernel, copy-on-write),
   embeddings, /stats, /metrics, /health, then SIGTERM and a drain that
   must exit 0.
2. **second boot**, same shapes: the compile cache must already hold
   every program the sequential requests need.
3. **kernels**: each Pallas entry point of the default path, compiled
   (not interpreted) at the 1.5B geometry, against its jnp twin.
4. ``--chips 4`` only: Qwen2.5-7B at tp=4 in one process, same requests.

Any failed check, dead child or timeout exits non-zero; nothing is
caught and passed over.  Prints set-up facts (device, versions, load and
compile seconds, pool size) and NO speed: this is not a measurement.
The last stdout line is ``{"ok": true, "device": {...}}`` as JAX reports
the device.  Logs land in ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PORT = 8765
# weights are drawn and digested before the server listens: about two
# minutes for the 1.5B on a cold cache, several for the 7B
READY_TIMEOUT_S = {1: 420.0, 4: 1200.0}
REQUEST_TIMEOUT_S = 300.0
DRAIN_TIMEOUT_S = 90.0
KERNEL_TIMEOUT_S = 300.0

MODEL_1CHIP = "Qwen/Qwen2.5-1.5B-Instruct"
MODEL_4CHIP = "Qwen/Qwen2.5-7B-Instruct"
# 256 slots x 2,048 tokens caps the pool at 16,385 pages (14.0 GiB of
# K+V at 1.5B); a 16 GB chip holds about 13k after the weights, so the
# chip's memory, not the cap, sizes the pool
CHIP_ENV = {
    "VGT_MODEL__DTYPE": "bfloat16",
    "VGT_MODEL__MAX_MODEL_LEN": "2048",
    "VGT_TPU__PLATFORM": "tpu",
    "VGT_TPU__USE_PALLAS": "true",
    "VGT_TPU__KV_NUM_PAGES": "0",
    "VGT_TPU__KV_PAGE_SIZE": "32",
    "VGT_TPU__MAX_BATCH_SLOTS": "256",
}
REHEARSE_ENV = {
    "VGT_MODEL__MODEL_ID": "tiny-dense",
    "VGT_MODEL__DTYPE": "float32",
    "VGT_MODEL__MAX_MODEL_LEN": "512",
    "VGT_TPU__PLATFORM": "cpu",
    "VGT_TPU__NUM_DEVICES": "1",
    "VGT_TPU__USE_PALLAS": "false",
    "VGT_TPU__KV_NUM_PAGES": "0",
    "VGT_TPU__KV_PAGE_SIZE": "4",
    "VGT_TPU__MAX_BATCH_SLOTS": "8",
    "VGT_TPU__PREFILL_BUCKETS": "[32,64,128,256,512]",
    # the default (8 shared tokens before a page is worth copying) can
    # never fire inside a 4-token page
    "VGT_TPU__PREFIX_CACHE": '{"enabled": true, "cow_min_tokens": 2}',
    "JAX_PLATFORMS": "cpu",
}

_tag = ""  # "REHEARSAL platform=cpu " in rehearsal mode, on every line


def say(msg: str) -> None:
    print(f"{_tag}{msg}", flush=True)


class SmokeFailure(Exception):
    """A check failed; main() turns it into a non-zero exit."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ HTTP

def http(method: str, url: str, body=None, timeout=REQUEST_TIMEOUT_S):
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
    except TimeoutError:
        raise SmokeFailure(
            f"{method} {url}: no answer in {timeout:.0f}s"
        ) from None


def get_json(base: str, path: str):
    status, raw = http("GET", base + path, timeout=30)
    check(status == 200, f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def post_json(base: str, path: str, body: dict):
    status, raw = http("POST", base + path, body)
    check(status == 200, f"POST {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


# ---------------------------------------------------------------- server

class Server:
    """``python main.py`` as a child, its output in a log file."""

    def __init__(self, env: dict, log_path: str, chips: int = 1) -> None:
        self.log_path = log_path
        self.ready_timeout_s = READY_TIMEOUT_S[chips]
        self.base = f"http://127.0.0.1:{PORT}"
        full_env = dict(os.environ)
        full_env.update(env)
        full_env["VGT_SERVER__HOST"] = "127.0.0.1"
        full_env["VGT_SERVER__PORT"] = str(PORT)
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "main.py")],
            env=full_env, cwd=HERE, stdout=self._log,
            stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 25) -> str:
        with open(self.log_path, "rb") as fh:
            lines = fh.read().decode("utf-8", "replace").splitlines()
        return "\n".join(lines[-n:])

    def wait_ready(self) -> float:
        start = time.monotonic()
        while time.monotonic() - start < self.ready_timeout_s:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode} before ready; "
                    f"{self.log_path} ends:\n{self.log_tail()}"
                )
            try:
                status, _ = http(
                    "GET", self.base + "/health/ready", timeout=2
                )
                if status == 200:
                    return time.monotonic() - start
            except OSError:
                pass  # not listening yet
            time.sleep(0.5)
        raise SmokeFailure(
            f"server not ready after {self.ready_timeout_s:.0f}s; "
            f"{self.log_path} ends:\n{self.log_tail()}"
        )

    def drain(self) -> None:
        """SIGTERM, and the graceful drain must end in exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=DRAIN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"drain did not finish in {DRAIN_TIMEOUT_S:.0f}s; "
                f"{self.log_path} ends:\n{self.log_tail()}"
            ) from None
        check(
            rc == 0,
            f"server exited rc={rc} after SIGTERM; {self.log_path} ends:\n"
            f"{self.log_tail()}",
        )

    def close(self) -> None:
        """Stop the child whatever happened (failure paths)."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()


# -------------------------------------------------------------- requests

def chat_body(model, text, n, **extra):
    # min_tokens == max_tokens pins the length: random weights may emit
    # EOS anywhere, and the checks below are on exact token counts
    return {
        "model": model, "max_tokens": n, "min_tokens": n, "temperature": 0,
        "messages": [{"role": "user", "content": text}], **extra,
    }


def expect_length(resp: dict, n: int, what: str) -> None:
    choice = resp["choices"][0]
    check(
        resp["usage"]["completion_tokens"] == n
        and choice["finish_reason"] == "length",
        f"{what}: wanted {n} tokens / 'length', got "
        f"{resp['usage']} / {choice['finish_reason']!r}",
    )


def stream_chat(base: str, model: str) -> None:
    status, raw = http(
        "POST", base + "/v1/chat/completions",
        chat_body(model, "stream a few tokens", 9, stream=True),
    )
    check(status == 200, f"stream chat -> {status}: {raw[:300]!r}")
    events = [
        line[len("data: "):]
        for line in raw.decode().splitlines()
        if line.startswith("data: ")
    ]
    check(events and events[-1] == "[DONE]", "stream did not end in [DONE]")
    chunks = [json.loads(e) for e in events[:-1]]
    check(
        all("error" not in c for c in chunks), f"stream error: {chunks[-1]}"
    )
    check(
        chunks[-1]["choices"][0]["finish_reason"] == "length",
        f"stream finish_reason: {chunks[-1]['choices'][0]}",
    )


# 192 characters = 192 byte-tokenizer tokens = whole pages at both page
# sizes used here (32 on the chip, 4 in rehearsal)
SHARED_PREFIX = ("The paged KV cache keeps every page resident. " * 5)[:192]
TAIL_A = "First tail: what follows the shared pages?"
TAIL_B = "Second tail, diverging at the page boundary."
# shares 18 more tokens with TAIL_A, so it diverges MID-page at both page
# sizes: the radix tree answers with a copy-on-write page and the
# unaligned suffix pass
TAIL_C = TAIL_A[:18] + "-- diverges inside a page."


def sequential_requests(base: str, model: str) -> None:
    """One request at a time, so the set of programs compiled is the
    same on every boot (the second boot's cache check rests on that)."""
    resp = post_json(
        base, "/v1/chat/completions",
        chat_body(model, "Say something short.", 21),
    )
    expect_length(resp, 21, "chat")
    stream_chat(base, model)
    for i, tail in enumerate((TAIL_A, TAIL_B, TAIL_C)):
        resp = post_json(
            base, "/v1/completions",
            {"model": model, "prompt": SHARED_PREFIX + tail,
             "max_tokens": 5, "min_tokens": 5, "temperature": 0},
        )
        expect_length(resp, 5, f"completion {i}")
        check(
            resp["usage"]["prompt_tokens"] == len(SHARED_PREFIX + tail),
            f"completion {i}: byte tokenizer not in use? {resp['usage']}",
        )


def concurrent_chats(base: str, model: str) -> int:
    budgets = [3, 7, 12, 16, 24, 33, 40, 48]

    def one(i_n):
        i, n = i_n
        resp = post_json(
            base, "/v1/chat/completions",
            chat_body(model, f"Concurrent request number {i}, go.", n),
        )
        expect_length(resp, n, f"concurrent chat {i}")

    with concurrent.futures.ThreadPoolExecutor(len(budgets)) as pool:
        # list() re-raises the first failure from any worker
        list(pool.map(one, enumerate(budgets)))
    return len(budgets)


def embeddings(base: str) -> None:
    resp = post_json(
        base, "/v1/embeddings",
        {"model": "embedding", "input": ["first text", "another one"]},
    )
    vecs = [d["embedding"] for d in resp["data"]]
    check(len(vecs) == 2 and len(vecs[0]) == len(vecs[1]) > 0, "embeddings")
    check(
        all(math.isfinite(x) for v in vecs for x in v),
        "embedding values are not finite",
    )
    check(vecs[0] != vecs[1], "two different texts, one embedding")


def check_settled(base: str, served: int, rehearse: bool) -> dict:
    """After all requests: nothing resident, nothing leaked, the prefix
    cache hit, and — on the chip — kernels, not twins, were traced."""
    engine = get_json(base, "/stats")["engine"]
    sched = engine["scheduler"]
    check(
        sched["running"] == 0 and sched["used_pages"] == 0,
        f"residency after settle: {sched}",
    )
    check(
        sched["admitted"] == sched["finished"] == served,
        f"admitted/finished/served: {sched['admitted']}/"
        f"{sched['finished']}/{served}",
    )
    cache = sched["prefix_cache"]
    check(cache["hit_tokens"] >= 192, f"no prefix hit: {cache}")
    check(cache["cow_copies"] >= 1, f"no copy-on-write page: {cache}")
    if not rehearse:
        check(engine["use_pallas"] is True, "use_pallas is off")
        # under tp the multi-token kernel is gated off by design (a
        # pallas_call has no partition rule; the jnp path partitions)
        kernel_programs = ("decode", "prefill") + (
            ("suffix",) if engine["mesh"]["tp"] == 1 else ()
        )
        for program in kernel_programs:
            impls = engine["attention"].get(program)
            check(
                bool(impls) and all(i.startswith("pallas") for i in impls),
                f"{program} traced {impls}, not the kernel",
            )
    status, raw = http("GET", base + "/metrics", timeout=30)
    check(
        status == 200 and b"vgt_" in raw, f"/metrics -> {status}"
    )
    return engine


def check_device(base: str, chips: int, rehearse: bool) -> dict:
    from vgate_tpu.observability.roofline import DEVICE_PEAKS

    device = get_json(base, "/health")["device"]
    check(device.get("alive") is True, f"device not alive: {device}")
    if rehearse:
        check(device["platform"] == "cpu", f"rehearsal on {device}")
        return device
    check(device["platform"] == "tpu", f"serving from {device}")
    check(
        device["num_devices"] == chips,
        f"engine uses {device['num_devices']} devices, wanted {chips}",
    )
    check(
        device["device_kind"] in DEVICE_PEAKS,
        f"{device['device_kind']!r} has no row in DEVICE_PEAKS",
    )
    return device


# bf16 weight bytes, for the "every chip holds its share" check
WEIGHT_BYTES = {MODEL_1CHIP: 3.09e9, MODEL_4CHIP: 15.2e9}


def check_device_memory(engine: dict, chips: int, model: str) -> None:
    """Every chip of the mesh reports its share of the weights plus its
    share of the pool — none was left empty, none holds the whole tree."""
    memory = engine["device_memory"]
    check(len(memory) == chips, f"device_memory has {len(memory)} rows")
    share = (WEIGHT_BYTES[model] + engine["kv_pool_bytes"]) / chips
    for row in memory:
        say(f"chip {row['id']}: bytes_in_use={row['bytes_in_use']} "
            f"bytes_limit={row['bytes_limit']}")
        check(
            0.9 * share <= row["bytes_in_use"] <= 1.25 * share,
            f"chip {row['id']} holds {row['bytes_in_use']} bytes; its "
            f"share of weights + pool is {share:.3g}",
        )


def report_setup(name: str, base: str, engine: dict, ready_s: float) -> None:
    totals = get_json(base, "/debug/perf")["totals"]
    say(
        f"{name}: set-up (not a measurement) ready_after_s={ready_s:.1f} "
        f"load_time_s={engine['load_time_s']} "
        f"compiles={sum(totals['compiles'].values())} "
        f"compile_seconds={totals['compile_seconds']:.1f}"
    )
    say(
        f"{name}: pool pages={engine['kv_pages_total']} "
        f"bytes={engine['kv_pool_bytes']} "
        f"sized_by={engine['kv_sized_by']} mesh={engine['mesh']} "
        f"use_pallas={engine['use_pallas']} "
        f"attention={json.dumps(engine['attention'], sort_keys=True)}"
    )


# ----------------------------------------------------------------- cache

def cache_dir() -> str:
    from vgate_tpu.config import COMPILE_CACHE_ENV, DEFAULT_COMPILE_CACHE_DIR

    return os.environ.get(COMPILE_CACHE_ENV) or DEFAULT_COMPILE_CACHE_DIR


def cache_entries() -> set:
    """Compiled-program entries (JAX also keeps an access-time file per
    entry, rewritten on every hit: not an entry)."""
    path = cache_dir()
    if not os.path.isdir(path):
        return set()
    return {n for n in os.listdir(path) if not n.endswith("-atime")}


# ---------------------------------------------------------------- phases

def server_phase(name, env, chips, out_dir, rehearse):
    model = env["VGT_MODEL__MODEL_ID"]
    server = Server(env, os.path.join(out_dir, f"{name}.log"), chips)
    try:
        ready_s = server.wait_ready()
        device = check_device(server.base, chips, rehearse)
        say(f"{name}: /health device={json.dumps(device, sort_keys=True)}")
        sequential_requests(server.base, model)
        served = 5 + concurrent_chats(server.base, model)
        embeddings(server.base)
        engine = check_settled(server.base, served, rehearse)
        if not rehearse:
            if chips == 1:
                check(
                    engine["kv_sized_by"] == "device_memory",
                    f"pool sized by {engine['kv_sized_by']}, not the chip",
                )
            else:
                mesh = engine["mesh"]
                check(
                    mesh["tp"] == chips
                    and all(v == 1 for k, v in mesh.items() if k != "tp"),
                    f"mesh is {mesh}, wanted tp={chips} only",
                )
            check_device_memory(engine, chips, model)
        report_setup(name, server.base, engine, ready_s)
        server.drain()
        say(f"{name}: drained, exit 0")
    finally:
        server.close()


def second_boot_phase(env, out_dir):
    """Same shapes, same sequential requests: with a working compile
    cache the boot adds NO entry (no times are compared)."""
    model = env["VGT_MODEL__MODEL_ID"]
    before = cache_entries()
    check(
        bool(before),
        f"the first boot left no compile-cache entry in {cache_dir()}",
    )
    server = Server(env, os.path.join(out_dir, "server_boot2.log"))
    try:
        ready_s = server.wait_ready()
        sequential_requests(server.base, model)
        added = cache_entries() - before
        check(
            not added,
            f"second boot compiled {len(added)} programs the first boot "
            f"should have cached in {cache_dir()}: {sorted(added)[:5]}",
        )
        engine = get_json(server.base, "/stats")["engine"]
        report_setup("server_boot2", server.base, engine, ready_s)
        say(
            f"server_boot2: compile cache {cache_dir()} holds "
            f"{len(before)} entries, none added"
        )
        server.drain()
        say("server_boot2: drained, exit 0")
    finally:
        server.close()


def kernel_phase(out_dir, rehearse) -> dict:
    """The kernel child; returns the device as JAX reports it there."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child-kernels"]
    env = dict(os.environ)
    if rehearse:
        cmd.append("--rehearse-cpu")
        env["JAX_PLATFORMS"] = "cpu"
    log_path = os.path.join(out_dir, "kernels.log")
    with open(log_path, "wb") as log:
        proc = subprocess.run(
            cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, stderr=log,
            timeout=KERNEL_TIMEOUT_S,
        )
    lines = proc.stdout.decode().splitlines()
    for line in lines[:-1]:
        say(f"kernels: {line}")
    check(
        proc.returncode == 0 and bool(lines),
        f"kernel child rc={proc.returncode}; see {log_path}",
    )
    return json.loads(lines[-1])


def kernels_child(rehearse: bool) -> int:
    """Runs in its own process (it owns the chip): each Pallas entry
    point of the default path, at the Qwen2.5-1.5B geometry and at one
    tp=4 shard of the 7B, against its jnp twin at the bf16 tolerance of
    tests/test_pallas_kernels.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vgate_tpu.config import apply_compile_cache
    from vgate_tpu.ops.attention import (
        flash_prefill_attention,
        paged_decode_attention,
        paged_suffix_attention,
    )
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
        paged_multitok_attention_pallas,
    )

    apply_compile_cache()
    device = jax.devices()[0]
    if not rehearse and device.platform != "tpu":
        print(f"no accelerator: jax.devices()[0] is {device}", file=sys.stderr)
        return 1
    ps, hd, layers, layer = 32, 128, 2, jnp.asarray(1, jnp.int32)
    if rehearse:  # interpret mode is slow: a few small rows
        B, pages_per_seq, S = 2, 4, 32
    else:
        B, pages_per_seq, S = 8, 16, 128
    dtype, tol = jnp.bfloat16, 2e-2
    interpret = {"interpret": True} if rehearse else {}
    ctx = pages_per_seq * ps
    P = 1 + B * pages_per_seq

    def compare(name, got, want, rows=None):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if rows is not None:
            got, want = got[rows], want[rows]
        if not np.all(np.isfinite(got)):
            raise SystemExit(f"{name}: kernel output is not finite")
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)
        print(
            f"{name}: shape={got.shape} max_abs_err="
            f"{float(np.max(np.abs(got - want))):.2e} (tol {tol})",
            flush=True,
        )

    # (heads, kv heads): Qwen2.5-1.5B on one chip, and one tp=4 shard of
    # Qwen2.5-7B; pools stacked [L, KV, P, ps, hd] and layer-indexed, as
    # the plain-mesh forwards pass them
    for H, KV in ((12, 2), (7, 1)):
        rng = np.random.default_rng(0)
        normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
        k_pages = normal(layers, KV, P, ps, hd)
        v_pages = normal(layers, KV, P, ps, hd)
        page_tables = jnp.asarray(
            rng.permutation(np.arange(1, P)).reshape(B, pages_per_seq),
            jnp.int32,
        )
        geom = f"[H{H}/KV{KV}]"

        seq_lens = jnp.asarray(rng.integers(1, ctx, size=B), jnp.int32)
        q = normal(B, H, hd)
        compare(
            f"paged_decode_attention_pallas{geom}",
            paged_decode_attention_pallas(
                q, k_pages, v_pages, page_tables, seq_lens, layer=layer,
                **interpret,
            ),
            paged_decode_attention(
                q, k_pages, v_pages, page_tables, seq_lens, layer=layer
            ),
        )

        q = normal(B, S, H, hd)
        k, v = normal(B, S, KV, hd), normal(B, S, KV, hd)
        lens = jnp.asarray(rng.integers(1, S + 1, size=B), jnp.int32)
        valid = np.arange(S)[None, :] < np.asarray(lens)[:, None]
        compare(
            f"flash_prefill_attention_pallas{geom}",
            flash_prefill_attention_pallas(q, k, v, lens, **interpret),
            flash_prefill_attention(q, k, v, lens),
            rows=valid,
        )

        positions0 = jnp.asarray(
            ps * rng.integers(0, (ctx - S) // ps, size=B), jnp.int32
        )
        input_lens = jnp.asarray(rng.integers(1, S + 1, size=B), jnp.int32)
        valid = np.arange(S)[None, :] < np.asarray(input_lens)[:, None]
        compare(
            f"paged_multitok_attention_pallas{geom}",
            paged_multitok_attention_pallas(
                q, k_pages, v_pages, page_tables, positions0, input_lens,
                layer=layer, **interpret,
            ),
            paged_suffix_attention(
                q, k_pages, v_pages, page_tables, positions0,
                positions0 + input_lens, layer=layer,
            ),
            rows=valid,
        )

    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, not a check
        libtpu = "unknown"
    print(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    print(json.dumps({
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }), flush=True)
    return 0


# ------------------------------------------------------------------ main

def _deadline(signum, frame):
    raise SmokeFailure("the run's deadline passed")


def main() -> int:
    global _tag
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="never the default: tiny-dense on the CPU, jnp twins, "
        "interpreted kernels — to debug this script without a chip",
    )
    ap.add_argument("--child-kernels", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_kernels:
        return kernels_child(args.rehearse_cpu)
    if args.rehearse_cpu:
        _tag = "REHEARSAL platform=cpu "
        check(args.chips == 1, "--rehearse-cpu has no four-chip phase")
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    # one deadline for the whole run: a hung child must not outlive it
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(1150 if args.chips == 1 else 3000)

    out_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    env = dict(REHEARSE_ENV if args.rehearse_cpu else CHIP_ENV)
    if not args.rehearse_cpu:
        env.update(VGT_MODEL__MODEL_ID=MODEL_1CHIP, VGT_TPU__NUM_DEVICES="1")
    else:
        # a process on the CPU keeps no cache unless one is placed
        # (vgate_tpu/config.py apply_compile_cache); children inherit it
        from vgate_tpu.config import COMPILE_CACHE_ENV

        os.environ.setdefault(
            COMPILE_CACHE_ENV, os.path.join(out_dir, "rehearsal_jax_cache")
        )
    say(f"logs: {out_dir}; compile cache: {cache_dir()}")

    server_phase("server", env, 1, out_dir, args.rehearse_cpu)
    second_boot_phase(env, out_dir)
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    device = kernel_phase(out_dir, args.rehearse_cpu)
    if args.rehearse_cpu:
        check(device["platform"] == "cpu", f"rehearsal ran on {device}")
        say("rehearsal complete: the script runs; nothing was measured")
        return 0
    check(device["platform"] == "tpu", f"kernels ran on {device}")
    if args.chips == 4:
        check(
            device["count"] >= 4,
            f"--chips 4 but JAX sees {device['count']} device(s)",
        )
        env4 = {**CHIP_ENV, "VGT_MODEL__MODEL_ID": MODEL_4CHIP,
                "VGT_TPU__NUM_DEVICES": "4", "VGT_TPU__TP": "4",
                "VGT_TPU__MAX_BATCH_SLOTS": "128"}
        server_phase("server_tp4", env4, 4, out_dir, False)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"{_tag}FAILED: {failure}", file=sys.stderr, flush=True)
        sys.exit(1)
