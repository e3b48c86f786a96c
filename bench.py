"""Engine-direct burst benchmark: prints ONE JSON line, on a TPU or not
at all.

Measures output tokens/sec/chip + p50 TTFT for one burst against the
single-chip serving config (Qwen2.5-1.5B-Instruct architecture, bf16,
random-init weights — this environment has no model egress), driving the
continuous-batching engine directly (no HTTP gateway).  Every line names
the platform, device_kind and device count it ran on; with no TPU the
process exits non-zero and prints no metric — a CPU timing is never
written under a device metric's name.  The table of cells, the layered
metrics and the trace reduction are ROADMAP S1.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _device_fields() -> dict:
    """platform / device_kind / count as JAX reports them; exits non-zero
    unless the first device is a TPU."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"bench: no TPU (jax.devices()[0] is {device}); nothing measured",
            file=sys.stderr,
        )
        sys.exit(1)
    return {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "num_devices": len(jax.devices()),
    }


def _run_loadlab_scenario(name: str) -> int:
    """VGT_BENCH_SCENARIO=<loadlab scenario name or YAML path>: delegate
    to the workload lab (vgate_tpu/loadlab) — boot the real HTTP server
    as a subprocess pinned to the TPU (it fails to start without one)
    with the scenario's server_env, drive it open-loop, and print the
    graded artifact lines to stdout.  This process stays off JAX: the
    server child owns the chip."""
    import urllib.request

    from vgate_tpu.loadlab.runner import (
        launch_server, run_scenario, scenario_server_env,
    )
    from vgate_tpu.loadlab.scenario import load_scenario

    scenario = load_scenario(name)
    here = os.path.dirname(os.path.abspath(__file__))
    # scenario server_env is a DEFAULT layer: explicitly exported env wins
    env = scenario_server_env(scenario)
    env["VGT_TPU__PLATFORM"] = "tpu"
    out_dir = os.path.join(here, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.environ.get("VGT_BENCH_OUT") or os.path.join(
        out_dir, f"loadlab_{scenario.name}.jsonl"
    )
    port = int(os.environ.get("VGT_BENCH_PORT", "8791"))
    with launch_server(
        env, port=port, log_path=os.path.join(out_dir, "server.log")
    ) as base:
        with urllib.request.urlopen(f"{base}/health", timeout=30) as resp:
            device = json.load(resp)["device"]
        if device.get("platform") != "tpu":
            print(f"bench: server runs on {device}", file=sys.stderr)
            return 1
        result = run_scenario(
            scenario, base,
            out_path=out_path,
            platform=device["platform"],
            device=device["device_kind"],
            progress=lambda s: print(s, file=sys.stderr, flush=True),
        )
    for line in result["lines"]:
        if line.get("kind") == "meta":
            line = {**line, "num_devices": device["num_devices"]}
        print(json.dumps(line), flush=True)
    return 0


def _run_kv_quant_scenario(
    config, device_fields, n_requests, prompt_len, max_tokens, buckets
) -> None:
    """bf16-vs-int8 KV A/B on one process (arms run serially; each
    core's pool frees before the next auto-sizes).  The oracle arm is
    the plain pool at the model compute dtype.  (With tpu.use_pallas on,
    engine construction refuses the int8 arm on this toolchain — ROADMAP
    S3; VGT_TPU__USE_PALLAS=false runs both arms on the jnp twins.)"""
    import gc

    import jax

    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    rng_tokens = [
        [3 + (i * 37 + j * 11) % 200 for j in range(prompt_len)]
        for i in range(n_requests)
    ]
    n_quality = min(8, n_requests)
    # min_tokens pins every quality stream to the full horizon: an
    # early greedy EOS (likely with random-init weights)
    # would shrink the compared window to a few tokens and report a
    # vacuous identity horizon
    # the acceptance bar is a >= 64-step identity horizon, so the
    # quality probe never runs shorter than that even when the
    # throughput arms use a smaller max_tokens
    quality_tokens = max(64, max_tokens)
    quality_params = SamplingParams(
        max_tokens=quality_tokens, min_tokens=quality_tokens,
        temperature=0.0, logprobs=True, top_logprobs=1,
    )
    # quality prompts clip to the largest warmup bucket and must leave
    # the full horizon of decode room (a short max_model_len would
    # otherwise clamp the streams to ~1 token — a vacuous probe)
    quality_clip = max(buckets) - 1
    config.model.max_model_len = max(
        config.model.max_model_len, max(buckets) + quality_tokens
    )
    # quality probe text: deterministic natural prompts (synthetic
    # digit streams produce near-tied logits whose argmax flips on any
    # numeric noise, which would measure tie-breaking, not KV quality)
    topics = [
        "systolic arrays", "high bandwidth memory",
        "sequence parallelism", "paged attention",
        "speculative decoding", "continuous batching",
        "prefix caching", "tensor parallelism",
    ]
    quality_prompts = [
        f"Explain {topics[i % len(topics)]} to a systems "
        f"engineer in part {i} of the series, covering the "
        "performance trade-offs in detail"
        for i in range(n_quality)
    ]
    arms = {}
    for arm in ("oracle", "int8"):
        config.kv_cache.dtype = "auto" if arm == "oracle" else "int8"
        core = EngineCore(config, devices=jax.devices()[:1])
        core.start()
        try:
            core.warmup(buckets=buckets)
            params = SamplingParams(max_tokens=max_tokens, temperature=0.0)
            start = time.perf_counter()
            seqs = [core.submit_tokens(ids, params) for ids in rng_tokens]
            for seq in seqs:
                # a hung or failed arm must abort the A/B, not skew
                # toks_ratio — that number adjudicates the default flip
                if not seq.done_event.wait(timeout=1800):
                    raise TimeoutError(
                        f"kv_quant {arm} arm: request never finished"
                    )
                if seq.error is not None:
                    raise seq.error
            wall = time.perf_counter() - start
            total_out = sum(s.num_output_tokens for s in seqs)
            # quality probe: greedy + logprobs, prompts tokenized and
            # clipped so the full horizon fits both the bucket ladder
            # and max_model_len on every platform
            q_seqs = [
                core.submit_tokens(
                    core.tokenizer.encode(text)[:quality_clip]
                    or [core.tokenizer.bos_id],
                    quality_params,
                )
                for text in quality_prompts
            ]
            for seq in q_seqs:
                seq.done_event.wait(timeout=1800)
                if seq.error is not None:
                    raise seq.error
            arms[arm] = {
                "kv_dtype": core.geometry.kv_dtype,
                "toks_per_s": total_out / wall if wall > 0 else 0.0,
                "kv_pages_total": core.allocator.num_allocatable,
                "kv_token_capacity": core.geometry.total_tokens,
                "kv_page_bytes": core.geometry.page_bytes,
                "quality": [
                    {
                        "token_ids": list(seq.generated_ids),
                        "logprobs": [
                            e["logprob"]
                            for e in core.logprob_entries(seq)
                        ],
                    }
                    for seq in q_seqs
                ],
            }
        finally:
            core.stop()
            del core
            gc.collect()
        row = {
            "scenario": "kv_quant",
            "arm": arm,
            **{
                k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in arms[arm].items()
                if k != "quality"
            },
            "requests": n_requests,
            **device_fields,
        }
        print(json.dumps(row), flush=True)

    # comparison: identity horizon = first greedy divergence (min over
    # prompts); drift = max |chosen-logprob delta| over identical
    # prefixes — the numbers the default flip is adjudicated on
    max_drift = 0.0
    diverged_tokens = 0
    diverged_at = []  # first-divergence steps of prompts that diverged
    compared = 0  # longest fully-compared identical stream
    for qa, qb in zip(arms["oracle"]["quality"], arms["int8"]["quality"]):
        ids_a, ids_b = qa["token_ids"], qb["token_ids"]
        n = next(
            (i for i, (a, b) in enumerate(zip(ids_a, ids_b)) if a != b),
            min(len(ids_a), len(ids_b)),
        )
        d = max(len(ids_a), len(ids_b)) - n
        diverged_tokens += d
        if d:
            diverged_at.append(n)
        else:
            compared = max(compared, n)
        for la, lb in zip(qa["logprobs"][:n], qb["logprobs"][:n]):
            max_drift = max(max_drift, abs(la - lb))
    # horizon semantics: earliest observed divergence, or — when every
    # stream stayed identical — the longest stream fully verified (a
    # lower bound, not a divergence)
    horizon = min(diverged_at) if diverged_at else compared
    oracle, int8 = arms["oracle"], arms["int8"]
    print(json.dumps({
        "scenario": "kv_quant",
        "metric": "kv_quant_ab",
        "model": config.model.model_id,
        "capacity_ratio": round(
            int8["kv_token_capacity"]
            / max(1, oracle["kv_token_capacity"]), 3
        ),
        "toks_ratio": round(
            int8["toks_per_s"] / max(1e-9, oracle["toks_per_s"]), 3
        ),
        "greedy_identity_horizon": horizon,
        "all_identical": diverged_tokens == 0,
        "quality_max_tokens": quality_tokens,
        "max_logprob_drift": round(max_drift, 4),
        "diverged_tokens": diverged_tokens,
        **device_fields,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": (
            "config.yaml kv_cache.dtype flips to int8 only if "
            "toks_ratio >= 1.0 at equal batch AND the capacity win "
            "holds AND drift/horizon are acceptable on hardware"
        ),
    }), flush=True)


def main() -> int:
    from vgate_tpu.config import apply_platform, load_config

    scen = os.environ.get("VGT_BENCH_SCENARIO")
    if scen and scen != "kv_quant":
        # the lab drives a server subprocess over HTTP, and that child
        # owns the chip: delegate BEFORE this process touches jax
        return _run_loadlab_scenario(scen)

    import jax

    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    apply_platform(load_config().tpu)
    device_fields = _device_fields()

    # VGT_BENCH_MODEL sweeps other registered families (e.g.
    # google/gemma-2-2b-it exercises the sliding-window kernel path)
    model_id = os.environ.get(
        "VGT_BENCH_MODEL", "Qwen/Qwen2.5-1.5B-Instruct"
    )
    # tunables (VGT_BENCH_* env for sweeps).  Long-context runs override
    # e.g. CTX=8192 PROMPT=7900 MAXTOK=128 REQUESTS=8 SLOTS=8; 7B runs
    # override MODEL + QUANT=int8.  None of these defaults has been
    # measured on the current toolchain (ROADMAP D14).
    n_requests = int(os.environ.get("VGT_BENCH_REQUESTS", 128))
    prompt_len = int(os.environ.get("VGT_BENCH_PROMPT", 120))
    max_tokens = int(os.environ.get("VGT_BENCH_MAXTOK", 128))
    slots = int(os.environ.get("VGT_BENCH_SLOTS", 128))
    page_size = int(os.environ.get("VGT_BENCH_PAGE", 32))
    max_model_len = int(os.environ.get("VGT_BENCH_CTX", 512))
    # long contexts prefill in chunks (serial suffix passes) instead
    # of compiling a max_model_len-wide program
    prefill_chunk = int(
        os.environ.get(
            "VGT_BENCH_PREFILL_CHUNK",
            1024 if max_model_len > 2048 else 0,
        )
    )
    # one prefill bucket: the smallest power of two >= the prompt,
    # capped at the chunk size when chunking
    bucket = max(128, 1 << (prompt_len - 1).bit_length())
    if prefill_chunk:
        bucket = min(bucket, prefill_chunk)
    buckets = [bucket]
    decode_chunk = int(os.environ.get("VGT_BENCH_CHUNK", 64))

    config = load_config(
        model={
            "model_id": model_id,
            "engine_type": "jax_tpu",
            "dtype": "bfloat16",
            "max_model_len": max_model_len,
            # None | "int8" | "int4" (weight-only; VGT_BENCH_QUANT sweeps)
            "quantization": os.environ.get("VGT_BENCH_QUANT") or None,
        },
        tpu={
            "dp": 1,
            "tp": 1,
            "ep": 1,
            "sp": 1,
            "num_devices": 1,
            "kv_num_pages": 0,  # auto-size from the chip's memory
            "kv_page_size": page_size,
            "max_batch_slots": slots,
            "prefill_buckets": buckets,
            "prefill_batch_max": int(
                os.environ.get("VGT_BENCH_PREFILL_BATCH", 32)
            ),
            "prefill_chunk": prefill_chunk,
            "decode_chunk": decode_chunk,
            "decode_pipeline": int(
                os.environ.get("VGT_BENCH_PIPE", 2)
            ),
        },
        scheduler={"max_queue_size": 4096},
        logging={"level": "ERROR"},
    )

    if scen == "kv_quant":
        # int8-KV A/B: same model/config, bf16 vs int8 pages — tok/s,
        # resident capacity, and the quality deltas (greedy
        # token-identity horizon + max logprob drift vs the
        # full-precision oracle).  One JSON line per arm + a comparison.
        _run_kv_quant_scenario(
            config, device_fields, n_requests, prompt_len, max_tokens,
            buckets,
        )
        return 0

    core = EngineCore(config, devices=jax.devices()[:1])
    core.start()
    # compile decode + the prefill bucket outside the timed burst
    core.warmup(buckets=buckets)

    try:
        rng_tokens = [
            [3 + (i * 37 + j * 11) % 200 for j in range(prompt_len)]
            for i in range(n_requests)
        ]
        params = SamplingParams(max_tokens=max_tokens, temperature=0.0)

        # VGT_BENCH_RATE > 0: open-loop Poisson arrivals at that many
        # requests/sec instead of one burst.  The burst mode overstates
        # queue-dominated TTFT (every request queues behind the whole
        # batch); the Poisson mode measures TTFT under a realistic
        # arrival process.  Deterministic seed so
        # runs compare.
        rate = float(os.environ.get("VGT_BENCH_RATE", "0") or 0)
        start = time.perf_counter()
        if rate > 0:
            import random as _random

            _r = _random.Random(20260731)
            seqs = []
            for ids in rng_tokens:
                seqs.append(core.submit_tokens(ids, params))
                time.sleep(_r.expovariate(rate))
        else:
            seqs = [core.submit_tokens(ids, params) for ids in rng_tokens]
        for seq in seqs:
            seq.done_event.wait(timeout=1800)
        wall = time.perf_counter() - start

        total_out = sum(s.num_output_tokens for s in seqs)
        ttfts = sorted(s.ttft for s in seqs if s.ttft is not None)
        toks_per_s = total_out / wall if wall > 0 else 0.0
        p50_ttft_ms = (
            ttfts[len(ttfts) // 2] * 1000 if ttfts else float("nan")
        )
        # MFU = achieved FLOP/s over peak (2*params FLOPs per generated
        # token), and the fraction of the HBM decode roofline (every
        # decode step must stream the full weights).  Peaks come from
        # the ONE definition site the live gauges also read
        # (vgate_tpu/observability/roofline.py); a device_kind without a
        # row there is an error, not a default.
        from vgate_tpu.observability.roofline import (
            DEVICE_PEAKS,
            stream_weight_bytes,
        )

        peak_flops, hbm_gbps = DEVICE_PEAKS[device_fields["device_kind"]]
        mfu = (2.0 * core.spec.num_params * toks_per_s) / peak_flops
        # untied embed tables are GATHERED (one row per token), not
        # streamed; only tied models read them fully as lm_head
        weight_bytes = stream_weight_bytes(
            core.params, core.spec.tie_embeddings
        )
        # steps/s at MEASURED average decode concurrency (live decoding
        # slot-seconds over the wall), not the configured slot count —
        # staggered finishes would otherwise understate the fraction.
        # Roofline steps/s = HBM_BW / weight_bytes (KV traffic excluded:
        # optimistic bound).
        live_s = sum(
            (s.finish_t - s.first_token_t)
            for s in seqs
            if s.finish_t is not None and s.first_token_t is not None
        )
        occupancy = min(
            float(min(slots, n_requests)),
            max(1e-6, live_s / wall),
        )
        hbm_frac = (toks_per_s / occupancy) / (
            hbm_gbps * 1e9 / weight_bytes
        )
        p95_ttft_ms = (
            ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.95))] * 1000
            if ttfts
            else float("nan")
        )
        result = {
            "metric": "output_tokens_per_sec_per_chip",
            "value": round(toks_per_s, 2),
            "unit": "tok/s/chip",
            **(
                {
                    "arrival": f"poisson {rate:g} req/s",
                    "p95_ttft_ms": round(p95_ttft_ms, 1),
                }
                if rate > 0
                else {}
            ),
            "mfu": round(mfu, 4),
            "hbm_roofline_frac": round(hbm_frac, 3),
            "p50_ttft_ms": round(p50_ttft_ms, 1),
            "model": model_id,
            "requests": n_requests,
            "output_tokens": total_out,
            "wall_s": round(wall, 2),
            **device_fields,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        print(json.dumps(result))
    finally:
        core.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
