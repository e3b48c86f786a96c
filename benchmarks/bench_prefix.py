"""Prefix-cache reuse-rate sweep: TTFT and prefill-token reduction vs
shared-prefix fraction.

Two workload shapes from the million-user serving mix the radix cache
(vgate_tpu/runtime/radix_cache.py) targets:

* ``multi_turn`` — each user's request extends their own previous
  transcript (prompt + generated answer), the chat/agent-loop shape;
  the measured turn re-sends the warm turn's GENERATED answer, hitting
  transcript pages only the radix tree indexes.
* ``rag`` — every request shares one global preamble (system prompt +
  retrieved corpus) plus a unique tail, the RAG shape; whole-page
  sharing across unrelated users, with mid-page COW at the preamble
  boundary (multi-turn divergence lands past the last indexed
  transcript page, so COW shows up here).

Each (shape, reuse in {0, 0.5, 0.9}) cell runs the same requests
through a cache-ON and a cache-OFF engine (same process, same seeded
random-init weights), reporting mean TTFT, prefilled tokens (submitted
prompt tokens minus prefix hits) and greedy output identity.  One JSON
row per cell, same JSON-lines convention as the other benches.

Run on hardware:

    python benchmarks/bench_prefix.py

or dry-sized on CPU (CI smoke / local verification):

    python benchmarks/bench_prefix.py --cpu
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

CPU_MODE = "--cpu" in sys.argv

import jax  # noqa: E402

from vgate_tpu.backends.base import SamplingParams  # noqa: E402
from vgate_tpu.config import load_config  # noqa: E402
from vgate_tpu.runtime.engine_core import EngineCore  # noqa: E402

REUSE_RATES = (0.0, 0.5, 0.9)
SHAPES = ("multi_turn", "rag")

if CPU_MODE:
    PROMPT_LEN = 192  # tokens per measured request
    N_REQUESTS = 6
    MODEL = {
        "model_id": "tiny-dense", "engine_type": "jax_tpu",
        "dtype": "float32", "max_model_len": 512,
    }
    TPU = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
        "kv_num_pages": 2048, "kv_page_size": 4,
        "max_batch_slots": 8, "prefill_buckets": [16, 32, 64],
        "use_pallas": False,
    }
else:
    PROMPT_LEN = 1008
    N_REQUESTS = 16
    MODEL = {
        "model_id": "Qwen/Qwen2.5-1.5B-Instruct",
        "engine_type": "jax_tpu", "dtype": "bfloat16",
        "max_model_len": 2048,
    }
    TPU = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
        "kv_num_pages": 0, "kv_page_size": 16,
        "max_batch_slots": 16, "prefill_buckets": [64, 1024],
        "decode_chunk": 8, "decode_pipeline": 2,
    }

GEN_TOKENS = 8
GREEDY = SamplingParams(max_tokens=GEN_TOKENS, temperature=0.0)


def make_engine(prefix_cache: bool) -> EngineCore:
    # CPU smoke uses 4-token pages, where the default cow_min_tokens=8
    # could never fire (max partial share is page_size - 1)
    pc = {"enabled": prefix_cache}
    if CPU_MODE:
        pc["cow_min_tokens"] = 2
    config = load_config(
        model=MODEL,
        tpu={**TPU, "prefix_cache": pc},
        scheduler={"max_queue_size": 256},
        logging={"level": "ERROR"},
    )
    core = EngineCore(config, devices=jax.devices()[:1])
    core.start()
    return core


_MAX_TOK = 500 if CPU_MODE else 4000  # inside each model's vocab


def _tokens(seed: str, n: int):
    """A unique pseudo-random token stream per logical role — seeded so
    runs are reproducible, and free of the periodic structure that a
    linear-congruential shortcut would leak across cells (which shows
    up as spurious prefix matches)."""
    import random

    rng = random.Random(seed)
    return [rng.randrange(3, _MAX_TOK) for _ in range(n)]


def build_requests(shape: str, reuse: float, salt: int, extra: int = 0):
    """Per measured request: (warm_prefix_tokens or None, base, tail).
    The warm prefix is submitted first (unmeasured) so the measured
    request's first ``reuse`` fraction is resident; the measured prompt
    is composed in ``run_cell`` AFTER the warm phase — multi_turn
    re-sends the warm turn's GENERATED answer between base and tail
    (the real chat shape, whose generated pages only the radix tree
    indexes), rag shares only the static preamble.  ``extra`` appends
    shakeout requests of the same shape (compile warmup)."""
    shared_len = int(PROMPT_LEN * reuse)
    if shared_len:
        # land the divergence point mid-page so the sweep also
        # exercises the copy-on-write partial-page path (page-aligned
        # splits would only ever take whole-page sharing)
        shared_len += 2
    out = []
    if shape == "rag":
        preamble = _tokens(f"rag-pre-{salt}", shared_len)
        for r in range(N_REQUESTS + extra):
            tail = _tokens(
                f"rag-tail-{salt}-{r}", PROMPT_LEN - shared_len
            )
            warm = preamble if r == 0 and shared_len else None
            out.append((warm, preamble, tail))
    else:  # multi_turn: per-user transcript, measured turn extends it
        for r in range(N_REQUESTS + extra):
            base = _tokens(f"mt-base-{salt}-{r}", shared_len)
            tail = _tokens(
                f"mt-tail-{salt}-{r}", PROMPT_LEN - shared_len
            )
            out.append((base if shared_len else None, base, tail))
    return out


def run_cell(core: EngineCore, shape: str, reuse: float, salt: int):
    requests = build_requests(shape, reuse, salt, extra=1)
    # warm phase: prior turns / the shared preamble pass through the
    # engine first.  multi_turn keeps each warm turn's generated answer
    # and re-sends it inside the measured prompt (base + answer + tail)
    # — identical on the cache-off engine because greedy decode over
    # the same seeded weights generates the same answer there.
    answers = {}
    for i, (warm, _base, _tail) in enumerate(requests):
        if warm is not None and len(warm) > 1:
            seq = core.submit_tokens(list(warm), GREEDY)
            seq.done_event.wait(timeout=600)
            if shape == "multi_turn":
                answers[i] = list(seq.generated_ids)
    prompts = [
        base + answers.get(i, []) + tail
        for i, (_warm, base, tail) in enumerate(requests)
    ]
    # shakeout: the last request (not measured, not reported) compiles
    # every program variant this cell's shape selects, so the measured
    # means compare prefill work, not first-contact XLA compiles
    seq = core.submit_tokens(list(prompts.pop()), GREEDY)
    seq.done_event.wait(timeout=600)
    hits0 = core.scheduler.total_prefix_hit_tokens
    ttfts = []
    outputs = []
    submitted = 0
    for prompt in prompts:
        seq = core.submit_tokens(list(prompt), GREEDY)
        seq.done_event.wait(timeout=600)
        assert seq.error is None, seq.error
        ttfts.append(seq.ttft)
        outputs.append(list(seq.generated_ids))
        submitted += len(prompt)
    hit = core.scheduler.total_prefix_hit_tokens - hits0
    return {
        "mean_ttft_ms": round(1000 * sum(ttfts) / len(ttfts), 2),
        "hit_tokens": hit,
        "prefilled_tokens": submitted - hit,
        "submitted_tokens": submitted,
        "outputs": outputs,
    }


def main() -> None:
    if not CPU_MODE and jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_prefix needs a real TPU (or --cpu)")
    platform = jax.devices()[0].platform
    on = make_engine(True)
    off = make_engine(False)
    try:
        # compile warmup on both engines (the sweep measures prefill
        # reuse, not first-contact XLA compiles)
        for core in (on, off):
            s = core.submit_tokens(
                _tokens("global-warmup", PROMPT_LEN), GREEDY
            )
            s.done_event.wait(timeout=600)
        salt = 0
        for shape in SHAPES:
            for reuse in REUSE_RATES:
                salt += 1
                cow0 = (
                    on.radix_cache.total_cow_copies
                    if on.radix_cache is not None
                    else 0
                )
                got_on = run_cell(on, shape, reuse, salt)
                got_off = run_cell(off, shape, reuse, salt)
                identical = got_on["outputs"] == got_off["outputs"]
                row = {
                    "metric": "prefix_reuse_sweep",
                    "platform": platform,
                    "model": MODEL["model_id"],
                    "shape": shape,
                    "reuse": reuse,
                    "prompt_len": PROMPT_LEN,
                    "requests": N_REQUESTS,
                    "cache_on_mean_ttft_ms": got_on["mean_ttft_ms"],
                    "cache_off_mean_ttft_ms": got_off["mean_ttft_ms"],
                    "ttft_speedup": round(
                        got_off["mean_ttft_ms"]
                        / max(got_on["mean_ttft_ms"], 1e-9),
                        2,
                    ),
                    "hit_tokens": got_on["hit_tokens"],
                    "prefilled_tokens_on": got_on["prefilled_tokens"],
                    "prefilled_tokens_off": got_off["submitted_tokens"],
                    "prefill_reduction": round(
                        got_off["submitted_tokens"]
                        / max(1, got_on["prefilled_tokens"]),
                        2,
                    ),
                    "cow_copies": (
                        on.radix_cache.total_cow_copies - cow0
                        if on.radix_cache is not None
                        else 0
                    ),
                    "outputs_identical": identical,
                }
                print(json.dumps(row), flush=True)
    finally:
        on.stop()
        off.stop()


if __name__ == "__main__":
    main()
