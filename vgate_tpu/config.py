"""Layered configuration system.

Mirrors the reference's config contract (vgate/config.py:15-27, 174-224):
priority is explicit init kwargs > environment variables (``VGT_`` prefix with
``__`` section nesting, e.g. ``VGT_BATCH__MAX_BATCH_SIZE=16``) > YAML file
(``VGT_CONFIG_PATH`` or ``./config.yaml``) > model defaults.  Implemented on
plain pydantic v2 (pydantic-settings is not available in this environment).

TPU-specific additions over the reference: a ``tpu`` section describing the
device mesh, dtype, static-shape buckets and the paged KV cache (SURVEY.md
section 5.6 calls for exactly this extension).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

import yaml
from pydantic import BaseModel, Field, field_validator, model_validator

ENV_PREFIX = "VGT_"
CONFIG_PATH_ENV = "VGT_CONFIG_PATH"


def apply_platform(tpu_cfg) -> None:
    """Pin the JAX platform per ``tpu.platform`` (no-op for "auto").

    Must run before the first JAX backend touch — ``jax.config.update``
    silently does nothing once backends are initialized, so this verifies
    the switch actually took and raises otherwise.  Call sites: engine
    construction and server startup (both before any device use).
    """
    if tpu_cfg.platform == "auto":
        return
    import jax

    # keep the cpu backend registered behind the pinned platform: the
    # quantized-load host staging (engine_core) needs jax.devices("cpu")
    # even when the compute platform is tpu
    platforms = tpu_cfg.platform
    if platforms != "cpu" and "cpu" not in platforms.split(","):
        platforms = f"{platforms},cpu"
    jax.config.update("jax_platforms", platforms)
    actual = jax.devices()[0].platform
    if actual != tpu_cfg.platform:
        raise RuntimeError(
            f"tpu.platform={tpu_cfg.platform!r} requested but JAX backends "
            f"were already initialized on {actual!r}; set the platform "
            "before any jax.devices()/device computation happens"
        )

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A FIXED path under the checkout: the directory is part of the cache
# key's lookup, so a path built from tempfile, a pid or the clock would
# never hit on the next boot.  Listed in .gitignore.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def _requested_platform() -> str:
    """First entry of the platform list JAX was asked for (JAX_PLATFORMS
    or ``apply_platform``'s pin), "" when JAX chooses — read from the
    config so no backend is initialised to find out."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0]


def apply_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no directory is set in code; otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Child processes (pod workers, loadlab's
    server) inherit the variable through ``os.environ``.  Call sites are
    the same as ``apply_platform``: engine construction and server
    startup, plus chip_smoke.py's kernel child.

    A process asked to run on the CPU gets no cache unless the variable
    places one (returns None): XLA:CPU reloads executables through its
    AOT loader, whose code need not match a fresh JIT compile bit for
    bit, and Tier-1's token-identity tests compare engines inside one
    process.  Cold compiles cost minutes on the chip, not here."""
    import jax

    placed = os.environ.get(COMPILE_CACHE_ENV)
    if not placed and _requested_platform() == "cpu":
        return None
    # cache every program, not only those that took over a second to
    # compile: a warm boot then compiles nothing, and whether a program
    # is cached does not depend on how long a compile happened to take
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


# "vllm" is the optional comparison backend (backends/vllm_backend.py):
# selectable everywhere, fails with a clear error unless a vllm wheel is
# installed (the reference benchmarks vLLM/SGLang side by side)
VALID_ENGINE_TYPES = ("dry_run", "jax_tpu", "vllm", "sglang")


class ServerConfig(BaseModel):
    """HTTP server settings (reference: vgate/config.py:37-40)."""

    host: str = "0.0.0.0"
    port: int = 8000
    request_timeout_s: float = 300.0


class ModelConfig(BaseModel):
    """Model + engine selection (reference: vgate/config.py:42-59)."""

    model_id: str = "Qwen/Qwen2.5-1.5B-Instruct"
    engine_type: str = "jax_tpu"
    # Local checkpoint dir with safetensors; None => random-init weights
    # (this environment has no network egress, so HF downloads are gated).
    checkpoint_path: Optional[str] = None
    tokenizer_path: Optional[str] = None
    dtype: str = "bfloat16"
    quantization: Optional[str] = None  # None | "int8" | "int4"

    @field_validator("quantization")
    @classmethod
    def _check_quantization(cls, v: Optional[str]) -> Optional[str]:
        if v is not None and v not in ("int8", "int4"):
            raise ValueError(
                f'model.quantization must be "int8", "int4" or null, got {v!r}'
            )
        return v
    max_model_len: int = 2048
    embedding_model_id: str = "BAAI/bge-base-en-v1.5"
    embedding_checkpoint_path: Optional[str] = None
    # Speculative decoding with a draft MODEL (tpu.speculative_k > 0):
    # a second, smaller registered model proposes tokens each round
    # (runtime/speculative.py DraftModelDrafter) instead of prompt
    # lookup.  Same tokenizer family as model_id (e.g. Qwen2.5-0.5B
    # drafting for 1.5B/7B); None keeps n-gram drafting.
    draft_model_id: Optional[str] = None
    draft_checkpoint_path: Optional[str] = None

    @field_validator("engine_type")
    @classmethod
    def _check_engine_type(cls, v: str) -> str:
        if v not in VALID_ENGINE_TYPES:
            raise ValueError(
                f"engine_type must be one of {VALID_ENGINE_TYPES}, got {v!r}"
            )
        return v

    @field_validator("dtype")
    @classmethod
    def _check_dtype(cls, v: str) -> str:
        if v not in ("bfloat16", "float32", "float16"):
            raise ValueError(f"unsupported dtype {v!r}")
        return v


class KVCacheConfig(BaseModel):
    """Paged KV cache storage format (runtime/kv_cache.py pools;
    ops/kv_quant.py).  Geometry (page size, pool sizing) stays under
    ``tpu.*`` — this section governs only what the pages HOLD.

    ``dtype``:

    * ``auto`` (default) — pages store the model compute dtype
      (bf16 in serving configs, f32 on CPU test meshes).
    * ``bf16`` — force bf16 pages regardless of compute dtype.
    * ``int8`` — quantize-on-write int8 KV: pages store int8 K/V plus
      one bf16 scale per (page, head, token slot); dequantization
      happens in the attention read (inside the Pallas page-DMA
      kernels and their jnp twins), so HBM only ever moves int8.  The
      same HBM budget then holds ~2x the bf16 page count (1.94x at
      head_dim 64, 1.97x at 128) — the capacity half of the decode
      roofline lever (ROADMAP "Attack the decode roofline").
      Requires a plain mesh (tp/pp/sp/ep == 1; dp composes — each
      replica owns its pool).  Quality: per-token-per-head symmetric
      scales bound the per-element error at ~0.4% of the row absmax;
      tests/test_kv_quant.py holds the logprob drift and the greedy
      token-identity horizon vs the bf16 oracle on the tiny model.
      bf16 stays the default until a cell of the benchmark
      (perfbench/README.md) adjudicates the flip on the chip
      (docs/operations.md capacity planning).
    """

    dtype: str = "auto"

    # Host-RAM KV swap tier (runtime/kv_swap.py): a budgeted pinned
    # host pool under the paged allocator.  > 0 enables it: KV-pressure
    # preemption swaps the victim's pages device->host and re-admission
    # swaps them back (token-identical resume, ZERO recompute tokens),
    # and radix-cache eviction demotes warm prefix pages into the same
    # pool (victim cache) before truly discarding.  0 (default) = off,
    # byte-identical to the pre-swap engine.  Requires a plain mesh
    # (tp/pp/sp/ep == 1; dp composes — each replica owns its pool and
    # host tier).  Sizing: each page costs geometry.page_bytes of host
    # RAM (see /stats engine.kv_page_bytes); the pool should hold at
    # least a few preemption victims' contexts — docs/operations.md
    # "KV pressure tiers" runbook.
    host_swap_bytes: int = 0

    @field_validator("host_swap_bytes")
    @classmethod
    def _check_swap(cls, v: int) -> int:
        if v < 0:
            raise ValueError(
                "kv_cache.host_swap_bytes must be >= 0 (0 disables)"
            )
        return v

    @field_validator("dtype")
    @classmethod
    def _check_dtype(cls, v: str) -> str:
        allowed = ("auto", "bf16", "int8")
        if v not in allowed:
            raise ValueError(
                f"kv_cache.dtype must be one of {allowed}, got {v!r}"
            )
        return v


class PrefixCacheConfig(BaseModel):
    """Cross-request KV prefix sharing (runtime/radix_cache.py;
    docs/operations.md "Cross-request KV reuse").  Accepts a bare bool
    for backward compatibility (``tpu.prefix_cache: true`` enables with
    defaults)."""

    enabled: bool = True
    # Page-granular radix tree with refcounted sharing, generated-token
    # reuse and COW partial pages; false falls back to the flat
    # whole-page hash chain (the pre-radix index, kept for comparison).
    radix: bool = True
    # Minimum full pages a match must share to be taken at all — tiny
    # shares cost tree locks and dispatch complexity for little reuse.
    min_share_pages: int = 1
    # Copy-on-write partial-page sharing: device-copy the shared head of
    # a diverging page so prefill starts mid-page.  Requires sp == 1
    # (the copy program indexes the unsharded pool).
    cow: bool = True
    # Shared tokens inside the diverging page below this are recomputed
    # instead of copied (a device copy has dispatch overhead).
    cow_min_tokens: int = 8
    # Index a finished sequence's generated tokens too (multi-turn chat:
    # turn N+1 re-sends turn N's answer inside its prompt).
    insert_generated: bool = True
    # Scheduler prefers admitting waiting work that shares resident tree
    # nodes (bounded FIFO bypass), keeping hot prefixes co-batched.
    cache_aware_sched: bool = True
    # Proactive eviction: keep at least this fraction of the pool truly
    # free by trimming cold cache (reason="pressure") from the engine
    # tick — ahead of admission's kv_free_watermark shedding.
    evict_watermark: float = 0.08

    @field_validator("min_share_pages")
    @classmethod
    def _check_min_share(cls, v: int) -> int:
        if v < 1:
            raise ValueError("prefix_cache.min_share_pages must be >= 1")
        return v

    @field_validator("evict_watermark")
    @classmethod
    def _check_watermark(cls, v: float) -> float:
        if not 0.0 <= v < 1.0:
            raise ValueError(
                "prefix_cache.evict_watermark must be in [0, 1)"
            )
        return v


class TPUConfig(BaseModel):
    """Device mesh + engine shape settings (TPU-only addition, SURVEY.md 5.6).

    Mesh axes follow the scaling-book convention: data (dp), tensor/model
    (tp), expert (ep) and sequence (sp) parallelism.  ``mesh_shape`` values of
    0 mean "use all visible devices on this axis" resolved at engine start.
    """

    dp: int = 1
    pp: int = 1  # pipeline stages (layer stack split; parallel/pipeline.py)
    tp: int = 0  # 0 => all devices
    ep: int = 1
    sp: int = 1
    # JAX platform to pin before device init: "auto" keeps whatever the
    # environment selects (JAX_PLATFORMS, else JAX's own default); "cpu"
    # and "tpu" are the config-file form of that pin.  "tpu" makes a
    # machine whose chip failed to initialise fail at start instead of
    # serving from the CPU.
    platform: str = "auto"

    @field_validator("platform")
    @classmethod
    def _check_platform(cls, v: str) -> str:
        allowed = {"auto", "cpu", "tpu"}
        if v not in allowed:
            raise ValueError(
                f"tpu.platform must be one of {sorted(allowed)}, got {v!r}"
            )
        return v
    num_devices: int = 0  # 0 => every visible device; else use a subslice
    # Paged KV cache geometry.
    # tokens per page: a 16-token page is a 4 KB DMA per kv head at
    # head_dim 128; 32 doubles it.  Not measured on the current
    # toolchain (ROADMAP D14).
    kv_page_size: int = 32
    kv_num_pages: int = 0  # 0 => auto-size from free HBM
    hbm_utilization: float = 0.9
    # Continuous batching shapes (static for XLA).
    max_batch_slots: int = 32
    prefill_buckets: List[int] = Field(
        default_factory=lambda: [128, 256, 512, 1024, 2048]
    )
    # Use Pallas kernels where available; False falls back to jnp reference
    # implementations (needed on CPU test meshes).
    use_pallas: bool = True
    # Fused dequant-matmul Pallas kernels for int8/int4 weights.
    # Default OFF: they have never finished a compile on a chip and are
    # not measured on the current toolchain (ROADMAP S6) — quantized
    # serving rides the jnp dequant path.  Opt in via
    # VGT_TPU__QUANT_KERNEL=true.
    quant_kernel: bool = False
    # W8A8/W4A8: dynamically quantize activations per-token (int8) and
    # run projection GEMMs on the MXU's NATIVE s8 x s8 -> s32 path (twice
    # the v5e's published bf16 peak) — pure jnp, no Pallas/Mosaic, and
    # it auto-partitions under any mesh.  Changes numerics (~1% per-GEMM
    # quantization error on top of weight quant), so opt-in until the
    # accuracy/throughput trade is measured on hardware
    # (VGT_TPU__INT8_NATIVE=true; applies when model.quantization is
    # int8 or int4).
    int8_native: bool = False
    # Per-chip HBM budget in bytes for KV auto-sizing, read ONLY when the
    # device reports no memory_stats (the v5e does report).  0 => such a
    # device is an error at engine start, not an assumed size.
    hbm_bytes: int = 0
    # Decode steps fused into one device program (lax.scan over the step
    # body).  The host reads tokens back once per chunk, amortizing the
    # host<->device round-trip over `decode_chunk` tokens per slot; chunk
    # sizes actually compiled are the powers of two <= this value.
    decode_chunk: int = 8
    # Keep up to `decode_pipeline` chunks in flight before blocking on the
    # oldest readback (overlaps host processing with device execution).
    decode_pipeline: int = 2
    # Max prefills admitted per engine tick WHILE sequences are decoding
    # (0 = unlimited).  Bounds the decode stall a prefill burst can cause:
    # resident slots get a decode chunk between every admission wave
    # instead of waiting out the whole burst.  Defaults to one full
    # batched-prefill program (prefill_batch_max).
    prefill_admit_limit: int = 8
    # Same-bucket prompts prefilled in ONE stacked [B, bucket] program
    # (B pads to a power of two).  Cuts dispatch count ~B-fold for bursts.
    prefill_batch_max: int = 8
    # Chunked prefill: cap the prefill-bucket ladder at this many tokens
    # and run longer prompts as serial page-aligned passes through the
    # suffix-prefill program (each chunk attends the resident context).
    # Long contexts then never compile a max_model_len-wide program —
    # an 8k prompt is e.g. eight 1k-chunk dispatches.  0 disables
    # (the top bucket covers max_model_len, the r2 behavior).  Requires
    # sp == 1 and pp == 1 (those reshape the prompt pass).
    prefill_chunk: int = 0
    # Cross-request KV prefix sharing (runtime/radix_cache.py): prompt
    # (and, with the radix tree, generated) pages are content-indexed
    # and shared across requests; a prefix hit prefills only the
    # suffix.  A bare bool is accepted (`prefix_cache: false`) and
    # coerced to {enabled: false}.  Disabled automatically when pp>1
    # (the relay prompt pass reshapes incompatibly).
    prefix_cache: PrefixCacheConfig = Field(
        default_factory=PrefixCacheConfig
    )

    @field_validator("prefix_cache", mode="before")
    @classmethod
    def _coerce_prefix_cache(cls, v):
        # the knob shipped as a bool through r5; a bare bool (config
        # files, env VGT_TPU__PREFIX_CACHE=false, test kwargs) keeps
        # working as the master switch
        if isinstance(v, bool):
            return {"enabled": v}
        return v
    # Speculative decoding: each decode round verifies up to
    # `speculative_k` drafted tokens in ONE forward pass, so accepted
    # drafts cost one model read for several tokens.  Greedy rows
    # verify by exact argmax match; sampled rows by rejection sampling
    # (both distribution-exact, runtime/speculative.py).  Drafts come
    # from prompt-lookup, or from a draft MODEL when
    # model.draft_model_id is set.  0 = off (the default; neither mode
    # is measured on the current toolchain — ROADMAP R7).
    speculative_k: int = 0
    # Match length for the prompt-lookup drafter.
    speculative_ngram: int = 2
    # Token window the draft MODEL sees (model.draft_model_id): each
    # draft round recomputes this suffix window, so it bounds the
    # drafter's cost and its context.
    draft_window: int = 128


class BatchConfig(BaseModel):
    """Gateway-side dynamic batching (reference: vgate/config.py:62-66)."""

    max_batch_size: int = 8
    max_wait_time_ms: float = 50.0


class CacheConfig(BaseModel):
    """Result cache (reference: vgate/config.py:68-72)."""

    enabled: bool = True
    max_size: int = 1024


class SchedulerConfig(BaseModel):
    """Continuous-batching scheduler (no reference equivalent; lives inside
    vLLM in the reference — SURVEY.md section 2.1)."""

    max_queue_size: int = 512
    # Shed a queued request instead of admitting it when it has already
    # waited longer than this (0 => no deadline-based shedding); the client
    # gets 503 + Retry-After rather than a late, useless completion.
    admission_deadline_ms: float = 0.0
    preempt_on_oom: bool = True


class RecoveryConfig(BaseModel):
    """Supervised engine recovery (runtime/supervisor.py): a fatal
    engine-loop error tears the core down and rebuilds it (weights kept,
    KV + scheduler state fresh) instead of killing serving until a
    process restart.  The health state machine SERVING → DEGRADED →
    RECOVERING → DEAD is surfaced through /health and /stats."""

    # dp == 1 engines only; ReplicatedEngine (tpu.dp > 1) has its own
    # replica failover and stays unsupervised.
    enabled: bool = True
    # Restart budget: more than `max_restarts` restarts within
    # `restart_window_s` lands the engine in DEAD (liveness probe then
    # recycles the pod) instead of crash-looping forever.
    max_restarts: int = 3
    restart_window_s: float = 300.0
    # Capped exponential backoff before each rebuild attempt.
    backoff_base_s: float = 0.25
    backoff_cap_s: float = 30.0
    # A freshly restarted engine serves in DEGRADED for this long; one
    # crash-free probation promotes it back to SERVING.
    degraded_probation_s: float = 30.0
    # A request in flight across this many consecutive crashes is
    # quarantined as suspected poison (rejected at submission with a 400
    # so it cannot crash the next incarnation).
    poison_threshold: int = 2
    # In-flight request survival: on any supervised restart (fatal,
    # poison sweep, or watchdog trip) checkpoint every live sequence's
    # resumable state and replay it into the rebuilt engine as a
    # prefill-continue (prompt + partial generation), so clients see a
    # latency blip instead of a 503.  Deadlines stay anchored to the
    # original budget; quarantined fingerprints are excluded.
    resume_in_flight: bool = True
    # A sequence checkpointed across more than this many restarts is
    # given up on (typed retryable 503) instead of replaying forever.
    max_resume_attempts: int = 3
    # Hang watchdog: the engine loop heartbeats around every dispatch/
    # readback; a beat older than step_stall_s is classified as an
    # EngineStalledError and fed through the supervisor path (stall →
    # checkpoint → rebuild → replay).  0 disables the watchdog.
    step_stall_s: float = 120.0
    # First-compile of a program variant can legitimately pause the
    # loop for minutes (XLA/Mosaic); beats carrying compiling=True get
    # this grace instead of step_stall_s.
    compile_grace_s: float = 900.0


class IntegrityConfig(BaseModel):
    """Silent-corruption defense (vgate_tpu/integrity.py): output
    sentinels folded into the engine tick, budgeted weight-checksum
    sweeps on idle ticks, canary self-probes, and the reload-on-corrupt
    rebuild mode in the supervisor / dp repair loop.  With
    ``enabled=false`` the engine byte-for-byte matches the
    pre-integrity behavior (no guard in the decode program, no sweep,
    no canary, corrupt classification falls back to transient)."""

    enabled: bool = True
    # --- output sentinels (per decode-chunk readback) ---
    sentinels_enabled: bool = True
    # Fold a per-slot guard word (NaN/Inf, all-zero row, saturated row)
    # into the jitted decode chunk; [B] uint8 rides back with the
    # sampled tokens.  Off = host-side token checks only.
    logit_guard: bool = True
    # |logit| at/above this trips the saturated-row sentinel.
    saturate_threshold: float = 1.0e4
    # Entropy collapse: a generation SAMPLING at temperature >=
    # entropy_min_temp that emits fewer than entropy_min_distinct
    # distinct tokens over a full entropy_window is a collapsed
    # distribution.  0 disables the window check (greedy runs are
    # never checked — repetition is legitimate there).
    entropy_window: int = 64
    entropy_min_distinct: int = 2
    entropy_min_temp: float = 0.5
    # --- weight checksum sweeps ---
    sweep_enabled: bool = True
    # Seconds between FULL sweep passes (the budget below spreads one
    # pass over many idle ticks; a pass only begins this long after
    # the previous one finished).
    sweep_interval_s: float = 30.0
    # Leaves verified per idle tick — the budget that keeps the sweep
    # from ever stealing a decode tick (each leaf is one small
    # on-device reduction + scalar readback).
    sweep_leaves_per_tick: int = 2
    # --- canary self-probes ---
    canary_enabled: bool = True
    # Slow-timer probe period per replica (0 = only on rebuild /
    # undrain / add_replica).  The first probe against a presumed-good
    # core RECORDS the fingerprint; later probes verify it.
    canary_interval_s: float = 0.0
    canary_prompt_len: int = 8
    canary_max_tokens: int = 8
    # Record the canary fingerprint at engine START (known-good boot,
    # fresh from the checkpoint) instead of lazily at the first gate.
    # STRONGLY recommended in production: without a boot baseline, the
    # first-ever probe — possibly the post-reload gate after a
    # corruption — records instead of verifies, and a corrupt on-disk
    # checkpoint would be baselined as truth.  Default off only because
    # it costs one probe (plus its compiles) per process start.
    canary_record_on_start: bool = False
    canary_timeout_s: float = 60.0
    # Extra probe headroom when the target core has executed ZERO steps
    # (post-reload / fresh add_replica): the probe's prefill/decode
    # programs compile inside it — the recovery.compile_grace_s lesson
    # applied to canaries, so a first-compile pause cannot quarantine a
    # healthy replica.
    canary_compile_grace_s: float = 900.0


class MigrationConfig(BaseModel):
    """Planned live request migration (runtime/dp_engine.py +
    /admin/replicas): generalizes the crash-time checkpoint/replay into
    an operational primitive — drain a replica for a rolling deploy
    with zero 5xx, rebalance long decodes off a pressured replica, and
    grow/shrink the dp degree without a process restart.  Requires
    tpu.dp > 1 (a dp=1 deployment has no in-process migration target;
    use the SIGTERM graceful drain instead)."""

    # Master switch for the admin drain/undrain/scale surface and the
    # VGT_DRAIN_REPLICA signal path.
    enabled: bool = True
    # How long an evacuation may wait for the source engine loop to
    # checkpoint the selected sequences (the loop may legitimately be
    # inside a long device dispatch; a wedged loop is the watchdog's
    # job, not this timeout's).
    evacuate_timeout_s: float = 30.0
    # --- hot-replica rebalancing policy thread (vgt-dp-balance) ---
    # Moves the longest-running decodes off a pressure-browned replica
    # while a sibling sits idle.  Conservative by construction:
    # hysteresis (sustained pressure for rebalance_hold_s), rate
    # limiting (one move batch per rebalance_cooldown_s), and bounded
    # batch size, so it can never thrash sequences back and forth.
    rebalance_enabled: bool = True
    rebalance_interval_s: float = 2.0
    # A replica is "hot" while its kv_free_ratio is at/below this OR
    # its engine queue depth is at/above hot_queue_depth — the same
    # pressure_signals() the admission brownout keys off.
    hot_kv_free_ratio: float = 0.15
    hot_queue_depth: int = 8
    # A target replica is "idle" only with at least this free-KV ratio
    # and an empty engine queue — rebalancing onto a busy sibling just
    # moves the pressure around.
    idle_kv_free_ratio: float = 0.5
    # Hysteresis: the replica must be CONTINUOUSLY hot this long before
    # the first move (a single tick of pressure is admission's job).
    rebalance_hold_s: float = 10.0
    # Rate limit: at most one move batch per cooldown window.
    rebalance_cooldown_s: float = 30.0
    # Sequences moved per batch (longest-running decodes first — they
    # free the most KV per move).
    max_moves_per_cycle: int = 2
    # Never move a decode younger than this many generated tokens: the
    # replay re-prefills the whole context, so very young sequences
    # cost more to move than to finish.
    min_generated_tokens: int = 8


class PodConfig(BaseModel):
    """Process-isolated engine workers (runtime/pod_engine.py +
    runtime/worker.py): the gateway process runs the HTTP surface,
    batcher and admission; each engine lives in its own worker
    process, reached over a length-prefixed frame protocol on a
    unix-domain (or localhost TCP) socket.  One wedged engine, native
    crash or OOM then costs one worker — the pod degrades and heals
    (heartbeats → route-around → supervised respawn → canary gate)
    instead of dying.  ``workers=0`` (the default) keeps today's
    in-process engines byte-identical; the restart budget/backoff and
    the canary gate reuse ``recovery.*`` / ``integrity.*``."""

    # Engine worker processes.  0 = in-process engines (EngineCore /
    # EngineSupervisor / ReplicatedEngine exactly as before); N >= 1
    # spawns N single-engine worker processes behind a PodEngine
    # router presenting the ReplicatedEngine surface.
    workers: int = 0
    # uds = unix-domain sockets under socket_dir (default: a private
    # tempdir); tcp = 127.0.0.1:port_base+i (environments without UDS).
    transport: str = "uds"
    socket_dir: str = ""
    port_base: int = 9310
    # Worker interpreter override (tests/drills); empty = sys.executable.
    python: str = ""
    # Bounded RPC plane: every connect and every call carries a
    # deadline — a wedged worker must cost a timeout, never a hang.
    connect_timeout_s: float = 10.0
    call_timeout_s: float = 30.0
    # Worker boot → hello budget (imports + weight init + first pools;
    # generous because CPU CI machines are slow and real boots compile).
    spawn_timeout_s: float = 180.0
    # Heartbeat liveness: the gateway pings every worker at this
    # cadence; a worker whose last successful ping is older than
    # heartbeat_timeout_s is declared lost (its in-flight requests
    # resubmit to survivors and a respawn begins).  The worker-side
    # engine beat rides back on each ping and is judged with the PR-5
    # classifier (recovery.step_stall_s / compile_grace_s), so a
    # first-compile pause never reads as death.
    heartbeat_interval_s: float = 0.5
    heartbeat_timeout_s: float = 10.0
    # Frame-size ceiling both directions: an oversized length prefix is
    # a protocol violation (typed error + connection teardown), never
    # an attempted allocation.
    max_frame_bytes: int = 8 * 1024 * 1024
    # Disaggregated prefill/decode pools: one role per worker, each
    # "prefill" | "decode" | "mixed".  Empty (the default) keeps every
    # worker "mixed" — byte-identical routing to the symmetric pod.
    # With roles set, new requests route to the prefill pool; after the
    # first token the sequence's KV pages hand off to a least-loaded
    # decode worker over a chunked, checksummed, epoch-stamped RPC
    # transfer.  A dead/empty decode pool degrades to monolithic decode
    # on the prefill worker — latency, never a 5xx.
    roles: List[str] = Field(default_factory=list)
    # KV handoff transfer plane.  Chunks must fit max_frame_bytes with
    # base64 + JSON envelope headroom.
    transfer_chunk_bytes: int = 1 * 1024 * 1024
    # Bounded retries per handoff before falling back to monolithic
    # decode on the prefill worker (each retry may re-pick the target).
    transfer_max_retries: int = 3
    # Per-RPC deadline for fetch/put/commit calls during a handoff.
    transfer_timeout_s: float = 30.0
    # Host staging-pool floor injected into role-split workers whose
    # config has kv_cache.host_swap_bytes=0 — the handoff stages KV
    # through that pool, so it must exist on both sides.
    transfer_staging_bytes: int = 64 * 1024 * 1024
    # Gateway-crash survivability: how long a worker outlives its
    # gateway.  0 (the default) keeps today's behavior byte-identical —
    # gateway EOF means the worker drains and exits.  > 0 makes gateway
    # EOF enter an explicit ORPHANED state instead: in-flight decodes
    # run to completion (frames buffered for replay), new submits are
    # refused with a typed retryable error, idle residents checkpoint,
    # and the worker keeps listening so a restarted gateway can adopt
    # it (warm weights, compile ledger and radix cache all survive a
    # gateway crash).  Only after the grace expires does the worker
    # self-terminate through the normal drain fold.  Requires a stable
    # pod.socket_dir — a successor gateway finds orphans through the
    # registry records written there.
    orphan_grace_s: float = 0.0

    @field_validator("transport")
    @classmethod
    def _check_transport(cls, v: str) -> str:
        if v not in ("uds", "tcp"):
            raise ValueError(
                f"pod.transport must be 'uds' or 'tcp', got {v!r}"
            )
        return v

    @field_validator("workers")
    @classmethod
    def _check_workers(cls, v: int) -> int:
        if v < 0:
            raise ValueError("pod.workers must be >= 0")
        return v

    @field_validator("roles")
    @classmethod
    def _check_roles(cls, v: List[str]) -> List[str]:
        for r in v:
            if r not in ("prefill", "decode", "mixed"):
                raise ValueError(
                    "pod.roles entries must be 'prefill', 'decode' or "
                    f"'mixed', got {r!r}"
                )
        return v

    @field_validator(
        "transfer_chunk_bytes", "transfer_max_retries",
        "transfer_timeout_s", "transfer_staging_bytes",
    )
    @classmethod
    def _check_transfer(cls, v, info):
        if v <= 0:
            raise ValueError(f"pod.{info.field_name} must be > 0")
        return v

    @field_validator("orphan_grace_s")
    @classmethod
    def _check_orphan_grace(cls, v: float) -> float:
        if v < 0:
            raise ValueError("pod.orphan_grace_s must be >= 0")
        return v

    @model_validator(mode="after")
    def _check_roles_len(self) -> "PodConfig":
        if self.roles and len(self.roles) != self.workers:
            raise ValueError(
                f"pod.roles has {len(self.roles)} entries but "
                f"pod.workers={self.workers}; give one role per worker "
                "(or leave roles empty for an all-mixed pod)"
            )
        return self


class GatewayConfig(BaseModel):
    """Gateway-process survivability (runtime/journal.py +
    server/app.py): a durable request journal keyed by the client's
    ``Idempotency-Key`` header.  Accepted-but-unsettled requests are
    appended (fsync'd) before dispatch and settled with their result
    body on completion; a restarted gateway replays the journal so a
    retried request whose generation already completed (possibly on an
    orphaned worker, see ``pod.orphan_grace_s``) returns the identical
    result with zero recompute, an incomplete one re-submits through
    normal admission, and a duplicate in-flight key gets a typed 409."""

    # Journal file path; "" disables journaling (idempotency keys are
    # then honored only within one gateway lifetime, in memory).
    journal_path: str = ""
    # fsync every append.  Off trades durability of the last few
    # records against write latency (the OS still flushes eventually).
    journal_fsync: bool = True
    # Compaction trigger: when the file exceeds this, settled/expired
    # records are dropped and the journal is rewritten in place.
    journal_max_bytes: int = 16 * 1024 * 1024
    # Settled records older than this are eligible for compaction and
    # no longer replayable — bounds both file growth and how long a
    # client may retry with the same key and expect a replay.
    journal_retention_s: float = 3600.0

    @field_validator("journal_max_bytes", "journal_retention_s")
    @classmethod
    def _check_positive(cls, v, info):
        if v <= 0:
            raise ValueError(f"gateway.{info.field_name} must be > 0")
        return v


class LifecycleConfig(BaseModel):
    """Graceful shutdown/drain (server/app.py + vgate_tpu/lifecycle.py):
    SIGTERM flips /health/ready to 503 ("draining"), admission stops
    with Retry-After, in-flight requests run to completion up to
    ``drain_timeout_s``, stragglers are aborted, then the process exits.
    Wired to the k8s preStop hook + terminationGracePeriodSeconds
    (k8s/base/deployment.yaml; docs/operations.md)."""

    # Install the SIGTERM drain handler when serving (main/run_app).
    # Off => aiohttp's default immediate-teardown SIGTERM behavior.
    drain_enabled: bool = True
    # In-flight requests get this long to finish after SIGTERM before
    # being aborted.  terminationGracePeriodSeconds must exceed
    # preStop sleep + this + a teardown margin.
    drain_timeout_s: float = 30.0
    # Drain-completion poll cadence.
    drain_poll_ms: float = 50.0
    # Retry-After suggested to clients shed during the drain (they
    # should land on another replica once the LB converges).
    drain_retry_after_s: float = 2.0


# the canonical tier vocabulary lives with the admission policy
# (admission.py has no config import, so this cannot cycle)
from vgate_tpu.admission import TIERS as VALID_TIERS  # noqa: E402


class AdmissionConfig(BaseModel):
    """Overload protection (vgate_tpu/admission.py): token-budget
    admission control, priority tiers and the adaptive brownout
    controller.  The gateway estimates each request's cost (prompt
    tokens + max_tokens) at submit time and **refuses work it cannot
    finish** — 503 + Retry-After when the backlog/KV limits are hit,
    429 for the per-key in-flight cap — instead of queuing into a
    deadline 504.  docs/operations.md has the runbook."""

    enabled: bool = True
    # Reject when the estimated token backlog (admitted but unsettled
    # prompt+completion tokens) would exceed this.  0 = unlimited.
    max_queued_tokens: int = 200_000
    # Capacity-scaled token budget: when > 0, the effective backlog
    # limit is max(max_queued_tokens, this x the engine's resident KV
    # token capacity) — flipping kv_cache.dtype to int8 (~2x resident
    # tokens for the same HBM) then raises the admission budget with
    # it instead of leaving a hand-tuned number sized for bf16.
    # 0 keeps the static limit only.
    auto_token_budget: float = 0.0
    # Reject when this many requests are admitted but unsettled.
    # 0 = unlimited.
    max_queued_requests: int = 256
    # Reject a deadline-carrying request whose predicted queue wait
    # (backlog / decode-throughput EWMA) already exceeds its deadline —
    # cheaper to refuse at the door than to shed mid-queue as a 504.
    reject_would_miss_slo: bool = True
    # KV free-page ratio floor: below it new work is rejected
    # (tier-scaled — batch tier rejects at a higher free ratio than
    # interactive).  0 disables the check.
    kv_free_watermark: float = 0.05
    # Host-swap pressure relief (kv_cache.host_swap_bytes > 0): with
    # the swap tier healthy (host pool has headroom), the kv_pressure
    # watermark above is multiplied by this factor — admission can run
    # the device pool hotter because a preemption there now costs a
    # cheap swap-out/swap-in instead of a full re-prefill (the cost
    # model charges swap-in, not recompute, for preempted work).
    # 1.0 = no relief; 0 disables the relief entirely.
    swap_kv_relief: float = 0.5
    # Per-API-key in-flight cap -> 429 + Retry-After.  0 = unlimited;
    # applies only to authenticated (Bearer-keyed) requests.
    per_key_max_inflight: int = 0
    # api key -> tier; a mapped key's tier also CAPS the request's own
    # `priority` field (a batch-mapped key cannot claim interactive).
    key_tiers: Dict[str, str] = Field(default_factory=dict)
    default_tier: str = "standard"
    # Weighted dequeue at the gateway batcher: per batch-fill cycle,
    # take up to this many requests from each tier, highest first.
    tier_weights: Dict[str, int] = Field(
        default_factory=lambda: {
            "interactive": 8, "standard": 4, "batch": 1,
        }
    )
    # Strict-priority shedding: each tier sees the backlog limits scaled
    # by its fraction (and the KV watermark divided by it), so batch
    # rejects first and interactive last as pressure rises.
    tier_fractions: Dict[str, float] = Field(
        default_factory=lambda: {
            "interactive": 1.0, "standard": 0.85, "batch": 0.6,
        }
    )
    # Decode-throughput EWMA feeding the queue-wait estimate.
    throughput_alpha: float = 0.3
    throughput_init_tps: float = 400.0
    # Cache-aware admission (vgate_tpu/admission.py PrefixHintIndex):
    # discount a request's estimated prompt cost by its predicted
    # prefix-cache hit, capped at this fraction of the prompt estimate
    # — a 90%-cached request must not be shed as if it were cold.
    # 0 disables; only meaningful with tpu.prefix_cache enabled.
    prefix_discount: float = 0.9

    # -- adaptive brownout (PressureController) --
    brownout_enabled: bool = True
    # Predicted queue wait that counts as pressure 1.0.
    target_wait_s: float = 5.0
    brownout_update_interval_s: float = 0.5
    # Hysteresis: a level releases (one step at a time) only after the
    # score has stayed below engage*release_ratio for this long.
    brownout_hold_s: float = 10.0
    brownout_release_ratio: float = 0.8
    # Pressure-score thresholds engaging levels 1..4.  The degradation
    # steps, in engage order: clamp max_tokens -> shrink the batch
    # window -> disable speculative decoding -> bypass result-cache
    # writes.
    brownout_engage: List[float] = Field(
        default_factory=lambda: [0.5, 0.7, 0.85, 0.95]
    )
    # Level >= 1: clamp every request's max_tokens to this.
    brownout_max_tokens: int = 128
    # Level >= 2: shrink batch.max_wait_time_ms to this.
    brownout_wait_ms: float = 10.0

    @field_validator("default_tier")
    @classmethod
    def _check_default_tier(cls, v: str) -> str:
        if v not in VALID_TIERS:
            raise ValueError(
                f"admission.default_tier must be one of {VALID_TIERS}, "
                f"got {v!r}"
            )
        return v

    @field_validator("key_tiers")
    @classmethod
    def _check_key_tiers(cls, v: Dict[str, str]) -> Dict[str, str]:
        for key, tier in v.items():
            if tier not in VALID_TIERS:
                raise ValueError(
                    f"admission.key_tiers[{key!r}] must be one of "
                    f"{VALID_TIERS}, got {tier!r}"
                )
        return v

    @field_validator("prefix_discount")
    @classmethod
    def _check_prefix_discount(cls, v: float) -> float:
        if not 0.0 <= v <= 1.0:
            raise ValueError(
                "admission.prefix_discount must be in [0, 1]"
            )
        return v

    @field_validator("brownout_engage")
    @classmethod
    def _check_engage(cls, v: List[float]) -> List[float]:
        if len(v) != 4 or any(
            b <= a for a, b in zip(v, v[1:])
        ):
            raise ValueError(
                "admission.brownout_engage must be 4 strictly "
                f"ascending thresholds, got {v!r}"
            )
        return v


class InferenceConfig(BaseModel):
    """Default sampling parameters (reference: vgate/config.py:74-80)."""

    max_tokens: int = 256
    temperature: float = 0.7
    top_p: float = 0.95
    top_k: int = 0  # 0 => disabled


class LoggingConfig(BaseModel):
    level: str = "INFO"
    format: str = "console"  # "json" | "console"

    @field_validator("format")
    @classmethod
    def _check_format(cls, v: str) -> str:
        if v not in ("json", "console"):
            raise ValueError("logging.format must be 'json' or 'console'")
        return v


class MetricsConfig(BaseModel):
    enabled: bool = True


class TracingConfig(BaseModel):
    enabled: bool = False
    endpoint: str = "localhost:4317"
    sample_rate: float = 1.0
    service_name: str = "vgate-tpu"


class ObservabilityConfig(BaseModel):
    """Engine flight recorder + cross-thread request tracing
    (vgate_tpu/observability/; docs/observability.md).

    Distinct from ``tracing`` (the OTel exporter wiring): this section
    governs what the serving stack *records about itself* — the
    per-tick flight recorder ring, the per-request phase records, and
    whether engine-side phase spans are emitted at all."""

    # Master switch: off = no flight recorder, no engine phase spans,
    # no /debug payloads — the hot path reverts to pre-observability
    # behavior exactly.
    enabled: bool = True
    # Ring sizes (entries kept; oldest evicted).  Ticks are small
    # fixed-shape dicts, requests a bit larger.
    flight_ticks: int = 512
    flight_requests: int = 256
    # Ticks included in the crash snapshot the supervisor logs and
    # /stats surfaces under engine.last_crash.
    crash_dump_ticks: int = 64
    # Never store prompt text in request records; only token counts and
    # the fingerprint.  Set false to keep a short preview for debugging
    # (prompt_preview_chars) — leaks user content into /debug and crash
    # logs, so off only in trusted environments.
    redact_prompts: bool = True
    prompt_preview_chars: int = 48
    # Perf-attribution stratum (observability/perf.py; /debug/perf):
    # per-tick phase decomposition (host/dispatch/device/readback/
    # detok), the compile ledger, and the rolling-window tok/s, MFU and
    # HBM-roofline gauges.  Gated on the master `enabled` switch too;
    # off = no per-tick timing calls beyond the pre-perf engine.
    perf_enabled: bool = True
    # Rolling window the live gauges (vgt_decode_mfu,
    # vgt_host_overhead_ratio, ...) and /stats aggregate over.
    perf_window_s: float = 30.0
    # Tick profiles kept in the attribution ring (oldest evicted).
    perf_ticks: int = 4096
    # Compile-ledger entries kept (one per compiled program variant;
    # steady state is far below this — hitting it IS a recompile storm).
    perf_compile_ledger_max: int = 256


class SecurityConfig(BaseModel):
    """API-key auth (reference: vgate/config.py:101-115)."""

    enabled: bool = False
    api_keys: List[str] = Field(default_factory=list)
    exempt_paths: List[str] = Field(
        default_factory=lambda: [
            "/health", "/health/live", "/health/ready", "/metrics",
        ]
    )


class RateLimitConfig(BaseModel):
    """Sliding-window rate limiting (reference: vgate/config.py:117-126)."""

    enabled: bool = False
    requests_per_minute: int = 60
    per_key_limits: Dict[str, int] = Field(default_factory=dict)


class BenchmarkConfig(BaseModel):
    prompts: List[str] = Field(
        default_factory=lambda: [
            "Explain the benefits of systolic arrays in two sentences.",
            "Write a haiku about high-bandwidth memory.",
            "What is sequence parallelism?",
        ]
    )
    rounds: int = 3
    warmup_rounds: int = 1
    max_tokens: int = 64


class VGTConfig(BaseModel):
    """Root config object."""

    server: ServerConfig = Field(default_factory=ServerConfig)
    model: ModelConfig = Field(default_factory=ModelConfig)
    tpu: TPUConfig = Field(default_factory=TPUConfig)
    kv_cache: KVCacheConfig = Field(default_factory=KVCacheConfig)
    batch: BatchConfig = Field(default_factory=BatchConfig)
    cache: CacheConfig = Field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = Field(default_factory=SchedulerConfig)
    recovery: RecoveryConfig = Field(default_factory=RecoveryConfig)
    lifecycle: LifecycleConfig = Field(default_factory=LifecycleConfig)
    gateway: GatewayConfig = Field(default_factory=GatewayConfig)
    migration: MigrationConfig = Field(default_factory=MigrationConfig)
    pod: PodConfig = Field(default_factory=PodConfig)
    integrity: IntegrityConfig = Field(default_factory=IntegrityConfig)
    admission: AdmissionConfig = Field(default_factory=AdmissionConfig)
    inference: InferenceConfig = Field(default_factory=InferenceConfig)
    logging: LoggingConfig = Field(default_factory=LoggingConfig)
    metrics: MetricsConfig = Field(default_factory=MetricsConfig)
    tracing: TracingConfig = Field(default_factory=TracingConfig)
    observability: ObservabilityConfig = Field(
        default_factory=ObservabilityConfig
    )
    security: SecurityConfig = Field(default_factory=SecurityConfig)
    rate_limit: RateLimitConfig = Field(default_factory=RateLimitConfig)
    benchmark: BenchmarkConfig = Field(default_factory=BenchmarkConfig)


def _deep_merge(base: Dict[str, Any], override: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for key, val in override.items():
        if key in out and isinstance(out[key], dict) and isinstance(val, dict):
            out[key] = _deep_merge(out[key], val)
        else:
            out[key] = val
    return out


def _coerce(raw: str) -> Any:
    """Parse an env-var string: JSON first, then bool words, else string."""
    try:
        return json.loads(raw)
    except (ValueError, TypeError):
        lowered = raw.lower()
        if lowered in ("true", "yes", "on"):
            return True
        if lowered in ("false", "no", "off"):
            return False
        return raw


def _env_overrides(environ: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Collect ``VGT_SECTION__KEY=value`` overrides into a nested dict."""
    environ = environ if environ is not None else os.environ  # type: ignore[assignment]
    result: Dict[str, Any] = {}
    for name, raw in environ.items():
        if not name.startswith(ENV_PREFIX) or name == CONFIG_PATH_ENV:
            continue
        path = name[len(ENV_PREFIX):].lower().split("__")
        if len(path) < 2:
            continue  # VGT_DRY_RUN-style flat flags are read directly
        node = result
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = _coerce(raw)
    return result


def _yaml_values(path: Optional[str]) -> Dict[str, Any]:
    if path is None:
        path = os.environ.get(CONFIG_PATH_ENV)
    if path is None and os.path.exists("config.yaml"):
        path = "config.yaml"
    if path is None or not os.path.exists(path):
        return {}
    with open(path) as fh:
        data = yaml.safe_load(fh) or {}
    if not isinstance(data, dict):
        raise ValueError(f"config file {path} must contain a mapping")
    return data


def load_config(
    config_path: Optional[str] = None, **overrides: Any
) -> VGTConfig:
    """Build a config with priority init > env > yaml > defaults
    (reference semantics: vgate/config.py:174-224)."""
    merged = _deep_merge(_yaml_values(config_path), _env_overrides())
    merged = _deep_merge(merged, overrides)
    return VGTConfig(**merged)


_config_lock = threading.Lock()
_config: Optional[VGTConfig] = None


def get_config() -> VGTConfig:
    """Global config singleton (reference: vgate/config.py:280-304)."""
    global _config
    if _config is None:
        with _config_lock:
            if _config is None:
                _config = load_config()
    return _config


def set_config(config: VGTConfig) -> None:
    global _config
    with _config_lock:
        _config = config


def reset_config() -> None:
    """Drop the singleton so tests can re-load (vgate/config.py:307-315)."""
    global _config
    with _config_lock:
        _config = None
