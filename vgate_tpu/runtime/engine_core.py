"""The TPU engine core: a device-owning continuous-batching loop.

Architecture (SURVEY.md section 7, stages 3-4):

* One **engine thread** owns the device.  Each tick it admits every
  waiting prompt it can (prefills dispatched back-to-back, first tokens
  read in one transfer), then runs decode in **chunks** of up to
  ``tpu.decode_chunk`` fused steps — no stop-the-world batch (the
  reference's design it replaces: vgate/batcher.py:195's global lock
  around blocking generate).
* **A small set of compiled programs** covers all steady-state work: one
  decode-chunk program per power-of-two chunk length at the static shape
  [max_batch_slots], and one prefill program per sequence bucket.
  Sampling runs inside both with per-slot parameters.
* **Latency-hiding pipeline**: up to ``tpu.decode_pipeline`` chunks stay
  in flight before the host blocks on the oldest readback, so host-side
  token processing overlaps device execution.  EOS/length stops are
  detected at readback; overshoot steps are discarded and their KV
  writes land in horizon pages the scheduler reserved (see
  Scheduler.prepare_decode).
* KV pages are donated through every call so XLA updates them in place;
  tokens/positions/rng-counter stay device-resident between chunks and are
  re-uploaded only when slot membership changes.
* The async serving world talks to the thread via a submit queue +
  ``threading.Event`` per sequence; token streaming via per-token callbacks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import queue
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence as Seq

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from vgate_tpu import faults, integrity, metrics
from vgate_tpu.analysis.witness import named_lock
from vgate_tpu.analysis.annotations import (
    engine_thread_only,
    engine_thread_root,
)
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.errors import (
    DeadlineExceededError,
    EngineRecoveringError,
    IntegrityError,
    MigrationError,
    PoisonRequestError,
    ResumeExhaustedError,
)
from vgate_tpu.config import (
    VGTConfig,
    apply_compile_cache,
    apply_platform,
    get_config,
)
from vgate_tpu.logging_config import bound_request, get_logger
from vgate_tpu.models.decoder import (
    WHOLE_BUCKET_IMPLS,
    decode_attention_impl,
    decode_head_impl,
    decode_kv_write,
    multitok_attention_impl,
    packed_group,
    prefill_attention_impl,
    prefill_attn_tiles,
)
from vgate_tpu.models.hybrid import eva_window_pages
from vgate_tpu.models.hybrid import make_state as make_hybrid_state
from vgate_tpu.models.hybrid import prompt_rows
from vgate_tpu.models.hybrid import (
    state_bytes_per_slot as hybrid_state_bytes_per_slot,
)
from vgate_tpu.models.specs import ModelSpec, spec_for_model_id
from vgate_tpu.observability.flight import FlightRecorder
from vgate_tpu.observability.perf import (
    BOOT_SECONDS,
    PerfRecorder,
    note_boot,
    set_capturing,
)
from vgate_tpu.observability.reqtrace import RequestMeta, RequestTrace
from vgate_tpu.observability.roofline import (
    EngineRoofline,
    kv_bytes_per_token,
    stream_weight_bytes,
)
from vgate_tpu.ops.kv_quant import SCALE_BYTES, by_pairs, dtype_short_name
from vgate_tpu.parallel.mesh import build_mesh, initialize_distributed
from vgate_tpu.parallel.sharding import kv_pspec, named, shard_params
from vgate_tpu.runtime.kv_cache import (
    KVGeometry,
    PageAllocator,
    auto_num_pages,
    make_kv_buffers,
)
from vgate_tpu.runtime.kv_swap import KVSwapManager
from vgate_tpu.runtime.radix_cache import RadixCache
from vgate_tpu.runtime.scheduler import PrefillPlan, Scheduler, SwapInPlan
from vgate_tpu.runtime.sequence import Sequence, SeqStatus
from vgate_tpu.runtime.step_programs import (
    _cow_copy_pages,
    _decode_chunk,
    _gather_swap_pages,
    _join_decode_rows,
    _prefill_step,
    _scatter_swap_pages,
    _spec_verify_step,
    _state_kw,
    _suffix_prefill_step,
)
from vgate_tpu.runtime.tokenizer import get_tokenizer
from vgate_tpu.runtime.weights import load_or_init_params
from vgate_tpu.utils.math import bucket_for, cdiv

logger = get_logger(__name__)

# Threading contract (enforced by scripts/vgt_lint.py, checker
# thread-discipline — see docs/static_analysis.md): cross-module call
# resolution for self.scheduler.*, and the fields only ever mutated
# under their paired lock.
VGT_COMPONENTS = {"scheduler": "Scheduler"}
# Epoch-guard contract (vgtlint epoch-guard checker): token-append
# readbacks publish sequence state a cross-thread containment fold may
# have invalidated while the device call blocked.  Every append must
# run under the readback lock AND be dominated by a staleness
# comparison on the sequence's preempt epoch — the PR-5/8/11 bug
# shape, previously re-verified by hand each PR.
VGT_EPOCH_GUARDS = {
    "append_token": {"lock": "_readback_lock", "epoch": "preempt_count"},
}
VGT_LOCK_GUARDS = {
    # the containment fold vs. token-append readbacks publication
    # guard (PR-5 hardening): a woken stalled thread must observe
    # either pre-fold or fully-folded state, never a fold in progress
    "_checkpointed": "_readback_lock",
    # first-entry-only containment arbitration
    "_fatal": "_contain_lock",
}

# top-alternatives returned per position when a request asks for
# logprobs (requests may ask for fewer; the schema clamps to this)
LOGPROBS_K = 8

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}

# pages moved per device call when swapping KV to/from host RAM
# (runtime/kv_swap.py): fixed so each direction compiles exactly one
# program per pool dtype — short runs pad their index vector with the
# reserved trash page 0, which absorbs the padding writes on swap-in
# and whose padding rows are dropped host-side on swap-out
SWAP_CHUNK_PAGES = 16


class _DeviceSwapExecutor:
    """The device half of the host swap tier (runtime/kv_swap.py): the
    manager stays pure host-side policy, and every device touch —
    chunked ``jax.device_get`` of page slices on swap-out, the jitted
    scatter on swap-in — happens here, on the engine thread, at tick
    boundaries.  Reads heartbeat like every other blocking readback so
    the hang watchdog attributes a wedged transfer correctly."""

    def __init__(self, core: "EngineCore") -> None:
        self._core = core

    def read_pages(self, pages: List[int]):
        core = self._core
        k_chunks: list = []
        v_chunks: list = []
        for i in range(0, len(pages), SWAP_CHUNK_PAGES):
            chunk = pages[i : i + SWAP_CHUNK_PAGES]
            idx = np.zeros((SWAP_CHUNK_PAGES,), np.int32)
            idx[: len(chunk)] = chunk
            core._beat("swap_readback", batch=len(chunk))
            k_c, v_c = _gather_swap_pages(
                core.k_pages, core.v_pages, jnp.asarray(idx)
            )
            host = jax.device_get((k_c, v_c))
            trim = lambda x: np.asarray(x)[:, :, : len(chunk)]
            k_chunks.append(jax.tree.map(trim, host[0]))
            v_chunks.append(jax.tree.map(trim, host[1]))
        cat = lambda *xs: np.concatenate(xs, axis=2)
        return (
            jax.tree.map(cat, *k_chunks),
            jax.tree.map(cat, *v_chunks),
        )

    def write_pages(self, pages: List[int], payload) -> None:
        core = self._core
        k_data, v_data = payload
        for i in range(0, len(pages), SWAP_CHUNK_PAGES):
            chunk = pages[i : i + SWAP_CHUNK_PAGES]
            idx = np.zeros((SWAP_CHUNK_PAGES,), np.int32)
            idx[: len(chunk)] = chunk

            def pad(x):
                sl = x[:, :, i : i + len(chunk)]
                if len(chunk) < SWAP_CHUNK_PAGES:
                    shape = list(sl.shape)
                    shape[2] = SWAP_CHUNK_PAGES
                    out = np.zeros(shape, sl.dtype)
                    out[:, :, : len(chunk)] = sl
                    return out
                return sl

            core.k_pages, core.v_pages = _scatter_swap_pages(
                core.k_pages,
                core.v_pages,
                jnp.asarray(idx),
                jax.tree.map(pad, k_data),
                jax.tree.map(pad, v_data),
            )


def rebuild_core(
    old: "EngineCore",
    config: VGTConfig,
    devices: Optional[list],
    reload_weights: bool = False,
) -> "EngineCore":
    """Tear a dead core down and construct its successor — the ONE
    rebuild sequence both the dp=1 supervisor and the dp repair thread
    use (a per-core device buffer freed in one copy but not the other
    would keep the dead incarnation's pool alive and OOM every rebuild
    on real hardware).  Stops the old core, releases its device KV pool
    and decode state BEFORE the new pool is sized (auto-sized pools
    fill most of HBM; the old core stays referenced by its owner until
    the swap, pinning anything still shared), rebuilds with weights
    KEPT (the old tree is already quantized/sharded on these devices),
    and carries the brownout spec-suspension flag so a crash at level
    >= 3 cannot silently re-enable speculative decoding.  The caller
    swaps it in, re-attaches on_fatal, and start()s it.

    Silent-corruption defense (vgate_tpu/integrity.py): a kept tree is
    ALWAYS re-verified against its checksum baseline first — restarting
    on a bit-flipped tree would preserve the corruption through every
    incarnation — and a mismatch raises :class:`IntegrityError` so the
    caller escalates to ``reload_weights=True``, which drops the old
    tree and reloads from the checkpoint (the ``corrupt``-classified
    fatal path)."""
    old.stop()
    old.k_pages = None
    old.v_pages = None
    old.state = None
    old._dec_state = None
    old._pending_chunks.clear()
    old._spec_rows = None
    # the host swap pool dies with its core: every parked ticket's
    # epoch went stale when containment folded the owners, and the new
    # core builds a fresh (empty) pool — free the host RAM now rather
    # than holding both pools across the rebuild
    old.kv_swap = None
    old_integrity = getattr(old, "integrity", None)
    if (
        not reload_weights
        and old_integrity is not None
        and old_integrity.verifier is not None
        and old.params is not None
    ):
        mismatch = old_integrity.verifier.verify_all(old.params)
        if mismatch is not None:
            metrics.INTEGRITY_EVENTS.labels(
                kind="rebuild_verify_failed"
            ).inc()
            raise IntegrityError(
                "kept-weights rebuild verification failed: shard "
                f"{mismatch['leaf']!r} no longer matches its load-time "
                "checksum; escalate to a weight reload",
                kind="checksum_mismatch",
                detail=mismatch,
            )
    if reload_weights:
        # free the suspect tree BEFORE the reload materializes a fresh
        # one — two full trees would OOM the chip
        old.params = None
        metrics.CORRUPT_RELOADS.inc()
        metrics.INTEGRITY_EVENTS.labels(kind="corrupt_reload").inc()
        logger.warning(
            "rebuilding engine with a FULL WEIGHT RELOAD "
            "(corrupt-classified fatal; weights-kept would preserve "
            "the corruption)"
        )
        new_core = EngineCore(config, spec=old.spec, devices=devices)
    else:
        new_core = EngineCore(
            config,
            spec=old.spec,
            params=old.params,
            devices=devices,
            params_ready=True,
        )
    new_core.spec_suspended = bool(
        getattr(old, "spec_suspended", False)
    )
    # same carry for brownout L4: a crash while cache writes were
    # bypassed must not silently resume prefix-tree inserts (the method
    # also propagates the flag onto the fresh core's radix cache)
    new_core.set_prefix_insert_suspended(
        getattr(old, "prefix_insert_suspended", False)
    )
    return new_core


def replay_into(
    core: "EngineCore",
    seq: Sequence,
    quarantine: set,
    retry_after: float = 1.0,
    kind: str = "resume",
    **tick_fields: Any,
) -> str:
    """Replay ONE checkpointed sequence into ``core`` — the shared
    per-sequence pipeline behind the supervisor's restart replay, the
    dp router's failover redistribution AND planned live migration
    (one definition so lost/resumed accounting can never drift between
    dp=1 and dp>1): quarantined fingerprints fail with the 400 poison
    error, a refused resubmission fails with the retryable 503,
    success records the ``kind`` flight tick ("resume" for crash
    replay, "migrate" for planned movement) and bumps
    vgt_resumed_sequences (resume only — migrations have their own
    vgt_migrations counter, labeled by reason, owned by the caller).
    Returns "replayed" | "quarantined" | "failed"; callers fold the
    outcome into their own counters."""
    fp = faults.fingerprint(seq.prompt_ids[: seq.orig_prompt_len])
    if fp in quarantine:
        metrics.LOST_SEQUENCES.labels(reason="quarantined").inc()
        seq.fail(
            PoisonRequestError(
                f"request {fp} was quarantined while its generation "
                "was checkpointed and will not be replayed"
            )
        )
        return "quarantined"
    try:
        core.submit_existing(seq)
    except Exception:
        logger.error("resume resubmission failed", exc_info=True)
        metrics.LOST_SEQUENCES.labels(reason="resubmit_failed").inc()
        seq.fail(
            EngineRecoveringError(
                "engine restarted but the checkpointed request could "
                "not be replayed; retry shortly",
                retry_after=retry_after,
            )
        )
        return "failed"
    if kind == "resume":
        metrics.RESUMED_SEQUENCES.inc()
    core.flight.record_tick(
        kind,
        seq_id=seq.seq_id,
        request_id=seq.request_id,
        tokens=seq.num_generated,
        attempt=seq.resume_count if kind == "resume" else seq.migrate_count,
        **tick_fields,
    )
    return "replayed"


def _plain_mesh(mesh) -> bool:
    return all(int(mesh.shape.get(a, 1)) == 1
               for a in ("tp", "pp", "sp", "ep"))


def refuse_unbuildable_kernels(spec: ModelSpec, kv_quant: bool,
                               plain_mesh: bool = True) -> None:
    """Engine-construction gate for the paged kernels Mosaic refuses on
    the v5e toolchain (jax 0.9.0 / libtpu 0.0.34; tests/test_tpu_aot.py
    holds each case as a strict xfail, so the day one compiles the suite
    says so).  Raised at boot with the compiler's own message: left to
    the first request, the failure would surface inside a supervised
    restart loop.  ``tpu.use_pallas: false`` serves either combination
    through the jnp twins.  A head of 64 is NOT refused where KV heads
    pair on a ``plain_mesh``: the pool then holds two heads a 128-lane
    row (ops/head_pack.py; ``ModelSpec.pack_kv_heads``, which the
    engine has applied by then)."""
    if kv_quant:
        raise ValueError(
            "kv_cache.dtype=int8 cannot run the Pallas paged-attention "
            "kernels on this TPU toolchain — Mosaic refuses the per-page "
            "scale-row DMA: 'Slice shape along dimension 2 must be "
            "aligned to tiling (8), but is 1'.  Use kv_cache.dtype=bf16, "
            "or tpu.use_pallas=false (jnp twins)."
        )
    # what a page's row holds: head_dim (two heads of 64 where they
    # pair), or the latent row's lanes
    if plain_mesh:
        spec = spec.pack_kv_heads()
    if spec.cache_head_dim % 128:
        raise ValueError(
            f"{spec.name} (head_dim {spec.cache_head_dim}) cannot run the "
            "Pallas paged-attention kernels on this TPU toolchain — "
            "Mosaic refuses the page DMA: 'Slice shape along dimension 4 "
            f"must be aligned to tiling (128), but is {spec.head_dim}'.  "
            "Heads of 64 are served two to a 128-lane row where the KV "
            f"heads pair (an even number of them: {spec.num_kv_heads} "
            "here) on an unpartitioned mesh.  Set tpu.use_pallas=false "
            "(jnp twins)."
        )


def _pages_only_feature(config: VGTConfig, mesh):
    """The first configured feature that knows K and V pages only (a
    partitioned mesh, speculative decoding, the host swap tier,
    disaggregated roles, int8 pages, quantized weights) as ``(what it
    is called, its key)``; None when none is on."""
    bad_axes = {
        a: int(mesh.shape.get(a, 1)) for a in ("tp", "pp", "sp", "ep")
        if int(mesh.shape.get(a, 1)) > 1
    }
    if bad_axes:
        return f"a {bad_axes} mesh", "mesh"
    if config.tpu.speculative_k > 0:
        return "speculative decoding (tpu.speculative_k)", "speculative"
    if int(config.kv_cache.host_swap_bytes) > 0:
        return "the host swap tier (kv_cache.host_swap_bytes)", "swap"
    if any(r in ("prefill", "decode") for r in config.pod.roles):
        return "disaggregated prefill/decode roles (pod.roles)", "roles"
    if config.kv_cache.dtype == "int8":
        return "kv_cache.dtype=int8", "int8"
    if config.model.quantization not in (None, "", "none"):
        return f"model.quantization={config.model.quantization}", "quant"
    return None


def refuse_unsupported_recurrent(spec: ModelSpec, config: VGTConfig,
                                 mesh) -> None:
    """Engine-construction gate for a spec with recurrent layers
    (models/hybrid.py): everything that moves, shares or rolls back a
    sequence's cache knows pages only, and a page without the recurrent
    state that belongs to it is a WRONG cache.  Each is refused here by
    name, at boot, not at the first request that would need it.  (Prefix
    matching is not refused but turned off: it is on by default.)"""
    found = (_pages_only_feature(config, mesh)
             if spec.recurrent_layers else None)
    if not found:
        return
    why = {
        "mesh": "the recurrent state and its kernel are not partitioned "
                "(dp composes: a replica owns its state)",
        "speculative": "rejected drafts would have to be rolled back out "
                       "of the state",
        "swap": "it parks pages and would leave the state behind",
        "roles": "the handoff of a live sequence moves pages and would "
                 "leave the state behind",
        "int8": "the gated attention path writes bf16",
        "quant": "the grouped expert product and the recurrent layers "
                 "take plain weights",
    }[found[1]]
    has = ("gated short-convolution layers (a convolution tail a slot "
           "beside the pages)" if spec.conv_layers
           else "recurrent (linear-attention or state-space) layers")
    raise ValueError(
        f"{spec.name} has {has}, which cannot run with {found[0]}: {why}.  "
        "Preemption by recompute and journal replay rebuild the state and "
        "are supported."
    )


def refuse_unsupported_latent(spec: ModelSpec, config: VGTConfig,
                              mesh) -> None:
    """Engine-construction gate for a spec with latent attention
    (``ModelSpec.is_mla``): its cache is ONE pool of latent rows, and
    what is listed here still assumes K and V pools, or has not been
    tried over a latent one.  Each is refused by name, at boot.  (Prefix
    sharing over latent pages works: whole pages only, the radix
    cache's copy-on-write of a partial page is turned off.)"""
    found = _pages_only_feature(config, mesh) if spec.rows_cache else None
    if not found:
        return
    if spec.kv_rows:
        why = {
            "mesh": "its pool holds all KV heads in one row a token and "
                    "its kernels are not partitioned (dp composes: a "
                    "replica owns its pool)",
            "speculative": "the verify program attends head-major K and V "
                           "pools, and under no selection",
            "swap": "its gather and scatter programs move head-major K "
                    "and V pools and would leave the index keys behind",
            "roles": "the handoff of a live sequence ships head-major K "
                     "and V pages and would leave the index keys behind",
            "int8": "K over V and the index keys are written and read in "
                    "the model's float type only",
            "quant": "the indexer and the grouped expert product take "
                     "plain weights",
        }[found[1]]
        raise ValueError(
            f"{spec.name} has GQA attention under a learned selection (a "
            "pool of K over V a token and one of index keys under one "
            f"page table), which cannot run with {found[0]}: {why}.  "
            "Prefix sharing of whole pages, chunked prefill, preemption "
            "by recompute and journal replay are supported."
        )
    why = {
        "mesh": "the latent pool has one row a token for all heads and "
                "its kernels are not partitioned (dp composes: a replica "
                "owns its pool)",
        "speculative": "the verify program attends K and V pools",
        "swap": "its gather and scatter programs move K and V pools",
        "roles": "the handoff of a live sequence ships K and V pages",
        "int8": "latent rows are written and read in the model's float "
                "type only",
        "quant": "the latent projections and the grouped expert product "
                 "take plain weights",
    }[found[1]]
    rows = ("a pool of latent rows and one of index keys under one page "
            "table" if spec.is_dsa else "one pool of latent rows")
    raise ValueError(
        f"{spec.name} has latent attention ({rows}, no K "
        f"or V pool), which cannot run with {found[0]}: {why}.  Prefix "
        "sharing of whole pages, chunked prefill, preemption by recompute "
        "and journal replay are supported."
    )


def refuse_unsupported_rings(spec: ModelSpec, config: VGTConfig,
                             mesh) -> None:
    """Engine-construction gate for a spec whose window layers keep a
    per-slot RING beside the pool (``ModelSpec.swa_layers``,
    models/hybrid.py): a sequence's pages are then the full layers' K/V
    alone, and whatever moves, shares or rolls back pages would leave
    the rings behind.  Each is refused by name, at boot.  (Prefix
    matching is not refused but turned off: it is on by default.)"""
    found = _pages_only_feature(config, mesh) if spec.swa_layers else None
    if not found:
        return
    why = {
        "mesh": "the rings and their kernel launches are not partitioned "
                "(dp composes: a replica owns its rings)",
        "speculative": "the verify program attends K and V pools, and "
                       "rejected drafts would have to be rolled back out "
                       "of a ring",
        "swap": "it parks pages and would leave the rings behind",
        "roles": "the handoff of a live sequence ships pages and would "
                 "leave the rings behind",
        "int8": "the rings and the window layers' prompt pass hold the "
                "model's float type only",
        "quant": "the window layers, the dense layer and the grouped "
                 "expert product take plain weights",
    }[found[1]]
    raise ValueError(
        f"{spec.name} has window layers whose K/V is a per-slot ring "
        f"(no page a token), which cannot run with {found[0]}: {why}.  "
        "Chunked prefill, preemption by recompute and journal replay "
        "rebuild the rings and are supported."
    )


def refuse_unsupported_eva(spec: ModelSpec, config: VGTConfig,
                           mesh) -> None:
    """Engine-construction gate for a spec of EVA attention layers
    (``ModelSpec.eva_layers``, ops/eva.py): a sequence's pages hold ONE
    summary row for every ``eva_chunk`` tokens of its closed windows,
    the open window's exact rows are the decode slot's, and whatever
    moves, shares or rolls back pages would leave the window behind.
    Each is refused by name, at boot; so is a page size that does not
    cut a window's rows and its summary rows into whole pages.  (Prefix
    matching is not refused but turned off: it is on by default.)"""
    if not spec.eva_layers:
        return
    ps, W, c = config.tpu.kv_page_size, spec.eva_window, spec.eva_chunk
    if ps % c or (W // c) % ps:
        raise ValueError(
            f"{spec.name} holds windows of {W} tokens in chunks of {c}: "
            f"tpu.kv_page_size={ps} has to be a multiple of {c} (a chunk "
            f"of a chunked prefill starts at a page of tokens) and divide "
            f"{W // c} (a closed window's summary rows are whole pages)"
        )
    found = _pages_only_feature(config, mesh)
    if not found:
        return
    why = {
        "mesh": "the windows, the summary rows and their kernel launches "
                "are not partitioned (dp composes: a replica owns its "
                "pool)",
        "speculative": "the verify program attends K and V pools a token "
                       "a row, and rejected drafts would have to be "
                       "rolled back out of a window (the model's own "
                       "extra heads as drafts: not yet)",
        "swap": "it parks pages and would leave the open window behind",
        "roles": "the handoff of a live sequence ships pages and would "
                 "leave the open window behind",
        "int8": "the windows and the summary rows hold the model's "
                "float type only",
        "quant": "the EVA layers and their feed-forward take plain "
                 "weights",
    }[found[1]]
    raise ValueError(
        f"{spec.name} has EVA attention layers (a window of exact rows a "
        f"decode slot, a summary row for every {c} tokens in the pages), "
        f"which cannot run with {found[0]}: {why}.  Chunked prefill, "
        "preemption by recompute and journal replay rebuild the window "
        "and are supported."
    )


class _EvacRequest:
    """One planned-evacuation command in flight between a caller thread
    (dp drain/rebalance coordinator, admin surface) and the engine
    thread: the engine fills ``result`` (the checkpointed live
    sequences) or ``error`` and sets ``event``.  ``lock`` arbitrates
    the timeout race — a caller that gives up sets ``cancelled`` under
    it, and the engine checks it both before starting and before
    publishing, so a stale command can never strand ownerless
    sequences: not-yet-started work is skipped, just-finished work is
    folded straight back into the source scheduler."""

    __slots__ = (
        "seq_ids", "reason", "event", "result", "error",
        "lock", "cancelled",
    )

    def __init__(
        self, seq_ids: Optional[List[int]], reason: str
    ) -> None:
        self.seq_ids = seq_ids
        self.reason = reason
        self.event = threading.Event()
        self.result: Optional[List[Sequence]] = None
        self.error: Optional[BaseException] = None
        self.lock = threading.Lock()
        self.cancelled = False


@dataclasses.dataclass
class _PrefillWave:
    """One tick's admissions between their dispatch and the readback of
    their first tokens: ``dispatched`` pairs each prompt program's plans
    with its (async) result, whose token half the decode state's row
    edit reads on the device before the host reads it."""

    start: float
    plans: List[PrefillPlan] = dataclasses.field(default_factory=list)
    plan_epochs: Dict[int, int] = dataclasses.field(default_factory=dict)
    dispatched: list = dataclasses.field(default_factory=list)
    # how a row of the wave came in other than by one prompt program: a
    # membership change with either rebuilds the decode state
    chunked: bool = False
    swapped: bool = False


class EngineCore:
    """Owns params, KV pages, the mesh and the engine thread."""

    def __init__(
        self,
        config: Optional[VGTConfig] = None,
        spec: Optional[ModelSpec] = None,
        params: Optional[Any] = None,
        devices: Optional[list] = None,
        params_ready: bool = False,
    ) -> None:
        self.config = config or get_config()
        self.spec = spec or spec_for_model_id(self.config.model.model_id)
        tpu_cfg = self.config.tpu
        apply_platform(tpu_cfg)
        apply_compile_cache()
        # multi-host pods: join the process group before any device touch
        # (no-op on single hosts / CPU test meshes; VERDICT r1 missing-5)
        initialize_distributed()
        self.dtype = _DTYPES[self.config.model.dtype]
        self.mesh = build_mesh(tpu_cfg, devices)
        self.spec.check_expert_share()
        # K and V heads of 64: two a 128-lane row of the pool, whichever
        # attention reads it (ops/head_pack.py).  A partitioned mesh
        # splits the pool by KV head and int8 pages scale by head: both
        # keep a head a row
        if (_plain_mesh(self.mesh)
                and self.config.kv_cache.dtype != "int8"):
            self.spec = self.spec.pack_kv_heads()
        refuse_unsupported_recurrent(self.spec, self.config, self.mesh)
        refuse_unsupported_latent(self.spec, self.config, self.mesh)
        refuse_unsupported_rings(self.spec, self.config, self.mesh)
        refuse_unsupported_eva(self.spec, self.config, self.mesh)
        # Pallas kernels require a real TPU backend (tests run interpret-
        # mode kernels separately; the engine's jnp twins serve CPU meshes)
        platform = self.mesh.devices.flat[0].platform
        self.use_pallas = bool(tpu_cfg.use_pallas and platform == "tpu")
        if self.use_pallas:
            refuse_unbuildable_kernels(
                self.spec, self.config.kv_cache.dtype == "int8",
                _plain_mesh(self.mesh),
            )
        elif tpu_cfg.use_pallas:
            # the gate stays (Tier-1 builds engines on CPU with the
            # default) but is loud: a machine whose chip failed to
            # initialise must not serve the jnp twins from the CPU
            # without saying so
            logger.warning(
                "tpu.use_pallas is on but the engine's device is not a "
                "TPU: attention runs the jnp twins; set tpu.platform=tpu "
                "to fail at start instead",
                extra={"extra_data": {"platform": platform}},
            )
        # attention implementation each compiled step program traced
        # (models/decoder.py *_attention_impl), for /stats
        self._attention: Dict[str, set] = {}
        # model-level stop set: the tokenizer's eos plus the spec's extra
        # generation_config stops (e.g. Llama-3.1's end_of_text/eom)
        self._stop_ids = frozenset(self.spec.extra_stop_ids)
        self.tokenizer = get_tokenizer(
            self.spec,
            self.config.model.tokenizer_path
            or self.config.model.checkpoint_path,
        )

        load_start = time.perf_counter()
        quant = self.config.model.quantization
        quant_bits = int(quant[3:]) if quant in ("int8", "int4") else None
        # Single-device quantized loads stage on the HOST: a 7B-class
        # model's bf16 tree (~15 GB) would OOM a 16 GB chip before
        # quantization could ever run, so init/load and quantize on the
        # CPU backend and place only the narrow-int tree (the same shape
        # a real AWQ-style pre-quantized load has).  Multi-device meshes
        # keep the place-then-quantize order so the eager quantize ops
        # run SPMD and scales inherit the tp layout.
        host_stage = None
        if params_ready:
            # supervised restart (runtime/supervisor.py): `params` is the
            # previous incarnation's tree, already quantized/sharded on
            # these same devices — re-quantizing or re-sharding it would
            # corrupt it, so place it verbatim and skip the load path
            assert params is not None, "params_ready requires params"
        elif quant_bits and self.mesh.devices.size == 1:
            try:
                host_stage = jax.devices("cpu")[0]
            except RuntimeError as exc:
                # apply_platform keeps cpu registered behind a pinned
                # platform; only an environment that names accelerators
                # alone (JAX_PLATFORMS=tpu) lands here.  Quantizing on
                # the chip instead would put a 7B-class bf16 tree next
                # to its own quantized copy — an OOM at load, so refuse
                raise RuntimeError(
                    "host-staged quantized load needs the cpu backend: "
                    "set tpu.platform (apply_platform then keeps cpu "
                    "registered) or add cpu to JAX_PLATFORMS"
                ) from exc
        if params_ready:
            self.params = params
        elif host_stage is not None:
            from vgate_tpu.ops.quant import quantize_decoder_params

            with jax.default_device(host_stage):
                if params is None:
                    params = load_or_init_params(
                        self.spec,
                        self.config.model.checkpoint_path,
                        self.dtype,
                        log_digests=self.config.integrity.enabled,
                    )
                params = quantize_decoder_params(
                    params, self.spec, bits=quant_bits
                )
            self.params = jax.device_put(
                params, self.mesh.devices.flat[0]
            )
        else:
            if params is None:
                params = load_or_init_params(
                    self.spec, self.config.model.checkpoint_path, self.dtype,
                    log_digests=self.config.integrity.enabled,
                    mesh=self.mesh,
                )
            self.params = shard_params(params, self.spec, self.mesh)
            if quant_bits:
                from vgate_tpu.ops.quant import quantize_decoder_params

                self.params = quantize_decoder_params(
                    self.params, self.spec, bits=quant_bits
                )
        jax.block_until_ready(jax.tree.leaves(self.params)[0])
        self.load_time_s = time.perf_counter() - load_start
        # boot phases for /debug/perf totals.boot_seconds (the digest
        # pass inside load_or_init_params notes its own share first)
        note_boot("weights", self.load_time_s - BOOT_SECONDS.get(
            "digest", 0.0
        ))
        # silent-corruption defense (vgate_tpu/integrity.py): sentinel
        # scanner + weight-checksum baseline over the FINAL serving tree
        # (post-quantize/shard — the tree supervised rebuilds keep).
        # None when disabled, keeping every probe site a single
        # attribute check and the decode program byte-identical.
        icfg = self.config.integrity
        self.integrity: Optional[integrity.EngineIntegrity] = None
        if icfg.enabled:
            self.integrity = integrity.EngineIntegrity(
                icfg, self.spec.vocab_size
            )
            self.integrity.record_baseline(self.params)

        params_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.params)
        )
        self._params_bytes = int(params_bytes)
        # KV storage format (kv_cache.dtype — ops/kv_quant.py): int8
        # halves page data bytes (plus a bf16 scale per page/head/slot),
        # so the same HBM budget below yields ~2x the bf16 page count —
        # resident-batch capacity is what governs tail latency under
        # load (PAPERS.md vLLM/TGI study), and decode HBM traffic per
        # token halves with it.  Quantization happens at every KV write
        # site (models/decoder.py kv_write) and dequantization inside
        # the attention reads (Pallas VMEM loop / jnp gather twins).
        kv_mode = self.config.kv_cache.dtype
        self._kv_quant = kv_mode == "int8"
        if self._kv_quant:
            model_axes = {
                a: int(self.mesh.shape.get(a, 1))
                for a in ("tp", "pp", "sp", "ep")
            }
            bad = {a: n for a, n in model_axes.items() if n > 1}
            if bad:
                raise ValueError(
                    f"kv_cache.dtype=int8 requires a plain mesh, got "
                    f"{bad}: the quantized pool is a (data, scale) pair "
                    "the sp/pp relays and tp shard_map kernels do not "
                    "thread — dp composes (each replica owns its pool)"
                )
            kv_pool_dtype = jnp.int8
        elif kv_mode == "bf16":
            kv_pool_dtype = jnp.bfloat16
        else:  # auto: pages store the model compute dtype
            kv_pool_dtype = self.dtype
        kv_dtype_name = (
            "int8" if self._kv_quant else dtype_short_name(kv_pool_dtype)
        )
        kv_dtype_bytes = 1 if self._kv_quant else jnp.dtype(
            kv_pool_dtype
        ).itemsize
        kv_scale_bytes = SCALE_BYTES if self._kv_quant else 0
        # more pages than every slot's full context can never be used, and
        # bounding the pool keeps the page-scatter/gather programs small
        pages_per_seq = cdiv(
            self.config.model.max_model_len,
            tpu_cfg.kv_page_size * self.spec.cache_row_tokens,
        )
        sp_shards = int(self.mesh.shape.get("sp", 1))
        max_useful = (
            tpu_cfg.max_batch_slots * pages_per_seq + sp_shards
        )
        # what a hybrid spec keeps a decode slot beside the pool (the
        # recurrent state's row, the window layers' rings), sized
        # before the pool so that the pool gets what is left
        self._state_dtype = self.dtype
        self._state_slot_bytes = (
            hybrid_state_bytes_per_slot(
                self.spec, jnp.dtype(self.dtype).itemsize,
                tpu_cfg.kv_page_size)
            if self.spec.slot_state_layers else 0
        )
        state_bytes = self._state_slot_bytes * tpu_cfg.max_batch_slots
        if tpu_cfg.kv_num_pages:
            num_pages, sized_by = tpu_cfg.kv_num_pages, "config"
        else:
            device0 = self.mesh.devices.flat[0]
            # layers over pp and kv heads over tp split every page
            # (kv_pspec): a chip stores 1/shards of it
            kv_shards = 1
            for axis in kv_pspec(self.spec, self.mesh):
                if axis is not None:
                    kv_shards *= int(self.mesh.shape[axis])
            fits = auto_num_pages(
                self.spec,
                tpu_cfg.kv_page_size,
                tpu_cfg.hbm_utilization,
                device=device0,
                params_bytes=params_bytes,
                dtype_bytes=kv_dtype_bytes,
                hbm_bytes=tpu_cfg.hbm_bytes,
                scale_bytes=kv_scale_bytes,
                shards=kv_shards,
                reserved_bytes=state_bytes,
            )
            num_pages = min(max_useful, fits)
            # what set the pool size, for /stats: the chip's memory, the
            # slots x context cap, or the CPU test default
            if device0.platform == "cpu":
                sized_by = "cpu_default"
            elif fits < max_useful:
                sized_by = "device_memory"
            else:
                sized_by = "slots_x_context"
        self._kv_sized_by = sized_by
        if sp_shards > 1:
            # the pool shards contiguously over sp (parallel/sp_decode.py);
            # round UP so the computed capacity is preserved (at most
            # sp-1 extra pages, noise next to the pool)
            num_pages = num_pages + (-num_pages) % sp_shards
        self.geometry = KVGeometry(
            num_layers=self.spec.attn_layers,
            num_pages=num_pages,
            page_size=tpu_cfg.kv_page_size,
            kv_heads=self.spec.cache_heads,
            head_dim=self.spec.cache_head_dim,
            max_model_len=self.config.model.max_model_len,
            dtype_bytes=kv_dtype_bytes,
            num_reserved=sp_shards,
            scale_bytes=kv_scale_bytes,
            kv_dtype=kv_dtype_name,
            pools=self.spec.kv_pools,
            index_layers=self.spec.index_layers,
            index_dim=self.spec.index_key_lanes,
            row_tokens=self.spec.cache_row_tokens,
            # an EVA spec's open windows: pages of the pool arrays a
            # slot, behind the allocator's (their bytes are the
            # ``state_bytes`` taken out above)
            slot_pages=(
                tpu_cfg.max_batch_slots
                * eva_window_pages(self.spec, tpu_cfg.kv_page_size)
                if self.spec.eva_layers else 0
            ),
        )
        kv_sharding = named(
            self.mesh, kv_pspec(self.spec, self.mesh, num_pages)
        )
        self.k_pages, self.v_pages = make_kv_buffers(
            self.geometry, kv_pool_dtype, kv_sharding
        )
        # None for every spec that keeps nothing a slot: an empty
        # pytree in the step programs, which then are what they were
        # (as v_pages is for a latent pool)
        self.state = (
            make_hybrid_state(
                self.spec, tpu_cfg.max_batch_slots, self._state_dtype,
                tpu_cfg.kv_page_size, num_pages)
            if self.spec.slot_state_layers else None
        )
        # tokens a slot's ring holds in one window layer (0: no rings)
        self._ring_tokens = (
            (self.state["ring_k"].shape[2] - 1) // tpu_cfg.max_batch_slots
            * tpu_cfg.kv_page_size
            if self.spec.swa_layers else 0
        )
        self.allocator = PageAllocator(num_pages, num_shards=sp_shards)
        self.allocator.quantized = self._kv_quant
        for name in ("bf16", "f32", "f16", "int8"):
            metrics.KV_DTYPE.labels(dtype=name).set(
                1 if name == kv_dtype_name else 0
            )
        self.max_slots = tpu_cfg.max_batch_slots
        # prefix caching rides the suffix prefill program, which runs on
        # plain meshes AND sp-sharded pools (parallel/sp_decode.py
        # sp_suffix_attention_and_write — long-context serving is
        # exactly where shared-prefix reuse pays); only the pp relay
        # still reshapes the prompt pass incompatibly
        mesh_sp = int(self.mesh.shape.get("sp", 1))
        mesh_pp = int(self.mesh.shape.get("pp", 1))
        pc = tpu_cfg.prefix_cache
        # a prefix hit without the recurrent state, or the rings, that
        # belong to it would be wrong: matching is off for such a spec
        self.prefix_cache_enabled = bool(
            pc.enabled and mesh_pp == 1 and not self.spec.slot_state_layers
        )
        # radix-tree prefix index (runtime/radix_cache.py): page-granular
        # cross-request sharing with COW partial pages and
        # pressure-integrated eviction; the tree registers itself as the
        # allocator's reclaimer so cached pages stay allocatable.  COW
        # needs the unsharded pool (the copy program indexes pages
        # globally), so it gates off under sp > 1 while full-page radix
        # sharing stays on.
        self.radix_cache = None
        if self.prefix_cache_enabled and pc.radix:
            self.radix_cache = RadixCache(
                self.allocator,
                tpu_cfg.kv_page_size,
                min_share_pages=pc.min_share_pages,
                # latent pages are shared whole: the suffix program of
                # such a spec writes whole pages (models/hybrid.py)
                cow=bool(pc.cow and mesh_sp == 1
                         and not self.spec.rows_cache),
                cow_min_tokens=pc.cow_min_tokens,
            )
            self.allocator.set_reclaimer(self.radix_cache)
        # host-RAM KV swap tier (runtime/kv_swap.py): a budgeted pinned
        # host pool under the paged allocator — preemption parks the
        # victim's pages device->host instead of recomputing, and
        # radix eviction demotes warm prefixes into it (victim cache).
        # 0 = off keeps the engine byte-identical; the device half
        # (chunked gather/scatter) lives in _DeviceSwapExecutor and the
        # readback lock shared below epoch-guards swap-out publication
        # exactly like every other readback.
        self.kv_swap: Optional[KVSwapManager] = None
        host_swap_bytes = int(self.config.kv_cache.host_swap_bytes)
        if host_swap_bytes > 0:
            swap_axes = {
                a: int(self.mesh.shape.get(a, 1))
                for a in ("tp", "pp", "sp", "ep")
            }
            bad_axes = {a: n for a, n in swap_axes.items() if n > 1}
            if bad_axes:
                raise ValueError(
                    f"kv_cache.host_swap_bytes requires a plain mesh, "
                    f"got {bad_axes}: the swap gather/scatter indexes "
                    "pages globally across an unsharded pool — dp "
                    "composes (each replica owns its pool + host tier)"
                )
        # brownout L4 upstream state, carried across supervisor rebuilds
        # exactly like spec_suspended
        self.prefix_insert_suspended = False
        if tpu_cfg.prefill_chunk > 0 and mesh_pp > 1:
            raise ValueError(
                "prefill_chunk (chunked prefill) requires pp == 1 — the "
                "relay prompt pass reshapes the program incompatibly "
                "(sp is fine: chunks ride the sp-capable suffix program)"
            )
        # flight recorder (vgate_tpu/observability/flight.py): per-tick
        # + per-request post-mortem rings; the supervisor snapshots it
        # on every crash and /debug serves it live
        self.flight = FlightRecorder(self.config.observability)
        # perf attribution (vgate_tpu/observability/perf.py): per-tick
        # phase decomposition, compile ledger, live MFU/roofline gauges
        # from the engine's own geometry — served via /debug/perf and
        # the /stats perf block.  Rebuilt fresh on supervised restart
        # like the flight recorder (a rebuilt core recompiles, and the
        # ledger must say so).
        self.perf = PerfRecorder(
            self.config.observability,
            roofline=EngineRoofline(
                device_kind=getattr(
                    self.mesh.devices.flat[0], "device_kind", "unknown"
                ),
                num_chips=int(self.mesh.devices.size),
                num_params=int(self.spec.num_params),
                weight_stream_bytes=stream_weight_bytes(
                    self.params, self.spec.tie_embeddings
                ),
                kv_token_bytes=kv_bytes_per_token(
                    self.spec.attn_layers,
                    self.spec.num_kv_heads,
                    self.spec.head_dim,
                    dtype_bytes=kv_dtype_bytes,
                    scale_bytes=kv_scale_bytes,
                ),
            ),
        )
        self.perf.request_totals = self.flight.phase_totals
        self.perf.device_memory = lambda: self._device_memory(
            "bytes_in_use", "peak_bytes_in_use",
            "largest_free_block_bytes", "bytes_limit",
        )
        if self.spec.conv_layers:
            self.perf.conv_block = {
                "layers": self.spec.conv_layers,
                "taps": self.spec.conv_L_cache,
                "tail_bytes_per_slot": self._state_slot_bytes,
                "state_gb": round(
                    self._state_slot_bytes * tpu_cfg.max_batch_slots / 1e9,
                    6),
            }
        # see the long rationale further down where the readback paths
        # use it; constructed here so the swap manager can share it
        self._readback_lock = named_lock("EngineCore._readback_lock")
        if host_swap_bytes > 0:
            self.kv_swap = KVSwapManager(
                budget_bytes=host_swap_bytes,
                page_bytes=self.geometry.page_bytes,
                executor=_DeviceSwapExecutor(self),
                lock=self._readback_lock,
            )
            if self.radix_cache is not None:
                self.radix_cache.attach_swap(self.kv_swap)
        self.scheduler = Scheduler(
            allocator=self.allocator,
            max_slots=self.max_slots,
            page_size=tpu_cfg.kv_page_size,
            prefill_buckets=tpu_cfg.prefill_buckets,
            max_model_len=self.config.model.max_model_len,
            max_queue_size=self.config.scheduler.max_queue_size,
            preempt_on_oom=self.config.scheduler.preempt_on_oom,
            admission_deadline_ms=(
                self.config.scheduler.admission_deadline_ms
            ),
            prefix_cache=self.prefix_cache_enabled,
            prefill_chunk=tpu_cfg.prefill_chunk,
            text_fn=self.final_text,
            recorder=self.flight,
            radix=self.radix_cache,
            cache_aware_sched=pc.cache_aware_sched,
            insert_generated=pc.insert_generated,
            evict_watermark=pc.evict_watermark,
            swap=self.kv_swap,
            page_tokens=self.geometry.page_tokens,
        )

        # host-side mirror of the device page tables, one row per slot
        self._page_tables_np = np.zeros(
            (self.max_slots, self.geometry.pages_per_seq), np.int32
        )
        self._base_key = jax.random.PRNGKey(self.config.model.max_model_len)
        self._step_counter = 0
        # (family, variant key) of every program variant launched so
        # far (_launch): a key not in it compiles at its first call
        self._compiled: set = set()
        self._dec_state: Optional[Dict[str, Any]] = None
        self._decode_signature_cache: Optional[tuple] = None
        # why the cache was killed behind the state's back, for the
        # rebuild's trace span (_invalidate_decode_state)
        self._stale_reason: Optional[str] = None
        # in-flight decode chunks awaiting host readback:
        # (seq snapshot, chunk length, [chunk, B] device tokens, start time)
        self._pending_chunks: list = []
        self.decode_chunk = max(1, tpu_cfg.decode_chunk)
        self.pipeline_depth = max(1, tpu_cfg.decode_pipeline)
        # Speculative decoding (runtime/speculative.py): per-sequence
        # prompt-lookup drafts verified in one multi-token step.  The
        # drafter is pluggable (tests inject oracles).
        self.spec_k = max(0, tpu_cfg.speculative_k)
        self.spec_ngram = max(1, tpu_cfg.speculative_ngram)
        # brownout level >= 3 (vgate_tpu/admission.py) suspends
        # speculative decoding at runtime: drafting burns verify-step
        # compute that plain decode gives back under saturation.  One
        # boolean read per tick; flipped cross-thread via
        # set_spec_suspended (bool stores are atomic under the GIL).
        self.spec_suspended = False
        self.drafter: Callable[[Sequence, int], List[int]] = (
            self._ngram_drafter
        )
        # model.draft_model_id upgrades drafting from prompt-lookup to a
        # small draft MODEL (runtime/speculative.py DraftModelDrafter).
        # Plain meshes only: the drafter is a second single-device
        # program; model-parallel engines keep n-gram drafting.
        self.draft_model = None
        draft_id = self.config.model.draft_model_id
        if draft_id and self.spec_k <= 0:
            logger.warning(
                "model.draft_model_id has no effect with "
                "tpu.speculative_k=0 — speculative decoding is off",
                extra={"extra_data": {"draft_model_id": draft_id}},
            )
        if self.spec_k > 0 and draft_id:
            if all(
                int(self.mesh.shape.get(a, 1)) == 1
                for a in ("tp", "pp", "sp", "ep")
            ):
                from vgate_tpu.runtime.speculative import DraftModelDrafter

                self.draft_model = DraftModelDrafter(
                    draft_id,
                    k_max=self.spec_k,
                    dtype=self.dtype,
                    window=int(tpu_cfg.draft_window),
                    checkpoint_path=self.config.model.draft_checkpoint_path,
                    target_vocab=self.spec.vocab_size,
                    device=self.mesh.devices.flat[0],
                    # ADVICE r5: a randomly-initialized drafter next to
                    # a real target checkpoint is a pure slowdown —
                    # DraftModelDrafter warns loudly on the combination
                    target_has_checkpoint=bool(
                        self.config.model.checkpoint_path
                    ),
                )
                self.drafter = self.draft_model.draft_for
            else:
                logger.warning(
                    "draft_model_id ignored on a model-parallel mesh; "
                    "using n-gram drafting",
                    extra={"extra_data": {"draft_model_id": draft_id}},
                )
        self.total_spec_drafted = 0
        self.total_spec_accepted = 0
        # speculative mode's sampling rows (_sampling_rows) under a
        # membership signature: rebuilt from host state when membership
        # changes; in between only the device-resident penalty histogram
        # moves (updated in-program) and each round's step indices
        self._spec_rows: Optional[Dict[str, Any]] = None

        # sp>1: prefill attention runs sequence-parallel (ring attention
        # over the sp axis); buckets must then split evenly across shards.
        # pp>1: prefill AND decode run through the GPipe stage relay
        # (parallel/pipeline.py).  The two reshape the same forward in
        # incompatible ways, so they are mutually exclusive.
        sp_size = int(self.mesh.shape.get("sp", 1))
        pp_size = int(self.mesh.shape.get("pp", 1))
        if sp_size > 1 and pp_size > 1:
            raise ValueError(
                f"sp={sp_size} and pp={pp_size} cannot combine: ring-"
                "attention prefill and the pipeline relay restructure "
                "the same forward along incompatible axes (sequence-"
                "inside-layers vs layers-across-stages) — a permanent "
                "design exclusion, not a missing feature; rationale and "
                "the supported matrix: docs/composition.md. For large "
                "meshes use sp*tp (long context) or pp*tp (deep model) "
                "with dp over the remainder."
            )
        if pp_size > 1 and self.spec.num_layers % pp_size:
            raise ValueError(
                f"{self.spec.num_layers} layers not divisible by "
                f"pp={pp_size}"
            )
        self._fwd_mesh = (
            self.mesh if (sp_size > 1 or pp_size > 1) else None
        )
        self._pp = pp_size
        self._sp = sp_size
        if sp_size > 1:
            bad = [
                b for b in self.scheduler.prefill_buckets if b % sp_size
            ]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} not divisible by sp={sp_size}; "
                    "ring-attention prefill shards the sequence axis evenly"
                )

        # sliding-window/softcap families (Gemma-2) ride every mesh: the
        # ring prefill takes window/softcap natively, and the pp relay
        # threads per-layer windows + softcap/scale through the stage
        # scan (parallel/pipeline.py, r4 — the r3 gate is gone)
        if tpu_cfg.speculative_k > 0 and pp_size > 1:
            raise ValueError(
                "speculative decoding cannot combine with pp>1: a "
                "verify round would relay candidates through every "
                "stage and roll back rejected KV writes per stage, "
                "serializing the pipeline — a permanent design "
                "exclusion (docs/composition.md). Speculation composes "
                "with sp, its long-context home turf; pp's throughput "
                "workloads are served by continuous batching."
            )
        # speculative x sp composes (r4): the verify step rides
        # sp_multitok_attention_and_write on the sharded pool — the
        # long-context single-stream case is speculation's home turf

        # Local-attention families (Gemma-2) ride both kernels: they take
        # window/softcap/scale natively, and the decode kernel skips DMA
        # for pages below the window.
        # tp>1 with Pallas on: the forwards need the mesh so attention
        # kernels run per tp shard (parallel/tp_attention.py) instead of
        # GSPMD replicating the pallas_call's operands.  The sp/pp
        # routing mesh (self._fwd_mesh) takes precedence when set.
        tp_size = int(self.mesh.shape.get("tp", 1))
        self._attn_mesh = self._fwd_mesh
        if self._attn_mesh is None and tp_size > 1 and self.use_pallas:
            self._attn_mesh = self.mesh
        # suffix-prefill / spec-verify dispatch mesh: sp shard path, or
        # the tp mesh (those forwards then gate their kernels off and
        # ride the auto-partitioned jnp paths)
        self._mt_mesh = (
            self.mesh
            if (self._sp > 1 or (tp_size > 1 and self.use_pallas))
            else None
        )
        if self.config.model.quantization in ("int8", "int4"):
            # the fused dequant kernels don't auto-partition under jit
            # sharding; model-parallel meshes keep the jnp einsum path.
            # Threaded on the spec (a static jit arg) so engines with
            # different meshes in one process never share the setting.
            # tpu.quant_kernel gates them independently of the attention
            # kernels (r4: int8 serving warmup hung in kernel compile).
            self.spec = dataclasses.replace(
                self.spec,
                quant_kernel=self.use_pallas
                and bool(tpu_cfg.quant_kernel)
                and all(
                    int(self.mesh.shape.get(a, 1)) == 1
                    for a in ("tp", "pp", "sp", "ep")
                ),
                # W8A8/W4A8 native-int8 GEMMs: pure jnp, so no mesh or
                # Pallas restriction (auto-partitions under jit sharding)
                int8_native=bool(getattr(tpu_cfg, "int8_native", False)),
            )
        elif bool(getattr(tpu_cfg, "int8_native", False)):
            logger.warning(
                "tpu.int8_native has no effect without model.quantization "
                "(int8 or int4) — serving stays on the plain dtype path"
            )
        self._submit_q: "queue.Queue[Sequence]" = queue.Queue()
        # abort commands from OTHER threads: (seq_id | None for all,
        # reason).  Processed on the engine thread each tick — the
        # scheduler's deques are engine-thread-owned, so cross-thread
        # iteration (a drain sweep racing try_admit) is never safe.
        self._abort_q: "queue.Queue[tuple]" = queue.Queue()
        # planned-evacuation commands (live migration): same
        # cross-thread discipline as aborts — the caller blocks on the
        # request's event while the engine thread checkpoints the
        # selected sequences between ticks.  See evacuate().
        self._evac_q: "queue.Queue[_EvacRequest]" = queue.Queue()
        # disaggregated prefill→decode handoff (runtime/handoff.py):
        # sequences submitted with handoff_requested are watched here
        # until their first token exists, then folded + staged via
        # scheduler.hold_for_handoff and announced through
        # on_handoff_staged (the pod worker wires it to a gateway
        # notification).  _handoff_q carries the cross-thread verdicts
        # back in — ("done", seq): the decode worker accepted, evacuate;
        # ("cancel", seq): transfer fell through, release the hold and
        # resume monolithic decode here.
        self._handoff_pending: List[Sequence] = []
        self._handoff_q: "queue.Queue[tuple]" = queue.Queue()
        self.on_handoff_staged: Optional[Callable[[Sequence, bool], None]] = (
            None
        )
        self._wakeup = threading.Event()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._fatal: Optional[BaseException] = None
        # hang-watchdog heartbeat: the loop stamps a fresh dict around
        # every dispatch/readback (whole-dict store — atomic under the
        # GIL, so the watchdog thread reads a consistent beat without a
        # lock).  `compiling` beats get recovery.compile_grace_s instead
        # of step_stall_s before the watchdog declares a stall.
        self._heartbeat: Dict[str, Any] = {
            "t": time.monotonic(), "kind": "init", "compiling": True,
        }
        # set by declare_stalled(): the engine thread is presumed stuck
        # in a device call — stop() then joins briefly instead of 30s
        self._stalled = False
        # containment entry gate: the watchdog thread and the engine
        # thread can both reach _contain_fatal (a woken stalled thread
        # typically raises against the swept state) — only the first
        # entry may run, or the second would overwrite _checkpointed
        # and silently drop the in-flight sequences awaiting replay
        self._contain_lock = named_lock("EngineCore._contain_lock")
        # readback/containment mutual exclusion: every token-append
        # readback loop holds this, and so does containment's
        # checkpoint sweep — the status/epoch guards alone are
        # check-then-append, and a woken stalled thread interleaving
        # appends with prepare_resume's prompt fold would corrupt the
        # generation (a token streamed to the client but excluded from
        # the folded prompt gets regenerated by the replay).
        # Uncontended in steady state: one acquire per readback.
        # Created EARLY (before the scheduler) because the kv-swap
        # manager's swap-out publication guard shares it: a ticket is
        # only published under this lock against a re-checked
        # status/epoch, so a containment fold can never interleave.
        # published at the END of containment (before on_fatal): the dp
        # repair thread polls _fatal, which is set FIRST — acting on a
        # mid-containment core would take an empty checkpoint and then
        # stop() the old core, turning the late-published checkpoint
        # into shutdown-lost sequences
        self._containment_done = False
        # in-flight sequences checkpointed by fatal containment for the
        # supervisor / dp router to replay (resume_in_flight); consumed
        # via take_checkpointed()
        self._checkpointed: List[Sequence] = []
        # sequences containment gave up on (max_resume_attempts); the
        # replayer folds this into its lost accounting via
        # take_resume_losses()
        self._resume_losses = 0
        self._resume_enabled = bool(
            self.config.recovery.resume_in_flight
        )
        self._max_resume_attempts = max(
            0, int(self.config.recovery.max_resume_attempts)
        )
        # flight snapshot taken on the dying engine thread, while the
        # crashed tick's residents are still live (supervisor reads it)
        self._crash_snapshot: Optional[Dict[str, Any]] = None
        # supervision hook (runtime/supervisor.py): called once from the
        # engine thread after a fatal error is fully contained.  When set,
        # owed futures fail with a *retryable* error (the supervisor is
        # about to restart the core) instead of the raw fault.
        self.on_fatal: Optional[Callable[[BaseException], None]] = None
        # (fingerprint, resume_count) of the requests resident when the
        # loop died — the supervisor's poison heuristic counts repeat
        # offenders, but only FRESH submissions (resume_count == 0)
        # increment a streak: with resume_in_flight, innocent bystanders
        # ride consecutive crashes by design, and counting replays would
        # quarantine all traffic after any two rapid crashes
        self._fatal_suspects: List[tuple] = []
        self.total_steps = 0
        self.total_prefills = 0
        # (rows worked, real rows) of the prompt programs dispatched
        # and not read back yet (perf.note_prefill_rows)
        self._prompt_rows: List[tuple] = []
        self.total_decode_tokens = 0
        self.total_state_rebuilds = 0
        # loop iterations completed (capture_profile waits on it)
        self._ticks_done = 0
        self._replicated = named(self.mesh, PartitionSpec())
        self._warm_row_edits()

    def _carried(self, value):
        """A host value placed as the step programs' own results are:
        replicated over the engine's mesh.  The chunk programs thread
        tokens, positions, step counts and the counter from call to
        call, so a state built on the host then has the type of one the
        last chunk left, and both run ONE compiled program."""
        return jax.device_put(value, self._replicated)

    def _warm_row_edits(self) -> None:
        """Compile the decode state's row edit at boot, one variant per
        batch size a prompt program can have (the powers of two that
        ``tpu.prefill_batch_max`` pads to): a membership change in the serving
        window then compiles nothing.  Cheap: three scatters of that
        many int32 each."""
        carried = (self._carried(np.zeros((self.max_slots,), np.int32)),) * 3
        n = 1
        while n < 2 * max(1, self.config.tpu.prefill_batch_max):
            rows = np.zeros((n,), np.int32)
            # every slot past the batch: the edit drops all its rows
            _join_decode_rows(
                *carried, np.full((n,), self.max_slots, np.int32),
                self._carried(rows), rows, rows,
            )
            n *= 2

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="vgt-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        self._wakeup.set()
        if self._thread is not None:
            # a watchdog-declared stall means the thread is presumed
            # stuck inside a device call: don't hold the rebuild
            # hostage for 30s waiting on it (it is a daemon; the epoch
            # checks discard anything it does if it ever wakes)
            self._thread.join(timeout=1 if self._stalled else 30)
            self._thread = None
        # the device clock's thread ends with its core
        self.perf.device.shutdown()
        # resolve every owed future: a sequence still resident (or still
        # in the submit queue) when the loop exits would leave its
        # waiter blocked on done_event forever.  Runs after the join, so
        # no engine thread races these mutations.  Checkpointed
        # sequences nobody claimed (supervisor stopped before replay)
        # are owed too.
        self._fail_pending_evacuations(
            RuntimeError("engine stopped")
        )
        checkpointed = self.take_checkpointed()
        for _ in checkpointed:
            metrics.LOST_SEQUENCES.labels(reason="shutdown").inc()
        owed = (
            list(self.scheduler.running)
            + list(self.scheduler.waiting)
            + checkpointed
        )
        while True:
            try:
                owed.append(self._submit_q.get_nowait())
            except queue.Empty:
                break
        stop_exc: Optional[BaseException] = None
        for seq in owed:
            if seq.status in (SeqStatus.RUNNING, SeqStatus.WAITING):
                if stop_exc is None:
                    stop_exc = EngineRecoveringError(
                        "engine stopped before the request could finish"
                    )
                # vgt-lint: disable=thread-discipline -- stop() joined the engine thread above; this is single-threaded teardown
                self.scheduler._release_residency(seq)
                seq.fail(stop_exc)
        self.scheduler.waiting.clear()

    # ------------------------------------------------------------ submission

    def _fail_exception(self, exc: BaseException) -> BaseException:
        """The exception owed futures fail with after a fatal: supervised
        engines (on_fatal set) are about to restart, so clients get the
        retryable 503 type with the raw fault chained; unsupervised
        engines keep the raw fault (the dp router's containment
        contract)."""
        if self.on_fatal is None:
            return exc
        wrapped = EngineRecoveringError(
            f"engine crashed and is restarting: {exc}"
        )
        wrapped.__cause__ = exc
        return wrapped

    def _on_seq_settle(self, seq: Sequence) -> None:
        """Single settle observer (Sequence.finish/fail): closes the
        flight-recorder request record and the request's phase spans —
        covers every settle path, scheduler-internal sheds included."""
        self.flight.on_close(seq)
        tr = seq.trace
        if tr is not None:
            if seq.error is None:
                tr.end("decode", tokens=seq.num_generated)
            # failures leave the phase span open so close() annotates
            # it with the exception — a cleanly-ended decode span on a
            # failed request would misread as a normal completion
            tr.close(seq.error)

    def submit_tokens(
        self,
        prompt_ids: List[int],
        params: SamplingParams,
        stream_cb: Optional[Callable[[List[int], bool], Any]] = None,
        meta: Optional[RequestMeta] = None,
    ) -> Sequence:
        if self._fatal is not None:
            raise RuntimeError("engine is dead") from self._fatal
        seq = Sequence(
            prompt_ids=list(prompt_ids),
            params=params,
            stream_cb=stream_cb,
        )
        if self.flight.enabled:
            seq.on_settle = self._on_seq_settle
            if meta is not None:
                seq.request_id = meta.request_id
                seq.trace = RequestTrace(meta)
                # the queue phase starts NOW (caller thread); the engine
                # thread ends it at admission
                seq.trace.start("queue", start_pc=seq.arrival_t)
        self._submit_q.put(seq)
        # Re-check after the put: if the engine died between the check
        # above and the put, the fatal handler may already have drained
        # the queue and will never see this seq — fail everything still
        # queued ourselves so no client hangs on done_event.  NOTE:
        # several submitter threads can race this drain (and the fatal
        # handler's own sweep) over the same queue; get_nowait hands
        # each orphan to exactly one drainer, but the SAME sequence can
        # still see fail() twice when a submitter drains a sibling the
        # handler also holds in `doomed` — correctness relies on
        # Sequence.fail() being idempotent-safe (done_event.set and the
        # _settle_notified guard make the second call a no-op for the
        # waiter and the observer; status/error overwrite with an
        # equivalent terminal value).
        if self._fatal is not None:
            exc = self._fail_exception(self._fatal)
            while True:
                try:
                    orphan = self._submit_q.get_nowait()
                except queue.Empty:
                    break
                orphan.fail(exc)
            if seq.status is SeqStatus.FAILED:
                raise RuntimeError("engine is dead") from exc
        self._wakeup.set()
        return seq

    def encode_prompt(self, prompt: str) -> List[int]:
        """Prompt -> submission token ids (chat-style suffix truncation).
        Split out so the supervisor can fingerprint a prompt for the
        poison quarantine before submission."""
        ids = self.tokenizer.encode(prompt)
        max_prompt = self.config.model.max_model_len - 1
        if len(ids) > max_prompt:
            ids = ids[-max_prompt:]  # keep the suffix (chat-style truncation)
        return ids or [self.tokenizer.bos_id]

    def submit_prompt(
        self,
        prompt: str,
        params: SamplingParams,
        stream_cb: Optional[Callable[[List[int], bool], Any]] = None,
        meta: Optional[RequestMeta] = None,
    ) -> Sequence:
        return self.submit_tokens(
            self.encode_prompt(prompt), params, stream_cb, meta=meta
        )

    def generate(
        self, prompts: Seq[str], params: Seq[SamplingParams]
    ) -> List[Dict[str, Any]]:
        """Blocking batch API used by the sync backend seam."""
        seqs = [
            self.submit_prompt(p, sp) for p, sp in zip(prompts, params)
        ]
        results = []
        for seq in seqs:
            seq.done_event.wait()
            if seq.status is SeqStatus.FAILED:
                raise seq.error  # type: ignore[misc]
            text = self.final_text(seq)
            gen_time = (seq.finish_t or 0) - seq.arrival_t
            n = seq.num_output_tokens
            result = {
                "text": text,
                "token_ids": list(seq.generated_ids),
                "num_tokens": n,
                "prompt_tokens": seq.orig_prompt_len,
                "finish_reason": seq.finish_reason,
                "metrics": {
                    "ttft": seq.ttft or 0.0,
                    "tpot": seq.tpot or 0.0,
                    "gen_time": gen_time,
                    **seq.resume_metrics(),
                },
            }
            if seq.params.logprobs:
                result["logprobs"] = self.logprob_entries(seq)
            results.append(result)
        return results

    # ------------------------------------------------------------ the loop

    @engine_thread_root
    def _loop(self) -> None:
        logger.info("engine thread started")
        while self._running:
            try:
                self._beat("tick")
                # perf attribution brackets the whole tick: phases
                # measured inside (perf.span: schedule/state/dispatch/
                # device/readback/detok) are subtracted from the tick
                # wall, the remainder is host_s — so the phases sum to
                # the wall by construction (observability/perf.py)
                self.perf.tick_begin()
                worked = self._tick()
                self.perf.tick_end(worked)
                self._ticks_done += 1
                if not worked:
                    with self.perf.span("idle_wait"):
                        self._wakeup.wait(timeout=0.005)
                    self._wakeup.clear()
            except Exception as exc:
                logger.error("engine loop fatal error", exc_info=True)
                self._contain_fatal(exc)
        logger.info("engine thread stopped")

    @engine_thread_only
    def _beat(self, kind: str, compiling: bool = False, **fields) -> None:
        """Stamp the watchdog heartbeat (whole-dict store — atomic under
        the GIL).  Call immediately BEFORE any potentially-blocking
        device dispatch/readback so a wedge there is exactly what ages
        the beat; ``compiling`` widens the stall threshold to
        recovery.compile_grace_s for first-compile pauses."""
        self._heartbeat = {
            "t": time.monotonic(),
            "kind": kind,
            "compiling": bool(compiling),
            **fields,
        }

    def _contain_fatal(self, exc: BaseException) -> bool:
        """Fatal containment, shared by the engine thread's crash
        handler and the watchdog's :meth:`declare_stalled`: record the
        crash tick + flight snapshot, collect poison suspects, then
        either CHECKPOINT resumable in-flight sequences for the
        supervisor / dp router to replay (resume_in_flight under
        supervision) or fail every owed future (the unsupervised
        containment contract).

        The crash becomes the ring's final tick, so a snapshot ends
        with the faulting dispatch; the snapshot runs BEFORE the sweep
        below — the in-flight view must show what was resident at the
        moment of death, not after.

        First entry only (returns False otherwise): after a watchdog
        declare_stalled, the stuck engine thread usually wakes into the
        already-swept state, raises, and lands here AGAIN via the loop's
        except handler — re-running the sweep would overwrite
        _checkpointed (dropping the sequences awaiting replay) and fire
        a duplicate on_fatal."""
        with self._contain_lock:
            if self._fatal is not None:
                logger.warning(
                    "fatal containment skipped: engine already "
                    "contained",
                    extra={
                        "extra_data": {
                            "error": f"{type(exc).__name__}: {exc}",
                            "first": (
                                f"{type(self._fatal).__name__}: "
                                f"{self._fatal}"
                            ),
                        }
                    },
                )
                return False
            self._fatal = exc
        try:
            self._contain_body(exc)
        except Exception:  # pragma: no cover - defensive
            # containment itself failing must NOT strand the system:
            # _fatal is already set, so if _containment_done never
            # published, the supervisor would stay SERVING with hung
            # clients and the dp sweep would skip this replica forever.
            # Swallow (we are already dying of `exc`), log loudly, and
            # fall through so the flag + on_fatal always run.
            logger.error(
                "fatal containment raised; proceeding to publication "
                "with a possibly partial sweep",
                exc_info=True,
            )
            self._running = False
        # published before on_fatal: when the dp repair thread (or the
        # supervisor) wakes on the hook, the checkpoint is complete
        self._containment_done = True
        # unblock any evacuate() caller: the containment checkpoint now
        # owns the residents (the dp sweep will redistribute them)
        self._fail_pending_evacuations(exc)
        if self.on_fatal is not None:
            try:
                self.on_fatal(exc)
            except Exception:  # pragma: no cover - defensive
                logger.error("on_fatal hook failed", exc_info=True)
        return True

    def _contain_body(self, exc: BaseException) -> None:
        """The containment work itself (snapshot, suspects, sweep) —
        split from :meth:`_contain_fatal` so the caller can guarantee
        `_containment_done` + `on_fatal` publication even if any of
        this raises."""
        self.flight.record_tick(
            "crash",
            error=f"{type(exc).__name__}: {exc}",
            batch=len(self.scheduler.running),
            queue_depth=len(self.scheduler.waiting),
        )
        self._crash_snapshot = self.flight.crash_snapshot(exc)
        # poison-heuristic evidence: the requests resident at the
        # crash (keyed by their ORIGINAL prompt, which survives
        # preemption's and resume's prompt folding), with the resume
        # attempt count so the supervisor can tell client persistence
        # (fresh submissions) from the engine's own replays
        self._fatal_suspects = [
            (
                faults.fingerprint(s.prompt_ids[: s.orig_prompt_len]),
                s.resume_count,
            )
            for s in self.scheduler.running
            # integrity canaries are the ENGINE's own probes: never
            # poison suspects (quarantining the canary prompt would
            # blind every future self-probe)
            if not s.canary
        ]
        # sweep EVERY owed future: running, waiting, and anything still
        # sitting in the submit queue (a client blocked on one of those
        # would otherwise hang forever).  Under supervision with
        # resume_in_flight, resumable sequences are checkpointed as
        # prefill-continues instead of failed — the supervisor replays
        # them into the rebuilt core and clients see a latency blip,
        # not a 503.
        checkpointing = (
            self._resume_enabled and self.on_fatal is not None
        )
        fail_exc: Optional[BaseException] = None
        kept: List[Sequence] = []
        # the sweep excludes token-append readbacks (see
        # _readback_lock): a woken stalled thread must observe either
        # pre-fold state (its epoch check passes, containment waits) or
        # fully-folded state (epoch bumped, it skips) — never a fold in
        # progress.  BOUNDED acquire, fail-open: the append sections
        # run stream_cb/settle callbacks, and a wedge *there* is
        # precisely a stall — blocking the watchdog thread on it
        # forever would wedge the monitor itself (no rebuild, no
        # further stall detection).  Proceeding without the lock risks
        # only the narrow interleaving the lock exists for; a wedged
        # monitor loses everything.
        locked = self._readback_lock.acquire(timeout=5.0)
        if not locked:
            logger.error(
                "containment proceeding WITHOUT the readback lock "
                "(append section appears wedged — likely a stuck "
                "stream callback); sequences mid-append may replay "
                "with a duplicated token"
            )
        try:
            doomed = list(self.scheduler.running) + list(
                self.scheduler.waiting
            )
            while True:
                try:
                    doomed.append(self._submit_q.get_nowait())
                except queue.Empty:
                    break
            for seq in doomed:
                if (
                    checkpointing
                    and not seq.abort_requested
                    and not seq.canary
                ):
                    if seq.resume_count >= self._max_resume_attempts:
                        # replaying a request that has now ridden
                        # through max_resume_attempts restarts is more
                        # likely the crashes' cause than their victim:
                        # typed 503
                        metrics.LOST_SEQUENCES.labels(
                            reason="max_attempts"
                        ).inc()
                        self._resume_losses += 1
                        seq.fail(
                            ResumeExhaustedError(
                                "request was in flight across "
                                f"{seq.resume_count} engine restarts "
                                "and was given up on; retry shortly"
                            )
                        )
                        continue
                    if seq.trace is not None:
                        seq.trace.resumed()
                    # stamp the pool format the checkpoint's sampling
                    # history was produced under: submit_existing on the
                    # replay target refuses a mismatch (a replica fleet
                    # mid-rollout can mix kv dtypes; replaying into a
                    # different format would silently change numerics
                    # mid-generation).  getattr: bare-core test fakes
                    # run containment without ever building a pool.
                    geo = getattr(self, "geometry", None)
                    if geo is not None:
                        seq.kv_dtype = geo.kv_dtype
                    seq.prepare_resume()
                    kept.append(seq)
                    continue
                if fail_exc is None:
                    fail_exc = self._fail_exception(exc)
                seq.fail(fail_exc)
            self._checkpointed = kept
            self.scheduler.waiting.clear()
            for i in range(len(self.scheduler.slots)):
                self.scheduler.slots[i] = None
            self._pending_chunks.clear()
            self._running = False
        finally:
            if locked:
                self._readback_lock.release()

    def declare_stalled(self, exc: BaseException) -> bool:
        """Watchdog containment, called OFF the engine thread when the
        heartbeat went stale: the loop is presumed stuck inside a
        device call (Mosaic hang, stuck device call, wedged transfer) —
        nothing will ever *raise*, so the monitor declares the fault.
        Stops the loop flag first (the stuck thread exits if it ever
        wakes), then runs the same containment as an on-thread crash.
        The small window where a merely-slow thread wakes mid-sweep is
        covered by the preempt-epoch checks on every readback path:
        checkpointed sequences bumped their epoch, so late tokens are
        discarded.  Returns False when the engine already died (or
        stopped) another way."""
        if self._fatal is not None or not self._running:
            return False
        self._stalled = True
        self._running = False
        self._wakeup.set()
        hb = self._heartbeat
        self.flight.record_tick(
            "stall",
            phase=hb.get("kind"),
            stalled_s=round(time.monotonic() - hb.get("t", 0.0), 3),
            compiling=hb.get("compiling", False),
            batch=len(self.scheduler.running),
            queue_depth=len(self.scheduler.waiting),
        )
        # False when an on-thread crash won the containment race — the
        # caller must not count a stall the engine didn't die of
        return self._contain_fatal(exc)

    def take_checkpointed(self) -> List[Sequence]:
        """Hand the fatal-containment checkpoint to its replayer
        (supervisor restart / dp failover); idempotent-empty after."""
        # vgt-lint: disable=thread-discipline -- single GIL-atomic swap; callers gate on _containment_done, after which the folding writer is done
        out, self._checkpointed = self._checkpointed, []
        return out

    def take_resume_losses(self) -> int:
        """Sequences containment gave up on (already failed typed);
        the replayer folds the count into its lost total.  Zeroing like
        take_checkpointed so repeated sweeps never double-count."""
        n, self._resume_losses = self._resume_losses, 0
        return n

    def submit_existing(self, seq: Sequence) -> None:
        """Re-admit a checkpointed sequence from another engine
        incarnation (supervisor replay) or a dead dp replica
        (failover).  The SAME Sequence object rides in — done_event
        waiter, stream_cb, cancel-token abort hooks and the absolute
        deadline all stay valid — re-wired to this core's settle
        observer, and prefilled-continue on admission (prepare_resume
        already folded the partial generation into the prompt)."""
        if self._fatal is not None:
            raise RuntimeError("engine is dead") from self._fatal
        if (
            seq.kv_dtype is not None
            and seq.kv_dtype != self.geometry.kv_dtype
        ):
            # fail cleanly instead of replaying garbage: the generated
            # prefix being folded into the prompt was sampled against a
            # different KV storage format — continuing it here would
            # splice two numerically different streams.  replay_into
            # turns this into the typed retryable 503.
            raise ValueError(
                f"checkpoint was taken under kv dtype "
                f"{seq.kv_dtype!r} but this core serves "
                f"{self.geometry.kv_dtype!r}; refusing the replay"
            )
        seq.on_settle = (
            self._on_seq_settle if self.flight.enabled else None
        )
        self._submit_q.put(seq)
        # same post-put re-check as submit_tokens: a crash between the
        # gate and the put may have swept the queue already
        if self._fatal is not None:
            exc = self._fail_exception(self._fatal)
            while True:
                try:
                    orphan = self._submit_q.get_nowait()
                except queue.Empty:
                    break
                orphan.fail(exc)
            if seq.status is SeqStatus.FAILED:
                raise RuntimeError("engine is dead") from exc
        self._wakeup.set()

    # --------------------------------------------- planned evacuation

    def evacuate(
        self,
        seq_ids: Optional[List[int]] = None,
        reason: str = "drain",
        timeout: float = 30.0,
    ) -> List[Sequence]:
        """Checkpoint selected RUNNING/WAITING sequences WITHOUT a
        fatal — the planned-movement twin of ``_contain_fatal``'s
        checkpoint path (replica drain, hot-replica rebalance, dp
        scale-down).  The core stays alive and keeps serving its other
        residents; the selected sequences' slots + KV pages free this
        tick, nothing settles, and the LIVE Sequence objects come back
        folded as prefill-continues (``prepare_migrate``: the PR-5
        staleness epoch bumped so in-flight chunk readbacks discard,
        the kv-dtype stamp set so a mismatched replay target refuses
        cleanly).  ``seq.checkpoint()`` yields the pure-data
        ``SequenceCheckpoint`` form of each.

        Thread-safe: enqueues a command the engine thread applies
        between ticks (the scheduler's deques are engine-thread-owned)
        and blocks up to ``timeout`` — generous by default because the
        loop may legitimately be inside a long device dispatch.
        ``seq_ids=None`` selects everything resident or queued.
        Raises RuntimeError when the engine is (or dies while)
        evacuating — the caller's failover machinery then owns the
        residents — and MigrationError on timeout."""
        if self._fatal is not None:
            raise RuntimeError("engine is dead") from self._fatal
        req = _EvacRequest(
            list(seq_ids) if seq_ids is not None else None, reason
        )
        self._evac_q.put(req)
        self._wakeup.set()
        if not req.event.wait(timeout=timeout):
            with req.lock:
                published = (
                    req.result is not None or req.error is not None
                )
                if not published:
                    req.cancelled = True
            if not published:
                if self._fatal is not None:
                    raise RuntimeError(
                        "engine died while evacuating"
                    ) from self._fatal
                raise MigrationError(
                    f"evacuation did not complete within "
                    f"{timeout:.1f}s (engine loop busy or wedged); "
                    "sequences stayed put"
                )
            # publication raced the timeout: the evacuation completed
            # and we own the result after all — fall through
        if req.error is not None:
            raise req.error
        return req.result or []

    def _fail_pending_evacuations(self, exc: BaseException) -> None:
        """Unblock evacuate() callers when the loop can no longer serve
        them (stop/fatal); their sequences are untouched — containment
        or shutdown accounting owns the residents from here."""
        while True:
            try:
                req = self._evac_q.get_nowait()
            except queue.Empty:
                return
            with req.lock:
                if not req.cancelled:
                    req.error = RuntimeError(
                        f"engine unavailable for evacuation: {exc}"
                    )
            req.event.set()

    @engine_thread_only
    def _process_evacuations(self) -> None:
        """Apply queued evacuation commands (engine thread only)."""
        while True:
            try:
                req = self._evac_q.get_nowait()
            except queue.Empty:
                return
            with req.lock:
                if req.cancelled:
                    continue  # caller timed out; sequences stayed put
            result: Optional[List[Sequence]] = None
            error: Optional[BaseException] = None
            try:
                result = self._evacuate_now(req.seq_ids, req.reason)
            except Exception as exc:  # pragma: no cover - defensive
                logger.error("evacuation failed", exc_info=True)
                error = exc
            with req.lock:
                if req.cancelled:
                    # the caller gave up MID-evacuation: nobody will
                    # place these sequences — fold them straight back
                    # into this core so their clients keep streaming
                    # here, exactly as if nothing had moved
                    if result:
                        for seq in result:
                            try:
                                self.submit_existing(seq)
                            except RuntimeError:
                                # went fatal mid-undo: settle typed
                                # rather than strand the sequence
                                # outside every scheduler
                                seq.fail(self._fail_exception(
                                    self._fatal
                                    or RuntimeError("engine stopped")
                                ))
                        logger.warning(
                            "evacuation abandoned by timed-out "
                            "caller; re-admitted locally",
                            extra={"extra_data": {
                                "count": len(result),
                                "reason": req.reason,
                            }},
                        )
                else:
                    req.result = result
                    req.error = error
            req.event.set()

    @engine_thread_only
    def _evacuate_now(
        self, seq_ids: Optional[List[int]], reason: str
    ) -> List[Sequence]:
        targets = None if seq_ids is None else set(seq_ids)
        candidates = list(self.scheduler.running) + list(
            self.scheduler.waiting
        )
        if not any(
            targets is None or s.seq_id in targets for s in candidates
        ):
            return []
        # fold in-flight decode chunks into host state FIRST: tokens
        # already sampled on device would otherwise be discarded by the
        # epoch guard and regenerated on the target (correct for greedy/
        # seeded, but wasted compute — and a distribution re-draw for
        # unseeded sampling, exactly like preemption)
        if self._pending_chunks:
            self._process_chunks(drain=True)
            self._invalidate_decode_state("evacuate")
            candidates = list(self.scheduler.running) + list(
                self.scheduler.waiting
            )
        out: List[Sequence] = []
        for seq in candidates:
            if targets is not None and seq.seq_id not in targets:
                continue
            if seq.status not in (SeqStatus.RUNNING, SeqStatus.WAITING):
                continue  # settled while the chunks drained
            if seq.abort_requested:
                continue  # about to settle as abort; nothing to move
            # stamp the KV storage format the generated prefix was
            # sampled under — submit_existing on the target refuses a
            # mismatch (same guard as crash checkpoints)
            geo = getattr(self, "geometry", None)
            if geo is not None:
                seq.kv_dtype = geo.kv_dtype
            if seq.trace is not None:
                seq.trace.migrated()
            self.scheduler.evacuate(seq)
            seq.prepare_migrate()
            self.flight.record_tick(
                "migrate",
                seq_id=seq.seq_id,
                request_id=seq.request_id,
                tokens=seq.num_generated,
                reason=reason,
            )
            out.append(seq)
        if out:
            # membership changed: any device decode state is stale
            self._invalidate_decode_state("evacuate")
            logger.info(
                "evacuated sequences for planned migration",
                extra={
                    "extra_data": {
                        "count": len(out), "reason": reason,
                    }
                },
            )
        return out

    # ------------------ disaggregated prefill→decode handoff staging

    def handoff_done(self, seq: Sequence) -> None:
        """Cross-thread (worker RPC plane): the decode worker ACCEPTED
        this sequence's KV transfer — drop its queue slot and local
        staged ticket WITHOUT settling it (the decode worker owns the
        stream now).  Processed on the engine thread next tick."""
        self._handoff_q.put(("done", seq))
        self._wakeup.set()

    def handoff_cancel(self, seq: Sequence) -> None:
        """Cross-thread: the transfer fell through (retries exhausted,
        decode pool drained, gateway raced a loss) — lift the hold so
        the next try_admit swap-ins the staged KV and decode continues
        MONOLITHICALLY here with zero recompute."""
        self._handoff_q.put(("cancel", seq))
        self._wakeup.set()

    @engine_thread_only
    def _process_handoffs(self) -> None:
        """Handoff staging pump (runtime/handoff.py), run each tick
        after evacuations: apply cross-thread done/cancel verdicts,
        then fold+stage any watched sequence whose first token now
        exists and announce it via ``on_handoff_staged``."""
        while True:
            try:
                verb, seq = self._handoff_q.get_nowait()
            except queue.Empty:
                break
            if verb == "done":
                if getattr(seq, "_handoff_hold", False):
                    seq._handoff_hold = False  # type: ignore[attr-defined]
                    self.scheduler.evacuate(seq)
                    self.flight.record_tick(
                        "handoff_done", seq_id=seq.seq_id,
                        request_id=seq.request_id,
                    )
            else:  # "cancel"
                self.scheduler.release_hold(seq)
        if not self._handoff_pending:
            return
        pending: List[Sequence] = []
        ready: List[Sequence] = []
        for seq in self._handoff_pending:
            if (
                not seq.handoff_requested
                or seq.status not in (SeqStatus.WAITING, SeqStatus.RUNNING)
                or seq.abort_requested
            ):
                continue  # settled/cancelled — stop watching
            if seq.status is SeqStatus.RUNNING and seq.num_generated >= 1:
                ready.append(seq)
            else:
                pending.append(seq)  # still queued or mid-prefill
        self._handoff_pending = pending
        if not ready:
            return
        if self._pending_chunks:
            # fold in-flight decode chunks first (like _evacuate_now):
            # the staged KV must cover every token already streamed
            self._process_chunks(drain=True)
            self._invalidate_decode_state("handoff")
        for seq in ready:
            seq.handoff_requested = False
            if seq.status is not SeqStatus.RUNNING or seq.abort_requested:
                staged = False  # settled while the chunks drained
            else:
                # stamp the KV storage format like every checkpoint
                # path — submit_existing on the decode worker refuses
                # a mismatched pool
                geo = getattr(self, "geometry", None)
                if geo is not None:
                    seq.kv_dtype = geo.kv_dtype
                staged = self.scheduler.hold_for_handoff(seq)
            if staged:
                self._invalidate_decode_state("handoff")
                self.flight.record_tick(
                    "handoff_stage", seq_id=seq.seq_id,
                    request_id=seq.request_id, tokens=seq.num_generated,
                )
            cb = self.on_handoff_staged
            if cb is not None:
                try:
                    cb(seq, staged)
                except Exception:  # pragma: no cover - defensive
                    logger.error(
                        "on_handoff_staged callback failed", exc_info=True
                    )

    @engine_thread_only
    def _tick(self) -> bool:
        """One iteration of the engine loop.

        1. Dispatch every admissible prefill asynchronously
           (``_admit_and_dispatch``).
        2. Keep up to ``pipeline_depth`` decode chunks in flight: dispatch
           the next chunk against device-resident state, then block on the
           *oldest* chunk's readback — host-side token processing overlaps
           device execution of the newer chunk.
        3. Read the wave's first tokens back in a single transfer
           (``_read_first_tokens``), behind that dispatch.

        What a change of the decode batch's membership does between 1
        and 2.  A PLAIN change (streams ended at a readback, prompts
        joined through this wave's prompt programs, the program variant
        the same; ``_drain_reason``) drains nothing: the device state
        persists, ``_join_and_dispatch`` switches the ended rows off
        and gives each joiner its prompt program's device token, and
        the chunks in flight stay in flight.  Every other change
        (preemption and swap-in, evacuation and handoff, an abort, a
        deadline or a sentinel trip, speculative rounds, chunked
        prefill, another program variant, a row with a penalty
        histogram, the first dispatch) reads the first tokens FIRST,
        then folds every chunk in flight into host state
        (``_process_chunks(drain=True)``) and rebuilds the device state
        from it (``_build_decode_state``), as every change once did.

        Returns False when there was no work (the loop then sleeps).
        """
        with self.perf.span("schedule", self._schedule_args):
            self._drain_submissions()
            # planned evacuations before anything dispatches: a drain/
            # rebalance coordinator is blocked on this, and the selected
            # sequences must not burn another decode chunk here first
            self._process_evacuations()
            # then handoff staging (disaggregated prefill→decode): fold
            # first-token'd handoff candidates off the device before
            # they burn decode chunks that belong on the decode pool
            self._process_handoffs()
            # stall fault probe (vgate_tpu/faults.py): a `delay` armed
            # here past recovery.step_stall_s simulates a wedged loop
            # for the hang watchdog.  Only probed while work is
            # resident, so chaos arming cannot stall an idle engine
            # into a pointless restart.
            if faults.is_active() and self.scheduler.has_work():
                t_probe = time.perf_counter()
                faults.check("stall")
                # an armed delay slept here: a pause it makes is the
                # host's, not CPU time the thread lost
                self.perf.note_sleep(time.perf_counter() - t_probe)
                if not self._running:
                    # the watchdog declared this core stalled while the
                    # armed delay slept: containment already swept the
                    # residents — touching scheduler state now would
                    # race the replay on the rebuilt core
                    return False
            self._drain_abort_requests()
            self._handle_aborts()
            self._handle_deadlines()
            # proactive prefix-cache trim (two int compares when
            # healthy): keep truly-free pages above the evict watermark
            # so allocation bursts never pay the eviction walk
            # synchronously and admission's kv_pressure shedding only
            # ever sees a drained cache
            self.scheduler.maybe_trim()
        if self.spec_k > 0 and not self.spec_suspended:
            if self._pending_chunks:
                # chunked decode ran while a brownout suspended
                # speculation: fold the in-flight chunks into host
                # state before a spec round reads last-token/positions
                self._process_chunks(drain=True)
            # and kill the chunk path's signature cache: spec rounds
            # advance positions behind its device state's back
            self._invalidate_decode_state("spec")
            worked = self._admit_and_prefill()
            worked = self._tick_speculative() or worked
            if (
                self.integrity is not None
                and not worked
                and not self.scheduler.has_work()
            ):
                # idle-tick checksum sweep, speculative path (the
                # non-spec twin below)
                self.integrity.idle_tick(self)
            return worked
        wave = self._admit_and_dispatch()
        worked = bool(wave.plans) or wave.swapped

        # decode scheduling is bracketed statement by statement so the
        # leaf spans never overlap: _process_chunks, _build_decode_state
        # and _dispatch_chunk open their own
        active = self._running_seqs()
        changed, reason = False, None
        if not active:
            self.perf.clear_delivery_clock()
        else:
            with self.perf.span("schedule"):
                signature = self._decode_signature(active)
                changed = signature != self._decode_signature_cache
                if changed:
                    reason = self._drain_reason(active, wave)
        if changed and reason is None:
            # a plain change (streams ended at a readback, prompts
            # joined through this wave's programs): edit the device
            # state's rows and dispatch the next chunk behind the wave,
            # with every chunk in flight left in flight
            reason = self._join_and_dispatch(active, wave)
        # the wave's first tokens, exactly when they always were read:
        # behind a plain change's chunk dispatch, ahead of a rebuild
        # (which takes every row's last token from host state)
        self._read_first_tokens(wave)
        if changed:
            self.perf.note_membership_change(
                drained=reason is not None, reason=reason
            )
            worked = True
            if reason is not None:
                # every other change: all in-flight chunks must be folded
                # into host state before rebuilding the device state.  The
                # cache is dead from here until a rebuild succeeds — leaving
                # the old value would let a later identical-looking
                # membership dispatch against stale device tokens/positions.
                self._decode_signature_cache = None
                self._process_chunks(drain=True)
                with self.perf.span("schedule"):
                    active = self._running_seqs()
                    chunk = self._pick_chunk(active) if active else 0
                    if active and self.scheduler.prepare_decode(
                        active, horizon=chunk
                    ):
                        active = self._running_seqs()  # minus any victims
                    else:
                        chunk = 0
                if chunk and active:
                    self._build_decode_state(active, reason)
                    self._decode_signature_cache = (
                        self._decode_signature(active)
                    )
                    self._dispatch_chunk(active, chunk)
        elif active:
            if len(self._pending_chunks) < self.pipeline_depth:
                survivors: List[Sequence] = []
                new_sig = None
                go = refresh = False
                with self.perf.span("schedule"):
                    in_flight = sum(c[1] for c in self._pending_chunks)
                    chunk = self._pick_chunk(active, lead=in_flight)
                    if chunk and self.scheduler.prepare_decode(
                        active, horizon=in_flight + chunk
                    ):
                        # preemption changes membership -> handled next
                        # tick; dispatch when the slot set survived
                        # intact, refreshing only the page-table upload
                        # when pages merely grew (tokens/positions stay
                        # device-resident — a drain here would collapse
                        # the pipeline at every page boundary)
                        survivors = self._running_seqs()
                        new_sig = self._decode_signature(survivors)
                        if new_sig == self._decode_signature_cache:
                            go = True
                        elif [
                            t[:3] for t in new_sig
                        ] == [
                            t[:3]
                            for t in self._decode_signature_cache or ()
                        ]:
                            # identity (incl. preempt epoch) intact, only
                            # page counts grew -> page-table refresh is
                            # sufficient
                            go = refresh = True
                if chunk == 0:
                    # every sequence's budget is already covered by the
                    # in-flight steps — a new chunk would be pure overshoot
                    self._process_chunks()
                elif go:
                    if refresh:
                        with self.perf.span("state"):
                            self._refresh_page_tables(survivors)
                        self._decode_signature_cache = new_sig
                    self._dispatch_chunk(active, chunk)
                worked = True

        if self._pending_chunks and (
            len(self._pending_chunks) >= self.pipeline_depth
            or not active
        ):
            self._process_chunks(drain=not active)
            worked = True
        if (
            self.integrity is not None
            and not worked
            and not self._pending_chunks
            and not self.scheduler.has_work()
        ):
            # idle tick: advance the budgeted weight-checksum sweep
            # (integrity.sweep_leaves_per_tick small on-device
            # reductions) — never on a tick that did decode/prefill
            # work, so the sweep cannot steal serving latency.  A
            # mismatch raises IntegrityError: containment routes it to
            # the supervisor / dp repair as a `corrupt` fatal and the
            # rebuild reloads weights instead of keeping them.
            self.integrity.idle_tick(self)
        # re-tick immediately when processing just opened a slot for a
        # waiting prompt (otherwise the loop would nap 5ms before admitting)
        return (
            worked
            or bool(self._pending_chunks)
            or self.scheduler.has_admissible_waiting()
        )

    def _schedule_args(self) -> Dict[str, int]:
        """Arguments of a ``vgt.engine.schedule`` trace span (only
        evaluated while a profile capture runs)."""
        return {
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
        }

    @engine_thread_only
    def _running_seqs(self) -> List[Sequence]:
        return [
            s for s in self.scheduler.running
            if s.status is SeqStatus.RUNNING
        ]

    @engine_thread_only
    def _handle_aborts(self) -> None:
        """Drop RUNNING sequences whose client cancelled (SSE disconnect
        etc.): slot + pages free immediately, finish_reason "abort".
        In-flight chunks may still hold the sequence — the per-chunk
        epoch/status check discards their tokens at readback.  Waiting-
        queue aborts drop when they reach the queue head
        (scheduler.try_admit)."""
        for seq in self._running_seqs():
            if seq.abort_requested:
                # bind the owning request so every log record emitted
                # while dropping the sequence carries its identity
                # (logging_config falls back to the thread-local when
                # the engine thread has no active span)
                with bound_request(
                    seq.request_id, getattr(seq.trace, "trace_id", None)
                ):
                    self.scheduler.abort(seq)

    @engine_thread_only
    def _handle_deadlines(self) -> None:
        """Shed RUNNING sequences past their end-to-end deadline between
        decode ticks: the client's budget is blown, so decoding on would
        only burn batchmates' step time.  The owed future fails with a
        DeadlineExceededError carrying the partial generation (→ 504
        with partial-tokens metadata at the gateway); slot + KV pages
        free this tick.  Waiting-queue deadlines are the scheduler's
        ``_shed_expired``.  In-flight chunks holding the sequence are
        harmless: the per-chunk status check discards their tokens."""
        now = time.perf_counter()
        for seq in self._running_seqs():
            if not seq.past_deadline(now):
                continue
            if seq.trace is not None:
                seq.trace.event("deadline_shed")
            with bound_request(
                seq.request_id, getattr(seq.trace, "trace_id", None)
            ):
                self._shed_deadline(seq)

    @engine_thread_only
    def _shed_deadline(self, seq: Sequence) -> None:
        self.scheduler.shed(
            seq,
            DeadlineExceededError(
                f"request deadline ({seq.params.timeout_s:.3f}s) "
                f"passed mid-generation after "
                f"{seq.num_generated} tokens",
                partial_text=self.final_text(seq),
                partial_tokens=seq.num_generated,
                deadline_s=seq.params.timeout_s or 0.0,
                # where the budget went (flight recorder): lets a 504
                # distinguish "queued forever" from "decoded slowly"
                # without server access
                phases=self.flight.phases_of(seq),
            ),
        )

    def abort(self, seq_id: int, reason: str = "client_disconnect") -> None:
        """Request-scoped cancellation by sequence id (the vLLM
        ``abort_request`` surface): enqueues an abort command the engine
        thread applies at its next tick (shed within one tick; slot +
        KV pages freed).  Thread-safe by construction — the scheduler's
        deques are only ever touched on the engine thread."""
        self._abort_q.put((seq_id, reason))
        self._wakeup.set()

    def abort_in_flight(self, reason: str = "drain") -> None:
        """Request-abort EVERY waiting/running sequence (the graceful
        drain's straggler sweep once ``lifecycle.drain_timeout_s``
        passes).  Applied on the engine thread at its next tick."""
        self._abort_q.put((None, reason))
        self._wakeup.set()

    @engine_thread_only
    def _drain_abort_requests(self) -> None:
        """Apply queued abort commands (engine thread only)."""
        while True:
            try:
                seq_id, reason = self._abort_q.get_nowait()
            except queue.Empty:
                return
            for seq in list(self.scheduler.running) + list(
                self.scheduler.waiting
            ):
                if (
                    (seq_id is None or seq.seq_id == seq_id)
                    and seq.status
                    in (SeqStatus.RUNNING, SeqStatus.WAITING)
                    and not seq.abort_requested
                ):
                    seq.request_abort(reason)

    # ------------------------------------------------------------- prefill

    @engine_thread_only
    def _drain_submissions(self) -> None:
        while True:
            try:
                seq = self._submit_q.get_nowait()
            except queue.Empty:
                return
            adopt = getattr(seq, "_handoff_adopt", None)
            if adopt is not None:
                # decode-side arrival of a prefill→decode handoff: park
                # the shipped KV payload as a local swap ticket so
                # try_admit swap-ins with ZERO recompute.  A refusal
                # (no swap tier / pool full) folds to the recompute
                # path instead — slower, still token-identical.
                seq._handoff_adopt = None  # type: ignore[attr-defined]
                payload, num_pages = adopt
                adopted = (
                    self.kv_swap is not None
                    and self.kv_swap.adopt_remote(seq, payload, num_pages)
                )
                if not adopted:
                    seq.reset_for_recompute()
                    logger.warning(
                        "handoff payload adoption refused; falling back "
                        "to re-prefill",
                        extra={"extra_data": {
                            "seq_id": seq.seq_id,
                            "request_id": seq.request_id,
                            "pages": num_pages,
                        }},
                    )
            try:
                self.scheduler.add(seq)
            except Exception as exc:
                seq.fail(exc)
                continue
            if seq.handoff_requested:
                self._handoff_pending.append(seq)

    @engine_thread_only
    def _step_key(self):
        self._step_counter += 1
        return jax.random.fold_in(self._base_key, self._step_counter)

    @engine_thread_only
    def _admit_and_prefill(self) -> bool:
        """Admit, dispatch the prompt programs, read their first tokens
        back: the whole of an admission wave with nothing in between
        (the speculative tick; the chunked tick dispatches its next
        decode chunk between the two halves, see ``_tick``)."""
        wave = self._admit_and_dispatch()
        self._read_first_tokens(wave)
        return bool(wave.plans) or wave.swapped

    @engine_thread_only
    def _admit_and_dispatch(self) -> _PrefillWave:
        """Admit waiting prompts a free slot + pages exist for, then prefill
        them in **batched programs**: same-bucket admissions stack into one
        ``[B, bucket]`` dispatch (B padded to the next power of two, padding
        rows writing trash page 0), so a burst of N prompts costs
        ~N/prefill_batch_max dispatches instead of N.  Nothing here waits
        for the device: the wave's first tokens are read back by
        ``_read_first_tokens``, in a single transfer.

        While sequences are actively decoding, at most
        ``tpu.prefill_admit_limit`` prompts are admitted per tick, so a
        burst of prefills cannot stall resident slots for the whole burst —
        decode chunks keep flowing between admission waves (VERDICT r1
        weak-2; the capability vLLM's continuous batching provides opaquely
        at the reference's vgate/backends/vllm_backend.py:51)."""
        limit = self.config.tpu.prefill_admit_limit
        wave = _PrefillWave(start=time.perf_counter())
        plans = wave.plans
        swap_plans: List[SwapInPlan] = []
        with self.perf.span("schedule", self._schedule_args):
            decoding = bool(self._running_seqs())
            while True:
                if (
                    decoding and limit
                    and len(plans) + len(swap_plans) >= limit
                ):
                    break
                plan = self.scheduler.try_admit()
                if plan is None:
                    break
                if isinstance(plan, SwapInPlan):
                    swap_plans.append(plan)
                else:
                    plans.append(plan)
        for plan in swap_plans:
            # host-swap re-admission: a jitted host->device scatter
            # replaces the re-prefill entirely — zero recompute tokens
            self._dispatch_swap_in(plan)
        wave.swapped = bool(swap_plans)
        if not plans:
            return wave
        with self.perf.span("schedule"):
            wave.plan_epochs, chunked, by_bucket = self._stage_prefills(
                plans
            )
        wave.chunked = bool(chunked)
        dispatched = wave.dispatched  # (group plans, [B] device tokens)
        dispatched.extend(
            ([plan], self._dispatch_chunked_prefill(plan))
            for plan in chunked
        )
        batch_max = max(1, self.config.tpu.prefill_batch_max)
        for (bucket, cached, unaligned), group in sorted(by_bucket.items()):
            for i in range(0, len(group), batch_max):
                chunk = group[i : i + batch_max]
                dispatched.append((chunk, self._dispatch_prompt(
                    chunk, bucket, cached=cached, unaligned=unaligned
                )))
        # index the freshly-filled prompt pages only now, with every
        # writer program enqueued: a reader admitted in a LATER tick is
        # guaranteed to dispatch after the writer (device program order).
        # A sequence a watchdog containment checkpointed mid-dispatch
        # (its pages are already released) must not be indexed — the
        # epoch guard mirrors the readback one below.
        with self.perf.span("schedule"):
            for plan in plans:
                stale = (
                    plan.seq.status is not SeqStatus.RUNNING
                    or plan.seq.preempt_count != wave.plan_epochs[id(plan)]
                )
                self.scheduler.commit_prefill(plan, stale=stale)
        return wave

    @engine_thread_only
    def _read_first_tokens(self, wave: _PrefillWave) -> None:
        """The blocking half of an admission wave: wait for its prompt
        programs (and whatever was queued ahead of them), read every
        first token back in one transfer and emit them.  A decode chunk
        dispatched behind the wave meanwhile keeps the device busy."""
        plans, dispatched = wave.plans, wave.dispatched
        if not plans:
            return
        self._beat("prefill_readback", batch=len(plans))
        # the perf split of the one existing sync (see _process_chunks):
        # wait-for-compute (device_s), then the device_get transfer
        # (readback_s)
        handles = [h for _, h in dispatched]
        with self.perf.span("device_wait") as wait:
            jax.block_until_ready(handles)
        with self.perf.span("readback") as read:
            firsts = jax.device_get(handles)  # [(tok, lp)]
        device_s, readback_s = wait.seconds, read.seconds
        for worked, real in self._prompt_rows:
            self.perf.note_prefill_rows(worked, real)
        self._prompt_rows.clear()
        # batched admission costs one combined dispatch+readback: each
        # prefill's flight record and trace get an equal share of it
        share = (time.perf_counter() - wave.start) / len(plans)
        with self.perf.span("emit") as emit:
            delivered = self._emit_first_tokens(
                dispatched, firsts, plans, wave.plan_epochs,
                share, device_s, readback_s,
            )
            emit.note(tokens=delivered)
        self.perf.note_tokens(delivered)

    @engine_thread_only
    def _stage_prefills(self, plans: List[PrefillPlan]):
        """Admission bookkeeping between try_admit and the dispatches
        (one ``schedule`` bracket in _admit_and_dispatch): the stale-wake
        epochs, the flight/trace records, the fault probe, and the
        grouping into batched programs.  Returns ``(plan_epochs,
        chunked plans, {(bucket, cached, unaligned): plans})``."""
        # stale-wake epochs: if a watchdog-declared stall checkpoints
        # (preempt_count bump) and replays these sequences while this
        # thread is stuck in the device_get below, the replay may
        # already be RUNNING again on the NEW core when we wake — a
        # status check alone would pass, so readback also compares the
        # epoch captured here (mirrors the chunked-decode path)
        plan_epochs = {
            id(plan): plan.seq.preempt_count for plan in plans
        }
        if self.flight.enabled:
            for plan in plans:
                seq = plan.seq
                preview = None
                if not self.flight.redact_prompts:
                    try:
                        preview = self.tokenizer.decode(
                            seq.prompt_ids[:32]
                        )
                    except Exception:  # pragma: no cover - defensive
                        preview = None
                self.flight.on_admit(
                    seq, plan.bucket, plan.cached_len, preview=preview
                )
                if seq.trace is not None:
                    seq.trace.end("queue")
                    seq.trace.start(
                        "prefill",
                        bucket=plan.bucket,
                        cached_tokens=plan.cached_len,
                        chunked=plan.chunked,
                    )
        if faults.is_active():
            # fault probe (vgate_tpu/faults.py): payload is the request's
            # ORIGINAL prompt so a poison fault can target one request.
            # Gated so the disarmed hot path never pays the per-plan
            # prompt copy.
            for plan in plans:
                faults.check(
                    "prefill",
                    payload=tuple(
                        plan.seq.prompt_ids[: plan.seq.orig_prompt_len]
                    ),
                )
        # group same-bucket plans into batched dispatches; prefix-cache
        # hits (suffix-only prompt pass) compile a different program and
        # group separately, as do COW hits (unaligned start: the write
        # is a scatter and the suffix table carries an extra column).
        # Chunked plans (prompt > the bucket cap) run serial suffix
        # passes and never batch with others.
        by_bucket: Dict[tuple, List[PrefillPlan]] = {}
        chunked: List[PrefillPlan] = []
        for plan in plans:
            if plan.chunked:
                chunked.append(plan)
                continue
            key = (
                plan.bucket,
                plan.cached_len > 0,
                plan.cached_len % self.geometry.page_size != 0,
            )
            by_bucket.setdefault(key, []).append(plan)
        return plan_epochs, chunked, by_bucket

    @engine_thread_only
    def _emit_first_tokens(
        self, dispatched, firsts, plans, plan_epochs,
        share: float, device_s: float, readback_s: float,
    ) -> int:
        """Fold a prefill wave's first tokens into host state (the
        ``emit`` bracket of _read_first_tokens); returns how many were
        delivered."""
        delivered = 0
        wakes: Dict[Any, None] = {}
        for (group, _), (tokens, lp) in zip(dispatched, firsts):
            self.flight.record_tick(
                "prefill",
                batch=len(group),
                bucket=group[0].bucket,
                step_s=round(share * len(group), 6),
                device_s=round(
                    device_s * len(group) / len(plans), 6
                ),
                readback_s=round(
                    readback_s * len(group) / len(plans), 6
                ),
                kv_used=self.allocator.num_used,
                kv_free=self.allocator.num_free,
                queue_depth=len(self.scheduler.waiting),
            )
            arr = np.asarray(tokens).tolist()
            # append under the readback lock (device waits all happened
            # above): the stale-wake guard is check-then-append, and a
            # watchdog containment folding these sequences mid-loop
            # would otherwise interleave with the appends
            with self._readback_lock:
                for row, plan in enumerate(group):
                    # stale-wake guard: a watchdog-declared stall may
                    # have checkpointed this sequence while the
                    # readback above was stuck — appending its token
                    # now would corrupt the replay (which may already
                    # be RUNNING on the rebuilt core, hence the epoch
                    # check, not just status)
                    if (
                        plan.seq.status is not SeqStatus.RUNNING
                        or plan.seq.preempt_count
                        != plan_epochs[id(plan)]
                    ):
                        continue
                    token = arr[row]
                    self.total_prefills += 1
                    if self.spec.is_mla:
                        self.perf.note_mla_prefill(
                            plan.seq.total_len, plan.cached_len,
                            self.spec.attn_layers,
                        )
                    if self.spec.is_dsa:
                        self.perf.note_dsa_prefill(
                            plan.seq.total_len, plan.cached_len,
                            self.spec.index_layers,
                        )
                    if self.spec.swa_layers:
                        self.perf.note_swa_prefill(
                            plan.seq.total_len, plan.cached_len,
                            self.spec.swa_layers,
                            self._ring_tokens, self.geometry.page_size,
                        )
                    if self.spec.eva_layers:
                        self.perf.note_eva_prefill(
                            plan.seq.total_len, plan.cached_len,
                            self.spec.eva_layers, self.spec.eva_window,
                            self.spec.eva_chunk,
                        )
                    if lp is not None and plan.seq.params.logprobs:
                        self._attach_logprob(plan.seq, lp, 0, row)
                    # a RE-prefill (post-preemption) keeps the original
                    # first_token_t; its phase boundary is NOW, not the
                    # first incarnation's first token
                    fresh_first = plan.seq.first_token_t is None
                    plan.seq.append_token(token)
                    delivered += 1
                    self.flight.on_first_token(plan.seq)
                    tr = plan.seq.trace
                    if tr is not None:
                        boundary = (
                            plan.seq.first_token_t
                            if fresh_first
                            else time.perf_counter()
                        )
                        tr.end("prefill", end_pc=boundary)
                        tr.start("decode", start_pc=boundary)
                    self._maybe_finish(plan.seq, token, wakes)
                    plan.seq.deliver(wakes)
        self._wake_streams(wakes)
        return delivered

    @engine_thread_only
    def _dispatch_swap_in(self, plan: SwapInPlan) -> None:
        """Re-admit a host-swapped preemption victim: scatter its
        parked KV into the freshly-allocated ``seq.pages``
        (runtime/kv_swap.py) and let it rejoin decode at the exact
        position it stopped — token-identical, no prefill program, no
        first-token readback (its last sampled token is the next
        decode feed; ``_build_decode_state`` re-uploads it when the
        membership signature changes this tick)."""
        seq = plan.seq
        t0 = time.perf_counter()
        self._beat("swap_in", batch=1)
        n = self.kv_swap.swap_in_seq(seq, seq.pages)
        self.perf.count(swap_ins=1)
        if self.flight.enabled:
            self.flight.on_admit(
                seq, bucket=0, cached_len=seq.total_len - 1
            )
            # the sequence is mid-decode, not prefilling: flip the
            # phase record straight to decode
            self.flight.on_first_token(seq)
            if seq.trace is not None:
                seq.trace.end("queue")
                seq.trace.start("decode", swapped_in_pages=n)
        self.flight.record_tick(
            "swap_in",
            batch=1,
            pages=n,
            step_s=round(time.perf_counter() - t0, 6),
            kv_used=self.allocator.num_used,
            kv_free=self.allocator.num_free,
            queue_depth=len(self.scheduler.waiting),
            seq_id=seq.seq_id,
            request_id=seq.request_id,
        )

    # what differs between the launches, by the program's name in the
    # compile ledger and on the heartbeat: (family whose variants share
    # one "compiled" set and one vgt_engine_compilations label, span,
    # what makes a new variant).  A chunk of a long prompt runs the
    # suffix program, so its variants are a suffix group's.
    _PROGRAMS = {
        "prefill": ("prefill", "prefill_dispatch", "bucket"),
        "suffix_prefill": ("prefill", "prefill_dispatch", "bucket"),
        "chunked_prefill": ("prefill", "prefill_dispatch", "ctx_width"),
        "decode": ("decode", "decode_dispatch", "chunk_variant"),
        "spec_verify": ("spec_verify", "decode_dispatch", "spec_width"),
    }

    @contextlib.contextmanager
    @engine_thread_only
    def _launch(
        self, program: str, key: tuple, seqs, attention, span_args,
        **fields,
    ):
        """THE bracket around a step program's call: ``with
        self._launch(...): <the jitted call>``.  A variant ``key`` not
        launched before compiles inside the call, so it is counted, put
        on the flight recorder and on the rows' request traces, its
        attention implementation (``attention`` = name, impl()) noted,
        the heartbeat given the compile grace, and the span's seconds
        entered in the compile ledger as the compile's cost.  ``fields``
        (bucket or chunk, batch) go to the heartbeat and those records.

        Yields a dict the call site fills for the device clock
        (observability/perf.py ``DeviceClock.post``): ``output``, ONE
        small array of the launch that no later launch takes as a
        donated argument (never a pool, a ring or a state), and what the
        launch carried.  It is posted once the call has returned."""
        family, span, trigger = self._PROGRAMS[program]
        fresh = (family, key) not in self._compiled
        if fresh:
            self._compiled.add((family, key))
            metrics.RECOMPILES.labels(kind=family).inc()
            self.flight.record_tick("recompile", program=program, **fields)
            name, impl = attention
            self._attention.setdefault(name, set()).add(impl())
            for seq in seqs:
                if seq.trace is not None:
                    seq.trace.event("xla_compile", **fields)
        self._beat(program, compiling=fresh, **fields)
        # the jitted call's return is trace+enqueue; a fresh variant's
        # call also compiles synchronously, so its duration IS the
        # compile cost the ledger records
        posted: Dict[str, Any] = {}
        with self.perf.span(span, span_args) as disp:
            yield posted
        if fresh:
            self.perf.record_compile(
                program, key, disp.seconds, trigger=trigger
            )
        if posted and self.perf.enabled:
            self.perf.device.post(
                program, disp.t0,
                # the step-time histogram's exemplar: the first traced row
                trace_id=next(
                    (
                        s.trace.trace_id for s in seqs
                        if s.trace is not None and s.trace.trace_id
                    ),
                    None,
                ),
                **posted,
            )

    @engine_thread_only
    def _sampling_rows(self, B: int, rows) -> Dict[str, Any]:
        """The step programs' per-row sampling arguments, as device
        arrays under the programs' own argument names, from ``rows`` =
        (row index, Sequence) pairs in ONE pass; rows not named keep the
        padding defaults (temperature 0, top_p 1, top_k 0, no seed, step
        0).  The three optional groups are None unless a row needs them,
        so that a batch without them runs the program variant without:

        * ``counts`` [B, V] uint16 with ``freq_pens`` / ``pres_pens``:
          only when a penalised row has generated tokens (a decoding row
          always has; a fresh prompt's all-zero histogram is a no-op, a
          re-prefill after a preemption still counts what it folded);
        * ``min_toks`` [B] with ``stop_id_mat`` [B, K]: a floor row's
          stop set is the model's plus its request's ``stop_token_ids``;
        * ``bias_ids`` / ``bias_vals`` [B, K]: ``logit_bias``.

        Both K bucket to a power of two (bounded variant count) and pad
        with an out-of-vocab id, which no vocabulary position equals (the
        edits of ops/sampling.py compare, or past COMPARE_MAX_IDS ids a
        row scatter, and a scatter drops it).  With them the
        two static arguments the rows decide, ``num_logprobs`` (a row
        asked for them) and ``all_greedy`` (no row samples and none wants
        logprobs: the decode and verify programs' argmax variant), and
        ``variant``: what of all this forks a compiled program, the
        part of a variant key the rows decide (penalties, width of the
        stop-id matrix, logprobs, argmax, width of the bias matrix)."""
        temps = np.zeros((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.full((B,), -1, np.int32)
        steps = np.zeros((B,), np.int32)
        rows = list(rows)
        penalised = want_lp = sampled = False
        floors: list = []
        biased: list = []
        for row, seq in rows:
            sp = seq.params
            temps[row] = sp.temperature
            top_ps[row] = sp.top_p
            top_ks[row] = sp.top_k
            if sp.seed is not None:
                # token i always draws from (seed, i): a prompt pass
                # samples token index num_generated (0 fresh, >0 after
                # a preemption)
                seeds[row] = sp.seed
            steps[row] = seq.num_generated
            if sp.temperature != 0.0:
                sampled = True
            if sp.logprobs:
                want_lp = True
            if sp.has_penalties and seq.generated_ids:
                penalised = True
            if sp.min_tokens > 0:
                # only floor rows ever have their ids applied, so only
                # they size K (a zero-floor neighbour with many
                # stop_token_ids must not fork extra compiled variants)
                floors.append((row, sp))
            if sp.logit_bias:
                biased.append((row, sp.logit_bias))
        out: Dict[str, Any] = {
            "temps": jnp.asarray(temps),
            "top_ps": jnp.asarray(top_ps),
            "top_ks": jnp.asarray(top_ks),
            "seeds": jnp.asarray(seeds),
            "steps": jnp.asarray(steps),
            "counts": None, "freq_pens": None, "pres_pens": None,
            "min_toks": None, "stop_id_mat": None,
            "bias_ids": None, "bias_vals": None,
            "num_logprobs": LOGPROBS_K if want_lp else 0,
            "all_greedy": not (want_lp or sampled),
        }
        V = self.spec.vocab_size
        mt_width = lb_width = None
        if penalised:
            counts = np.zeros((B, V), np.uint16)
            freq = np.zeros((B,), np.float32)
            pres = np.zeros((B,), np.float32)
            for row, seq in rows:
                freq[row] = seq.params.frequency_penalty
                pres[row] = seq.params.presence_penalty
                if seq.generated_ids:
                    # histogram over everything generated (generated_ids
                    # survives preemption folds, matching OpenAI's
                    # "tokens generated so far")
                    np.add.at(
                        counts[row],
                        np.asarray(seq.generated_ids, np.int64), 1,
                    )
            out["counts"] = jnp.asarray(counts)
            out["freq_pens"] = jnp.asarray(freq)
            out["pres_pens"] = jnp.asarray(pres)
        if floors:
            base = [self.tokenizer.eos_id, *self.spec.extra_stop_ids]
            stops = [
                base + list(sp.stop_token_ids or []) for _, sp in floors
            ]
            mt_width = 1 << (max(map(len, stops)) - 1).bit_length()
            mat = np.full((B, mt_width), V, np.int32)
            min_toks = np.zeros((B,), np.int32)
            for (row, sp), stop_ids in zip(floors, stops):
                mat[row, : len(stop_ids)] = stop_ids
                min_toks[row] = sp.min_tokens
            out["min_toks"] = jnp.asarray(min_toks)
            out["stop_id_mat"] = jnp.asarray(mat)
        if biased:
            lb_width = 1 << (
                max(len(b) for _, b in biased) - 1
            ).bit_length()
            ids = np.full((B, lb_width), V, np.int32)
            vals = np.zeros((B, lb_width), np.float32)
            for row, items in biased:
                for j, (tid, b) in enumerate(sorted(items.items())):
                    ids[row, j] = tid
                    vals[row, j] = b
            out["bias_ids"] = jnp.asarray(ids)
            out["bias_vals"] = jnp.asarray(vals)
        out["variant"] = (
            penalised, mt_width, out["num_logprobs"], out["all_greedy"],
            lb_width,
        )
        return out

    @engine_thread_only
    def _dispatch_prompt(
        self, plans: List[PrefillPlan], bucket: int, cached: bool = False,
        unaligned: bool = False, upto: Optional[int] = None,
    ):
        """Launch ONE prompt-pass program for up to prefill_batch_max
        plans whose uncached lengths share ``bucket``; returns the
        (async) result ``(first tokens [B], logprob triple or None)``.
        B pads to a power of two so the compile ladder stays small
        ({1,2,4,...,prefill_batch_max} x buckets); padding rows use
        trash page tables, temperature 0 and length 1, and their sampled
        tokens are discarded at readback.  Three layouts:

        * fresh prompts (``cached`` False): ``_prefill_step`` over the
          bucket's page table;
        * prefix-cache hits: ``_suffix_prefill_step`` over the uncached
          suffix.  The cached prefix pages are read-only shared KV; only
          the suffix pages are written, and the pass attends the whole
          context through a second table.  ``unaligned`` is the
          copy-on-write group: each plan's page copy is dispatched first
          (device program order guarantees the copy reads the source
          before any later program could reuse it), the suffix then
          starts mid-page and its table carries one extra column;
        * a non-final chunk of a long prompt (``upto``: the one plan's
          pass stops at that prompt position): the suffix program again,
          with the padding row's sampling and no extras, since its
          sample is discarded."""
        B = 1 << (len(plans) - 1).bit_length()  # next power of two
        ps = self.geometry.page_size
        n_own_pages = bucket // ps + (1 if unaligned else 0)
        # tokens a page of a sequence stands for (ps, but for a spec
        # whose pool row is several tokens)
        pt = self.geometry.page_tokens
        seqs = [plan.seq for plan in plans]
        with self.perf.span("state", lambda: {"rows": B}):
            # copy-on-write: duplicate the shared head of each diverging
            # page into the sequence's own first page BEFORE the suffix
            # program that writes the rest of that page
            for plan in plans:
                if plan.cow is not None:
                    src, dst, n_shared = plan.cow
                    self.k_pages, self.v_pages = _cow_copy_pages(
                        self.k_pages, self.v_pages,
                        jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32),
                        jnp.asarray(n_shared, jnp.int32),
                    )
                    if self.radix_cache is not None:
                        self.radix_cache.total_cow_copies += 1
                    metrics.PREFIX_COW_COPIES.inc()
            ends = [upto or seq.num_prompt_tokens for seq in seqs]
            # context window bucketed to a power of two of pages: bounds
            # both the KV gather and the compile-variant count
            ctx_pages = min(
                self.geometry.pages_per_seq,
                1 << max(0, max(cdiv(end, pt) for end in ends) - 1)
                .bit_length(),
            )
            tokens = np.zeros((B, bucket), np.int32)
            prefix_lens = np.zeros((B,), np.int32)
            lens = np.ones((B,), np.int32)
            own_pt = np.zeros((B, n_own_pages), np.int32)
            ctx_pt = np.zeros((B, ctx_pages), np.int32)
            # row -> decode slot, for a spec with recurrent layers;
            # padding rows point past the state (their update is dropped)
            slots = np.full((B,), self.max_slots, np.int32)
            for row, (plan, end) in enumerate(zip(plans, ends)):
                seq = plan.seq
                part = seq.prompt_ids[plan.cached_len : end]
                tokens[row, : len(part)] = part
                prefix_lens[row] = plan.cached_len
                lens[row] = len(part)
                own = seq.pages[plan.cached_len // pt :]
                own_pt[row, : len(own)] = own[:n_own_pages]
                # decode-side page table row: real pages then trash
                # padding
                slot_row = self._page_tables_np[plan.slot]
                slot_row[:] = 0
                slot_row[: len(seq.pages)] = seq.pages
                ctx_pt[row, : len(seq.pages)] = seq.pages[:ctx_pages]
                slots[row] = plan.slot
            # the program's keyword arguments: the sampling rows' and,
            # below, the layout's
            kw = self._sampling_rows(
                B, enumerate(seqs) if upto is None else ()
            )
            temps, top_ps, top_ks = (
                kw.pop(k) for k in ("temps", "top_ps", "top_ks")
            )
            # a prompt program has no argmax variant (_sample_first)
            pen, mt_width, num_lp, _, lb_width = kw.pop("variant")
            del kw["all_greedy"]
        # what the layouts differ in
        if cached:
            program = "suffix_prefill" if upto is None else "chunked_prefill"
            key = ("suffix", bucket, B, ctx_pages, pen, mt_width, num_lp,
                   lb_width, unaligned)
            step, mesh = _suffix_prefill_step, self._mt_mesh
            lens_args, tables = (prefix_lens, lens), (own_pt, ctx_pt)
            kw["unaligned"] = unaligned
            attention = (
                "suffix_cow" if unaligned else "suffix",
                lambda: multitok_attention_impl(
                    self.use_pallas, mesh, rows=bucket, unaligned=unaligned,
                    latent=self.spec.rows_cache,
                    group=packed_group(self.spec),
                ),
            )
        else:
            program = "prefill"
            key = (bucket, B, pen, mt_width, num_lp, lb_width)
            step, mesh = _prefill_step, self._attn_mesh
            lens_args, tables = (lens,), (own_pt,)
            attention = ("prefill", lambda: prefill_attention_impl(
                self.spec, self.use_pallas, mesh
            ))
        real = int(lens[: len(plans)].sum())
        with self._launch(
            program, key, seqs, attention,
            lambda: {
                "program": program, "bucket": bucket, "rows": B,
                "ctx_tokens": sum(ends),
            },
            bucket=bucket, batch=B,
        ) as posted:
            out, *cache = step(
                self.params, self.spec, jnp.asarray(tokens),
                *map(jnp.asarray, lens_args),
                self.k_pages, self.v_pages,
                *map(jnp.asarray, tables),
                temps, top_ps, top_ks, self._step_key(),
                mesh=mesh, use_pallas=self.use_pallas,
                **kw, **self._state_args(slots),
            )
            self._set_cache(cache)
            posted.update(
                output=out[0], prompt_tokens=real, rows=B, bucket=bucket
            )
        self.perf.count(prompt_programs=1, prompt_tokens=real)
        if not cached and attention[1]() == "pallas":
            self.perf.note_prefill_attn(
                *prefill_attn_tiles(self.spec, bucket, lens))
        # the rows the program works on, by the model layer's own rule
        arrays, rows = prompt_rows(
            self.spec, bucket, lens,
            whole=not cached and attention[1]() not in WHOLE_BUCKET_IMPLS)
        self._prompt_rows.append((arrays * int(rows), real))
        return out

    @engine_thread_only
    def _dispatch_chunked_prefill(self, plan: PrefillPlan):
        """Serial chunked prefill for a (suffix-)prompt longer than the
        bucket cap (scheduler.prefill_chunk): page-aligned passes of up
        to ``plan.bucket`` tokens through the suffix-prefill program,
        each attending the full resident context.  Long prompts never
        compile a max_model_len-wide program — an 8k prompt at a 1k cap
        is eight dispatches of the SAME compiled 1k-suffix program.
        Only the final chunk's sampled token is real and only it carries
        the request's sampling surface: it is exactly a one-row suffix
        group.  Returns the (async) ([1] tokens, lp) handle of the final
        chunk."""
        seq = plan.seq
        chunk = plan.bucket  # page-aligned (scheduler buckets are)
        total = seq.num_prompt_tokens
        start = plan.cached_len  # page-aligned (full cached pages)

        def part(bucket: int) -> List[PrefillPlan]:
            return [PrefillPlan(
                seq=seq, slot=plan.slot, bucket=bucket, cached_len=start,
                register_hashes=None,
            )]

        while total - start > chunk:
            self._dispatch_prompt(
                part(chunk), chunk, cached=True, upto=start + chunk
            )
            start += chunk
        last = bucket_for(total - start, self.scheduler.prefill_buckets)
        return self._dispatch_prompt(part(last), last, cached=True)

    # ------------------------------------------------------------- decode

    @engine_thread_only
    def _decode_signature(self, seqs: List[Sequence]):
        """Cheap membership signature: when unchanged, every device input
        except tokens/positions/counter (which flow device→device) is
        reusable, so chunks can be dispatched without any host upload.

        ``preempt_count`` is part of the identity: a victim re-admitted
        into the same freed slot with the same page count must NOT match
        the pre-preemption cache — its device tokens/positions are stale
        (the re-prefill's first sampled token was never fed to decode).
        """
        return tuple(
            (seq.seq_id, seq.slot, seq.preempt_count, len(seq.pages))
            for seq in seqs
        )

    @engine_thread_only
    def _invalidate_decode_state(self, reason: str) -> None:
        """Host state moved behind the device decode state's back (rows
        folded, staged or evacuated; speculative rounds advanced
        positions): the next decode dispatch drains and rebuilds
        whatever the membership looks like, and names ``reason``."""
        self._decode_signature_cache = None
        self._stale_reason = reason

    @engine_thread_only
    def _drain_reason(
        self, active: List[Sequence], wave: _PrefillWave
    ) -> Optional[str]:
        """Why this change of the decode batch's membership has to drain
        the pipeline and rebuild the device state from host state, or
        None for a PLAIN change, which edits the state's rows instead
        (``_join_and_dispatch``): every row that left ended at a
        readback (stop or length), every row that came is a row of one
        of this wave's prompt programs, the others are who and where
        they were, and no row carries a penalty histogram.  Read off
        what the tick sees; the program variant is compared where the
        sampling rows are built."""
        state = self._dec_state
        if state is None or self._decode_signature_cache is None:
            return self._stale_reason or "initial"
        if wave.swapped:
            return "swap_in"
        if wave.chunked:
            return "chunked_prefill"
        known = {sig[0]: sig[1:3] for sig in self._decode_signature_cache}
        joined = {id(plan.seq) for plan in wave.plans}
        for seq in active:
            where = known.get(seq.seq_id)
            if where is None:
                if id(seq) not in joined:
                    # admitted by an earlier tick that dispatched no
                    # chunk: its first token is host state by now
                    return "late_join"
            elif where != (seq.slot, seq.preempt_count):
                return "preempt"
            if seq.params.has_penalties:
                return "penalties"
        live = {id(seq) for seq in active}
        for seq in state["members"]:
            if id(seq) in live:
                continue
            if seq.status is SeqStatus.FAILED:
                return "failed"  # deadline, sentinel, KV capacity
            if seq.status is not SeqStatus.FINISHED:
                return "preempt"  # or held for a handoff, evacuated
            if seq.finish_reason == "abort":
                return "abort"
        return None

    @engine_thread_only
    def _join_and_dispatch(
        self, active: List[Sequence], wave: _PrefillWave
    ) -> Optional[str]:
        """A plain membership change (``_drain_reason`` None): edit the
        device decode state's rows and dispatch the next chunk, waiting
        on nothing from the device.  Rows that ended are switched off; a
        row that joined takes its first token from its prompt program's
        DEVICE output and its position and step index from the host
        (``_join_decode_rows``, behind the prompt programs in device
        order); the page tables, the active mask and the sampling rows
        are re-uploaded whole.  Surviving rows keep their device tokens,
        positions and step counts, so the chunks in flight stay in
        flight: each is folded by the ``(seq, epoch)`` list it was
        dispatched with.

        Returns None with the chunk dispatched, or the reason the change
        was not plain after all (the pool ran dry, the rows need another
        program variant, nothing in flight and no budget to step):
        nothing was dispatched then and the caller drains and rebuilds."""
        state = self._dec_state
        B = self.max_slots
        with self.perf.span("schedule"):
            in_flight = sum(c[1] for c in self._pending_chunks)
            chunk = self._pick_chunk(active, lead=in_flight)
            # a joiner's first token is not in host state yet, so its
            # K/V writes start one position past what total_len says
            horizon = in_flight + chunk + (1 if wave.plans else 0)
            if chunk and (
                not self.scheduler.prepare_decode(active, horizon=horizon)
                or any(s.status is not SeqStatus.RUNNING for s in active)
            ):
                return "preempt"
        if chunk == 0:
            # the steps in flight cover every budget (a joiner's is its
            # first token then): nothing to edit for, the change stands
            # for the next tick and the oldest chunk is folded meanwhile
            if not self._pending_chunks:
                return "budget"
            self._process_chunks()
            return None
        with self.perf.span(
            "state",
            lambda: {"rows": len(active), "joined": len(wave.plans)},
        ):
            rows = self._sampling_rows(B, ((s.slot, s) for s in active))
            if rows["variant"] != state["variant"]:
                return "variant"
            self._refresh_page_tables(active)
            live = np.zeros((B,), bool)
            live[[seq.slot for seq in active]] = True
            carried = (state["tokens"], state["positions"], state["steps"])
            for group, (first_tokens, _) in wave.dispatched:
                n = first_tokens.shape[0]
                slots = np.full((n,), B, np.int32)  # padding: dropped
                positions = np.zeros((n,), np.int32)
                steps = np.zeros((n,), np.int32)
                for row, plan in enumerate(group):
                    seq = plan.seq
                    slots[row] = plan.slot
                    positions[row] = seq.total_len
                    steps[row] = seq.num_generated + 1
                carried = _join_decode_rows(
                    *carried, slots, first_tokens, positions, steps
                )
            # survivors keep the device's step counts; nothing here has
            # a histogram, so the penalty rows stay the state's zeros
            for name in ("steps", "counts", "freq_pens", "pres_pens"):
                del rows[name]
            state.update(rows)
            state["tokens"], state["positions"], state["steps"] = carried
            state["active"] = jnp.asarray(live)
            state["counter"] = self._carried(np.uint32(self._step_counter))
            state["members"] = list(active)
        self._decode_signature_cache = self._decode_signature(active)
        self._dispatch_chunk(active, chunk)
        return None

    @engine_thread_only
    def _build_decode_state(
        self, seqs: List[Sequence], reason: str = "initial"
    ) -> None:
        """The decode state from HOST state, every row's last token and
        position included: only current once every chunk in flight has
        been folded, so the caller drained the pipeline first.
        ``reason`` (``_drain_reason``) says in the trace why this
        membership change could not edit the state's rows instead."""
        self.total_state_rebuilds += 1
        self._stale_reason = None
        B = self.max_slots
        with self.perf.span(
            "state", lambda: {"rows": len(seqs), "reason": reason}
        ):
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            for seq in seqs:
                slot = seq.slot
                assert slot is not None
                row = self._page_tables_np[slot]
                row[:] = 0
                row[: len(seq.pages)] = seq.pages
                tokens[slot] = seq.output_ids[-1]
                positions[slot] = seq.total_len - 1
                active[slot] = True
            self._dec_state = state = {
                "tokens": self._carried(tokens),
                "positions": self._carried(positions),
                "page_tables": self._page_tables_upload(),
                "active": jnp.asarray(active),
                "counter": self._carried(np.uint32(self._step_counter)),
                **self._sampling_rows(B, ((s.slot, s) for s in seqs)),
                # whose rows these are (_drain_reason tells a stream
                # that ended from one that was taken away)
                "members": list(seqs),
            }
            state["steps"] = self._carried(state["steps"])
            if state["counts"] is None:
                # the chunk program takes the two penalty rows whether
                # or not a histogram rides with them
                state["freq_pens"] = state["pres_pens"] = jnp.zeros((B,))

    @engine_thread_only
    def _page_tables_upload(self):
        """The decode state's page tables as a device array of its own:
        a COPY.  On a CPU ``jnp.asarray`` may alias the host buffer,
        which the next prompt dispatch rewrites in place for a slot's
        new tenant while a chunk that still steps the old one (its
        overshoot) is in flight and must keep the table it was given."""
        return jnp.asarray(self._page_tables_np.copy())

    @engine_thread_only
    def _refresh_page_tables(self, seqs: List[Sequence]) -> None:
        """Re-upload ONLY the page tables: after in-place page growth (same
        sequences, same slots), and as part of a membership change's row
        edit.  In-flight chunks keep their older table, which is valid:
        the new page is only addressed at positions those chunks never
        reach."""
        state = self._dec_state
        assert state is not None
        for seq in seqs:
            row = self._page_tables_np[seq.slot]
            row[:] = 0
            row[: len(seq.pages)] = seq.pages
        state["page_tables"] = self._page_tables_upload()

    @engine_thread_only
    def _pick_chunk(self, active: List[Sequence], lead: int = 0) -> int:
        """Chunk length for the next dispatch: the largest power of two that
        neither exceeds ``decode_chunk`` nor overshoots every sequence's
        remaining budget (``lead`` = steps already in flight but not yet
        folded into host state).  Powers of two bound how many chunk-length
        program variants XLA ever compiles.

        Admission pressure: when prompts are WAITING and a free slot
        exists, the chunk caps at decode_chunk/8 so the loop returns to
        admission within a fraction of a full chunk — a mid-serving
        arrival's TTFT is then bounded by a short chunk, not up to
        ``decode_pipeline`` full ones.  With no free slot (or an empty
        queue) full-size chunks keep throughput maximal.  A stream's end
        is found at a tick's END (the readback) and its slot filled at
        the head of the next, before the pick: at saturation no slot is
        free here, and the rule fires for a burst larger than
        ``prefill_admit_limit`` or for arrivals into free slots, where
        it is meant to.  Only a change that drains the pipeline
        (``_drain_reason``) reads chunks back just before the pick and
        can free a slot there."""
        max_len = self.config.model.max_model_len
        headroom = 0
        for seq in active:
            rem_tokens = max(1, seq.params.max_tokens) - seq.num_generated
            rem_len = max_len - seq.total_len
            # a row that joined behind its prompt program this tick has
            # no step in flight, and its first token not in host state
            ahead = lead if seq.output_ids else 1
            headroom = max(headroom, min(rem_tokens, rem_len) - ahead)
        if headroom <= 0:
            # in-flight steps already cover every budget: dispatching more
            # would be pure overshoot (possible only when lead > 0; a
            # sequence with zero remaining budget is finished at readback)
            return 0
        headroom = min(self.decode_chunk, headroom)
        if self.scheduler.has_admissible_waiting():
            headroom = min(headroom, max(1, self.decode_chunk // 8))
        return 1 << (headroom.bit_length() - 1)

    def _decode_kv_write(self) -> str:
        """Who writes a decode step's K/V, as ``decode_forward`` traces
        it for this engine: "kernel" or "scatter"."""
        return decode_kv_write(
            self.spec, self.use_pallas, self._attn_mesh, self._kv_quant
        )

    def _decode_head(self, variant: tuple, rows: int) -> str:
        """What ends a step of the decode chunk this variant compiles,
        as ``_decode_chunk`` traces it: "fused" (the logits stay on the
        chip) or "logits"."""
        penalised, mt_width, num_lp, all_greedy, lb_width = variant
        return decode_head_impl(
            self.params, self.spec, self.use_pallas, self._attn_mesh,
            rows=rows, all_greedy=all_greedy, num_logprobs=num_lp,
            penalised=penalised, bias_width=lb_width or 0,
            stop_width=mt_width or 0,
        )

    @property
    def _dsa_fetch_chunk(self) -> int:
        """Picks a loop trip of the decode kernel under a selection
        fetches, a pair of token rows each (0: the jnp twin gathers the
        picked rows alone): /debug/perf -> totals.dsa.rows_fetched."""
        from vgate_tpu.ops.pallas.dsa import fetch_chunk

        kernel = decode_attention_impl(
            self.spec, self.use_pallas, self._attn_mesh) == "pallas"
        context = self.geometry.pages_per_seq * self.geometry.page_size
        return fetch_chunk(min(self.spec.index_topk, context)) if kernel else 0

    @engine_thread_only
    def _dispatch_chunk(self, active: List[Sequence], chunk: int) -> None:
        faults.check("decode_step")
        state = self._dec_state
        chunk_key = (chunk, *state["variant"])
        guard = (
            self.integrity is not None and self.integrity.guard_enabled
        )
        head = self._decode_head(
            state["variant"], int(state["tokens"].shape[0]))
        with self._launch(
            "decode", chunk_key, active,
            ("decode", lambda: decode_attention_impl(
                self.spec, self.use_pallas, self._attn_mesh
            )),
            lambda: {
                "program": "decode", "steps": chunk, "rows": len(active),
                "ctx_tokens": sum(s.total_len for s in active),
                # steps in flight that ctx_tokens does not hold yet
                "lead": sum(c[1] for c in self._pending_chunks),
                "kv_write": self._decode_kv_write(),
                # what ends a step: "fused", its logits never an array
                # in HBM (models/decoder.py greedy_head), or "logits"
                "head": head,
                # latent rows a step attends to under a learned
                # selection, over the rows (a layer's; at dispatch)
                **({"sel_rows": sum(
                    min(s.total_len, self.spec.index_topk)
                    for s in active)} if self.spec.is_dsa else {}),
            },
            chunk=chunk, batch=len(active),
        ) as posted:
            (
                chunk_tokens,
                chunk_lp,
                state["tokens"],
                state["positions"],
                state["counter"],
                state["steps"],
                state["counts"],
                self.k_pages,
                self.v_pages,
                chunk_flags,
                *more,
            ) = _decode_chunk(
                self.params,
                self.spec,
                state["tokens"],
                state["positions"],
                self.k_pages,
                self.v_pages,
                state["page_tables"],
                state["active"],
                state["temps"],
                state["top_ps"],
                state["top_ks"],
                self._base_key,
                state["counter"],
                num_steps=chunk,
                use_pallas=self.use_pallas,
                max_position=self.config.model.max_model_len - 1,
                seeds=state["seeds"],
                steps=state["steps"],
                mesh=self._attn_mesh,
                num_logprobs=state["num_logprobs"],
                counts=state["counts"],
                freq_pens=state["freq_pens"],
                pres_pens=state["pres_pens"],
                min_toks=state["min_toks"],
                stop_id_mat=state["stop_id_mat"],
                all_greedy=state["all_greedy"],
                bias_ids=state["bias_ids"],
                bias_vals=state["bias_vals"],
                guard=guard,
                guard_threshold=(
                    self.config.integrity.saturate_threshold
                    if guard else 1.0e4
                ),
                **self._state_args(),
            )
            # a spec with recurrent layers: the state, and the expert
            # layers' counters [chunk, 4], read back with the tokens
            moe_stats = None
            if more:
                self.state, moe_stats = more
            posted.update(
                output=chunk_tokens, steps=chunk, rows=len(active)
            )
        self._step_counter += chunk
        self.perf.count(decode_steps=chunk)
        # snapshot preempt_count as an epoch: a sequence preempted while
        # this chunk is in flight (and possibly re-admitted before the
        # readback is processed) must NOT receive the stale tokens
        self._pending_chunks.append(
            ([(s, s.preempt_count) for s in active], chunk, chunk_tokens,
             chunk_lp, chunk_flags, moe_stats, head == "fused")
        )

    @engine_thread_only
    def _process_chunks(self, drain: bool = False) -> None:
        """Fold the oldest in-flight chunk (all of them when ``drain``) into
        host state: append tokens in order, detect EOS/length stops, discard
        steps past a stop."""
        while self._pending_chunks:
            (seqs, chunk, tokens_dev, lp_dev, flags_dev, moe_dev,
             fused_head) = self._pending_chunks.pop(0)
            # observe only the host-blocking readback time (kind="decode"):
            # dispatch-to-now would double-count deliberate pipeline
            # queueing when more than one chunk is in flight
            self._beat("decode_readback", chunk=chunk, batch=len(seqs))
            # perf attribution splits the ONE sync this path already
            # had: block_until_ready is the wait-for-compute share
            # (device_s), the asarray transfers after it (readback_s) —
            # no sync is added the np.asarray would not have paid
            with self.perf.span("device_wait") as wait:
                jax.block_until_ready(tokens_dev)
            with self.perf.span("readback") as read:
                sampled = np.asarray(tokens_dev)  # [chunk, B]
                sampled = faults.corrupt_array("decode_step", sampled)
                lp_np = (
                    None
                    if lp_dev is None
                    else tuple(np.asarray(a) for a in lp_dev)
                )
                if moe_dev is not None:
                    # the expert layers' counters of these steps, and
                    # the state rows each step updated: one readback
                    # with the tokens (engine thread, once per chunk)
                    self.perf.note_moe(
                        np.asarray(moe_dev), rows=len(seqs),
                        moe_layers=self.spec.moe_layers,
                        linear_layers=self.spec.recurrent_layers,
                    )
                if self.spec.is_mla:
                    self.perf.note_mla_decode(
                        steps=chunk, rows=len(seqs),
                        ctx_tokens=sum(s.total_len for s, _ in seqs),
                        layers=self.spec.attn_layers,
                    )
                if self.spec.is_dsa:
                    self.perf.note_dsa_decode(
                        steps=chunk,
                        lens=[s.total_len for s, _ in seqs],
                        layers=self.spec.attn_layers,
                        index_layers=self.spec.index_layers,
                        topk=self.spec.index_topk,
                        fetch_chunk=self._dsa_fetch_chunk,
                        pair_tokens=1 if self.spec.kv_rows else 2,
                    )
                if self.spec.swa_layers:
                    self.perf.note_swa_decode(
                        steps=chunk,
                        lens=[s.total_len for s, _ in seqs],
                        layers=self.spec.swa_layers,
                        window=self.spec.sliding_window,
                    )
                if self.spec.eva_layers:
                    self.perf.note_eva_decode(
                        steps=chunk,
                        lens=[s.total_len for s, _ in seqs],
                        layers=self.spec.eva_layers,
                        window=self.spec.eva_window,
                        chunk=self.spec.eva_chunk,
                    )
            device_s = wait.seconds
            block_s = device_s + read.seconds
            if self.perf.enabled:
                self.perf.note_decode(
                    steps=chunk,
                    ctx_tokens=sum(s.total_len for s, _ in seqs),
                    device_s=device_s, fused_head=fused_head,
                )
            if self.integrity is not None and flags_dev is not None:
                # the flags readback + fault hooks stay OUTSIDE the
                # lock (np.asarray blocks on the device)
                flags_np = np.bitwise_or.reduce(
                    np.asarray(flags_dev), axis=0
                )
                faults.check("logit_corrupt")
                flags_np = faults.corrupt_array(
                    "logit_corrupt", flags_np
                )
            else:
                flags_np = None
            if self.integrity is not None:
                # sentinel scan BEFORE any append/stream — a HARD trip
                # discards this whole chunk (the entry is already
                # popped; containment clears the rest) so no token
                # sampled from corrupt logits ever reaches a client;
                # SOFT trips (entropy collapse) fail only the
                # attributed sequence, whose FAILED status then skips
                # it in the append loop below.  Under _readback_lock
                # like the append loop: the status/epoch snapshot and
                # the fail/residency-release must not interleave with a
                # cross-thread containment fold (watchdog, dp canary)
                # or a sequence could be checkpointed for replay AND
                # settled failed at once.
                with self._readback_lock:
                    live_rows = [
                        (s, s.slot)
                        for s, epoch in seqs
                        if s.status is SeqStatus.RUNNING
                        and s.preempt_count == epoch
                    ]
                    for _kind, seq, soft_exc in (
                        self.integrity.scan_decode(
                            sampled, flags_np, live_rows, chunk
                        )
                    ):
                        self.scheduler.fail_sequence(seq, soft_exc)
            self._record_decode_step(
                "decode", [s for s, _ in seqs], chunk,
                block_s, device_s, read.seconds,
            )
            # append under the readback lock (the blocking np.asarray
            # is above): see _emit_first_tokens — the epoch guard is
            # check-then-append, and containment's fold must not
            # interleave with it
            delivered = 0
            wakes: Dict[Any, None] = {}
            with self.perf.span("emit") as emit:
                # one host copy of the chunk, a sequence's steps side
                # by side: rows[slot] is its column as ints
                rows = sampled[:chunk].T.tolist()
                with self._readback_lock:
                    for seq, epoch in seqs:
                        if (
                            seq.status is not SeqStatus.RUNNING
                            or seq.preempt_count != epoch
                        ):
                            continue  # stopped or preempted since dispatch
                        slot = seq.slot
                        want_lp = (
                            lp_np is not None and seq.params.logprobs
                        )
                        for k, token in enumerate(rows[slot]):
                            if want_lp:
                                self._attach_logprob(seq, lp_np, k, slot)
                            seq.append_token(token)
                            delivered += 1
                            self._maybe_finish(seq, token, wakes)
                            if seq.status is not SeqStatus.RUNNING:
                                break
                        # the readback's tokens as ONE list (a sequence
                        # it finished took them with its end notice)
                        seq.deliver(wakes)
                # one cross-thread wake-up per consumer for the whole
                # readback, outside the lock
                self._wake_streams(wakes)
                self.total_decode_tokens += delivered
                emit.note(tokens=delivered)
            self.perf.note_tokens(delivered)
            if delivered:
                self._note_delivery(chunk, len(seqs))
            self.total_steps += chunk
            if not drain:
                break

    @engine_thread_only
    def _note_delivery(self, steps: int, rows: int) -> None:
        """A decode readback handed tokens to running streams: close
        the delivery gap (observability/perf.py).  A gap long enough to
        be a PAUSE comes back as a record with its one cause: it goes
        on the flight recorder (so /debug/flight and a crash snapshot
        hold it) and, unless an admission wave made it, into the log."""
        pause = self.perf.note_delivery(
            steps, rows,
            queue_depth=len(self.scheduler.waiting),
            preemptions=self.scheduler.total_preemptions,
        )
        if pause is None:
            return
        self.flight.record_tick("pause", **pause)
        if pause["cause"] != "prefill":
            logger.warning("engine_pause", extra={"extra_data": pause})

    @engine_thread_only
    def _record_decode_step(
        self, kind: str, seqs: List[Sequence], chunk: int,
        step_s: float, device_s: float, readback_s: float,
    ) -> None:
        """A decode readback's flight-recorder tick (chunk or verify
        round), with the HOST's times for it; the device's seconds on
        the launch are the device clock's, which feeds the step-time
        histogram (observability/perf.py DeviceClock)."""
        self.flight.record_tick(
            kind,
            batch=len(seqs),
            chunk=chunk,
            step_s=round(step_s, 6),
            device_s=round(device_s, 6),
            readback_s=round(readback_s, 6),
            kv_used=self.allocator.num_used,
            kv_free=self.allocator.num_free,
            queue_depth=len(self.scheduler.waiting),
            **self._ring_tick(),
        )

    # --------------------------------------------------------- speculative

    @engine_thread_only
    def _ngram_drafter(self, seq: Sequence, k: int) -> List[int]:
        from vgate_tpu.runtime.speculative import NgramIndex

        index = getattr(seq, "_ngram_index", None)
        if index is None or index.ngram != self.spec_ngram:
            index = NgramIndex(self.spec_ngram)
            seq._ngram_index = index  # incremental; dies with the seq
        return index.draft(seq.prompt_ids + seq.output_ids, k)

    @engine_thread_only
    def _tick_speculative(self) -> bool:
        """One speculative decode round (tpu.speculative_k > 0): draft up
        to k tokens per greedy sequence from its own history, verify all
        of them in ONE forward, and append the accepted run + the model's
        bonus token.  Per round each sequence advances by 1..k+1 tokens at
        the cost of a single dispatch; with zero drafts the round is
        exactly a decode step (runtime/speculative.py for the contract).

        Host-driven (no device-resident chaining, no chunk pipeline):
        acceptance counts are data-dependent, so positions feed back
        through the host each round.  That trade targets single-stream
        latency on local hardware; high-RTT links prefer chunked decode.
        """
        active = self._running_seqs()
        if not active:
            self.perf.clear_delivery_clock()
            return False
        S = self.spec_k + 1
        if not self.scheduler.prepare_decode(active, horizon=S):
            return True  # preemption changed membership; retry next tick
        active = self._running_seqs()
        if not active:
            return True
        B = self.max_slots
        max_len = self.config.model.max_model_len
        if (
            self.draft_model is not None
            and self.draft_model.total_draft_calls == 0
        ):
            # the drafter's lazily-jitted scan compiles on its FIRST
            # call (inside the array-build loop below) — beat with the
            # compile grace or the watchdog would judge a multi-minute
            # Mosaic draft compile against step_stall_s and restart-loop
            # a healthy engine through the same compile until DEAD
            self._beat("draft", compiling=True)
        tokens = np.zeros((B, S), np.int32)
        positions0 = np.zeros((B,), np.int32)
        input_lens = np.ones((B,), np.int32)
        active_mask = np.zeros((B,), bool)
        steps = np.zeros((B,), np.int32)
        for seq in active:
            slot = seq.slot
            row = self._page_tables_np[slot]
            row[:] = 0
            row[: len(seq.pages)] = seq.pages
            tokens[slot, 0] = seq.output_ids[-1]
            positions0[slot] = seq.total_len - 1
            active_mask[slot] = True
            steps[slot] = seq.num_generated
            # acceptance+bonus never exceeds input_len, so capping the
            # input at the remaining budget/length bounds overshoot
            room = min(
                S,
                max(1, seq.params.max_tokens) - seq.num_generated,
                max_len - seq.total_len + 1,
            )
            if room > 1:
                # greedy AND sampled sequences draft: greedy rows verify
                # by argmax match, sampled rows by rejection sampling
                # (verify_and_sample), both distribution-exact
                draft = self.drafter(seq, room - 1)
                if draft:
                    tokens[slot, 1 : 1 + len(draft)] = draft
                    input_lens[slot] = 1 + len(draft)
        # rounds where little/nothing drafted (non-repetitive text — the
        # n-gram drafter found no match for greedy OR sampled rows) run a
        # narrower program variant — a no-draft round costs a plain
        # decode step, not a k+1-wide verify of nothing.  Widths are
        # powers of two so the variant count stays log2(S), mirroring
        # the decode-chunk ladder.
        S_round = 1 << (max(1, int(input_lens.max())) - 1).bit_length()
        S_round = max(1, min(S, S_round))
        if S_round < S:
            tokens = tokens[:, :S_round]
        # bucket the context window to the live maximum (next power of two
        # in pages): the verify attention gathers the whole passed table
        # width per layer, so slicing it keeps the gather O(context), not
        # O(max_model_len) — at the cost of log2(pages_per_seq) compiled
        # variants
        w_needed = max(len(seq.pages) for seq in active)
        width = self._page_tables_np.shape[1]
        if w_needed < width:
            width = min(width, 1 << (max(1, w_needed) - 1).bit_length())
            width = max(width, w_needed)
        sig = tuple((s.seq_id, s.slot, s.preempt_count) for s in active)
        if self._spec_rows is None or self._spec_rows["sig"] != sig:
            self._spec_rows = {
                "sig": sig,
                **self._sampling_rows(B, ((s.slot, s) for s in active)),
            }
        rows = self._spec_rows
        want_pen = rows["counts"] is not None
        faults.check("decode_step")
        # stale-wake epochs for the readback loop below (the verify
        # call + np.asarray block this thread; a stall declared there
        # may checkpoint + replay these sequences)
        spec_epochs = {s.seq_id: s.preempt_count for s in active}
        start = time.perf_counter()
        num_lp, all_greedy = rows["num_logprobs"], rows["all_greedy"]
        spec_key = (S_round, width, num_lp, all_greedy, want_pen)
        with self._launch(
            "spec_verify", spec_key, active,
            ("spec_verify", lambda: multitok_attention_impl(
                self.use_pallas, self._mt_mesh, rows=S_round,
                group=packed_group(self.spec),
            )),
            lambda: {
                "program": "spec_verify", "steps": 1,
                "rows": len(active),
                "ctx_tokens": sum(s.total_len for s in active),
            },
            chunk=S_round, batch=len(active),
        ) as posted:
            (
                model_toks, accepted, lp_data, counts_out,
                self.k_pages, self.v_pages,
            ) = _spec_verify_step(
                self.params,
                self.spec,
                jnp.asarray(tokens),
                jnp.asarray(positions0),
                jnp.asarray(input_lens),
                self.k_pages,
                self.v_pages,
                jnp.asarray(self._page_tables_np[:, :width]),
                jnp.asarray(active_mask),
                rows["temps"],
                rows["top_ps"],
                rows["top_ks"],
                self._base_key,
                jnp.asarray(self._step_counter, jnp.uint32),
                seeds=rows["seeds"],
                steps=jnp.asarray(steps),
                use_pallas=self.use_pallas,
                num_logprobs=num_lp,
                counts=rows["counts"],
                freq_pens=rows["freq_pens"],
                pres_pens=rows["pres_pens"],
                min_toks=rows["min_toks"],
                stop_id_mat=rows["stop_id_mat"],
                all_greedy=all_greedy,
                bias_ids=rows["bias_ids"],
                bias_vals=rows["bias_vals"],
                mesh=self._mt_mesh,
            )
            posted.update(output=model_toks, steps=1, rows=len(active))
        # the histogram of the tokens this round appended, kept on the
        # device for the next round (None without penalties)
        rows["counts"] = counts_out
        self._step_counter += 1
        self.perf.count(decode_steps=1)
        # perf split of the existing sync (see _process_chunks)
        with self.perf.span("device_wait") as wait:
            jax.block_until_ready((model_toks, accepted))
        with self.perf.span("readback") as read:
            toks_np = np.asarray(model_toks)  # [B, S]
            acc_np = np.asarray(accepted)
            lp_np = None
            if lp_data is not None:
                # transpose to step-major so _attach_logprob's
                # [step][slot] indexing applies
                lp_np = (
                    np.asarray(lp_data[0]).T,
                    np.transpose(np.asarray(lp_data[1]), (1, 0, 2)),
                    np.transpose(np.asarray(lp_data[2]), (1, 0, 2)),
                )
        device_s, readback_s = wait.seconds, read.seconds
        spec_s = time.perf_counter() - start
        if self.perf.enabled:
            self.perf.note_decode(
                steps=1,
                ctx_tokens=sum(s.total_len for s in active),
                device_s=device_s,
                chunk=False,  # a verify pass, not a decode chunk
            )
        self._record_decode_step(
            "spec_verify", active, S_round, spec_s, device_s, readback_s
        )
        # append under the readback lock (device waits all happened
        # above): see _emit_first_tokens for the interleaving hazard
        delivered = 0
        wakes: Dict[Any, None] = {}
        with self.perf.span("emit") as emit:
            with self._readback_lock:
                for seq in active:
                    # stale-wake guard (see _emit_first_tokens): status
                    # AND the epoch captured at dispatch — a watchdog
                    # stall during the blocking readback above may have
                    # checkpointed + replayed this sequence already
                    if (
                        seq.status is not SeqStatus.RUNNING
                        or seq.preempt_count != spec_epochs[seq.seq_id]
                    ):
                        continue
                    slot = seq.slot
                    accepted_n = int(acc_np[slot])
                    self.total_spec_drafted += int(input_lens[slot]) - 1
                    self.total_spec_accepted += accepted_n
                    want_lp = lp_np is not None and seq.params.logprobs
                    # model_toks[:, j] for j < accepted IS draft j+1;
                    # position `accepted` holds the bonus token — one
                    # loop covers both
                    run = toks_np[slot, : accepted_n + 1].tolist()
                    for j, token in enumerate(run):
                        if want_lp:
                            self._attach_logprob(seq, lp_np, j, slot)
                        seq.append_token(token)
                        delivered += 1
                        self._maybe_finish(seq, token, wakes)
                        if seq.status is not SeqStatus.RUNNING:
                            break
                    seq.deliver(wakes)  # the accepted run as one list
            self._wake_streams(wakes)
            self.total_decode_tokens += delivered
            emit.note(tokens=delivered)
        self.perf.note_tokens(delivered)
        if delivered:
            self._note_delivery(1, len(active))
        self.total_steps += 1
        return True

    def lp_entry(self, tid: int, lp: float, top) -> Dict[str, Any]:
        """One OpenAI-shape logprob entry for a delivered token."""
        return {
            "token": self.tokenizer.decode([tid]),
            "token_id": tid,
            "logprob": lp,
            "top_logprobs": [
                {
                    "token": self.tokenizer.decode([i]),
                    "token_id": i,
                    "logprob": l,
                }
                for i, l in top
            ],
        }

    def logprob_entries(self, seq: Sequence) -> List[Dict[str, Any]]:
        """OpenAI-shape logprob content for a finished sequence (one entry
        per generated token, aligned with ``generated_ids``)."""
        return [
            self.lp_entry(tid, lp, top)
            for tid, (lp, top) in zip(seq.generated_ids, seq.logprob_data)
        ]

    @engine_thread_only
    def _attach_logprob(self, seq: Sequence, lp_np, k, slot) -> None:
        """Record one delivered token's logprob data from a readback
        triple ``(lp [.., B], top_ids [.., B, K], top_lps [.., B, K])``
        (leading step axis optional — prefill readbacks have none)."""
        lp, tids, tlps = lp_np
        if lp.ndim == 2:  # [chunk, B]
            lp, tids, tlps = lp[k], tids[k], tlps[k]
        n = min(seq.params.top_logprobs, tids.shape[-1])
        seq.logprob_data.append(
            (
                float(lp[slot]),
                [
                    (int(tids[slot, j]), float(tlps[slot, j]))
                    for j in range(n)
                ],
            )
        )

    @staticmethod
    def _wake_streams(wakes: Dict[Any, None]) -> None:
        """A readback's ONE cross-thread wake-up per stream consumer
        (``Sequence.deliver`` collected them)."""
        for wake in wakes:
            wake()

    @engine_thread_only
    def _maybe_finish(
        self, seq: Sequence, token: int, wakes: Dict[Any, None]
    ) -> None:
        reason = None
        # min_tokens gates STOP kinds only (device masking already
        # prevents stop tokens; this also holds back stop strings).  The
        # length finishes below must stay live: a floor above the budget
        # would otherwise leave the sequence RUNNING forever with zero
        # decode headroom.
        below_floor = seq.num_generated < seq.params.min_tokens
        if not below_floor:
            if token == self.tokenizer.eos_id or token in self._stop_ids:
                reason = "stop"
            elif (
                seq.params.stop_token_ids
                and token in seq.params.stop_token_ids
            ):
                reason = "stop"
            elif self._hit_stop_string(seq):
                reason = "stop"  # text_override truncated at the match
        if reason is None:
            if seq.num_generated >= max(1, seq.params.max_tokens):
                reason = "length"
            elif seq.total_len >= self.config.model.max_model_len:
                reason = "length"
        if reason is not None:
            self.scheduler.remove(seq)
            seq.finish(reason, wakes)

    @engine_thread_only
    def _hit_stop_string(self, seq: Sequence) -> bool:
        """Host-side stop-sequence detection at token readback (the
        reference delegates this to vLLM's ``SamplingParams.stop``,
        vgate/backends/vllm_backend.py:39-46).

        Cheap path first: decode only a tail window of tokens (a stop of L
        chars spans at most L tokens plus the just-appended one) and
        substring-match there; on a hit, decode the full generation once to
        find the earliest match and truncate ``text_override`` before it.
        Decode chunks may overshoot a stop; overshoot tokens remain in
        ``generated_ids`` but never reach the final text.
        """
        stops = seq.params.stop
        if not stops:
            return False
        longest = max(len(s) for s in stops)
        window = min(len(seq.generated_ids), longest + 8)
        tail = self.tokenizer.decode(seq.generated_ids[-window:])
        if not any(s in tail for s in stops):
            return False
        text = self.tokenizer.decode(seq.generated_ids)
        # min_tokens rule: matches ENDING inside the floor are ignored
        # (their stop checks were skipped while below the floor); a match
        # straddling the boundary still stops the sequence and truncates
        # at its start — the floor guarantees GENERATED tokens, not
        # post-truncation text length (vLLM semantics).  floor_chars has
        # the same +-few-chars BPE-boundary fuzz the tail-window check
        # tolerates (decoding a token prefix in isolation can render
        # replacement chars at a split multi-byte glyph).
        floor_chars = 0
        if seq.params.min_tokens > 0:
            floor_chars = len(
                self.tokenizer.decode(
                    seq.generated_ids[: seq.params.min_tokens]
                )
            )
        cuts = []
        for s in stops:
            idx = text.find(s, max(0, floor_chars - len(s) + 1))
            if idx != -1:
                cuts.append(idx)
        if not cuts:
            # tail decode produced chars the full decode doesn't (BPE
            # boundary artifact), or the only matches sit inside the
            # min_tokens floor — not a real stop
            return False
        seq.text_override = text[: min(cuts)]
        return True

    def final_text(self, seq: Sequence) -> str:
        """The request's final text: the stop-truncated override when a stop
        sequence matched, else the full decoded generation."""
        if seq.text_override is not None:
            return seq.text_override
        return self.tokenizer.decode(seq.generated_ids)

    # ------------------------------------------------------------- utilities

    def warmup(self, buckets: Optional[List[int]] = None) -> float:
        """Pre-compile the decode-chunk ladder and the given (default:
        smallest) prefill buckets so first requests don't pay XLA compile
        latency.  The first warmup sequence generates ``2*decode_chunk``
        tokens, which walks the power-of-two chunk descent (K, ..., 2, 1)
        that _pick_chunk produces near a budget boundary.  For the first
        bucket the batched-prefill ladder (B = batch_max, ..., 2, 1) is
        also compiled: each group is submitted as one burst so it admits
        as a single stacked program."""
        start = time.perf_counter()
        was_running = self._running
        if not was_running:
            self.start()
        ladder = SamplingParams(
            max_tokens=max(1, 2 * self.decode_chunk), temperature=0.0
        )
        # the decode-chunk/spec-verify programs split on all_greedy; a
        # second sampled ladder walk compiles those variants so the
        # first temperature>0 request doesn't pay them at serve time
        # (prefill programs don't split, so one bucket walk suffices)
        ladder_sampled = SamplingParams(
            max_tokens=max(1, 2 * self.decode_chunk), temperature=0.7
        )
        single = SamplingParams(max_tokens=1, temperature=0.0)
        buckets = buckets or [self.scheduler.prefill_buckets[0]]
        for i, bucket in enumerate(buckets):
            n = max(1, min(bucket - 1, 8))
            seq = self.submit_tokens([5] * n, ladder if i == 0 else single)
            seq.done_event.wait(timeout=600)
            if i == 0:
                seq = self.submit_tokens([5] * n, ladder_sampled)
                seq.done_event.wait(timeout=600)
                B = max(1, self.config.tpu.prefill_batch_max)
                while B >= 2:
                    group = [
                        self.submit_tokens([5] * n, single)
                        for _ in range(min(B, self.max_slots))
                    ]
                    for g in group:
                        g.done_event.wait(timeout=600)
                    B //= 2
        if self.scheduler.prefill_chunk > 0:
            # chunked prefill compiles suffix programs (one per pow2
            # context width) the bucket walk above never touches; one
            # max-length prompt hits every width so the first long
            # request doesn't pay serial compiles at serve time
            n_long = self.config.model.max_model_len - 2
            if n_long > self.scheduler.prefill_buckets[-1]:
                seq = self.submit_tokens([5] * n_long, single)
                seq.done_event.wait(timeout=600)
        if not was_running:
            self.stop()
        return time.perf_counter() - start

    def capture_profile(
        self,
        duration_s: float = 1.0,
        out_dir: Optional[str] = None,
        python_tracer: bool = False,
    ) -> Dict[str, Any]:
        """Capture a ``jax.profiler`` device trace while serving continues
        (SURVEY.md section 5.1: the reference has request-scoped OTel spans
        but no low-level profiler; on TPU the device timeline — kernel
        times, HBM traffic, infeed stalls — comes from the JAX profiler,
        viewable in TensorBoard/XProf).

        The Python tracer is OFF unless ``python_tracer``: it slows the
        host loop it measures and bloats the trace.  What the host was
        doing comes from the ``vgt.engine.*`` / ``vgt.gateway.*``
        annotations the perf brackets open while the capture runs
        (observability/perf.py); ``stop_s`` is how long writing the
        trace stalled, ``file_bytes`` what it wrote."""
        out_dir = out_dir or os.path.join(
            tempfile.gettempdir(),
            f"vgt_profile_{int(time.time())}",
        )
        duration_s = max(0.05, min(duration_s, 60.0))
        capture_start = time.time()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
        set_capturing(True)
        try:
            time.sleep(duration_s)
        finally:
            set_capturing(False)
            # let the tick in progress close its spans inside the
            # session (a prefill wave's tick lasts a second): spans
            # still open at stop_trace are lost from the trace
            ticks, deadline = self._ticks_done, time.monotonic() + 2.0
            while (
                self._running
                and self._ticks_done == ticks
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            stop_t0 = time.perf_counter()
            jax.profiler.stop_trace()
            stop_s = time.perf_counter() - stop_t0
        # count only files this capture wrote (out_dir may be reused)
        written = [
            os.path.join(root, f)
            for root, _, files in os.walk(out_dir)
            for f in files
            if os.path.getmtime(os.path.join(root, f)) >= capture_start - 1
        ]
        result = {
            "trace_dir": out_dir,
            "duration_s": duration_s,
            "files": len(written),
            "file_bytes": sum(os.path.getsize(f) for f in written),
            "stop_s": round(stop_s, 4),
            "python_tracer": bool(python_tracer),
        }
        # link the device-timeline capture to the attribution layer:
        # the flight ring shows WHEN the capture window sat relative to
        # recompiles/sheds, and /debug/perf reports the last capture so
        # operators can line up phase attribution with the XProf trace
        self.flight.record_tick("profile", **result)
        self.perf.note_profile(result)
        return result

    def perf_snapshot(self) -> Dict[str, Any]:
        """The /debug/perf payload (observability/perf.py): per-tick
        phase attribution window, compile ledger, live MFU/roofline
        gauges and the last profile capture."""
        return self.perf.snapshot()

    def set_spec_suspended(self, flag: bool) -> None:
        """Brownout hook (vgate_tpu/admission.py L3): suspend/resume
        speculative decoding without a rebuild.  Safe from any thread —
        the engine loop re-reads the flag every tick and folds any
        in-flight decode chunks before the first spec round."""
        self.spec_suspended = bool(flag)

    def set_prefix_insert_suspended(self, flag: bool) -> None:
        """Brownout hook (vgate_tpu/admission.py L4 "bypass cache
        writes"): stop inserting into the prefix tree, keep serving
        hits — under saturation new cache content mostly evicts warmer
        content, while existing hits still save prefill compute.  Safe
        from any thread (bool stores are atomic under the GIL); carried
        across supervisor rebuilds like spec_suspended."""
        self.prefix_insert_suspended = bool(flag)
        if self.radix_cache is not None:
            self.radix_cache.insert_suspended = bool(flag)
        if self.kv_swap is not None:
            # L4 also stops host-pool DEMOTIONS (a demotion is a cache
            # write) while promotions keep serving — existing warm
            # content saving prefill is exactly what overload needs.
            # Preemption swap-outs are NOT gated: parking client-owed
            # work beats recomputing it at any brownout level.
            self.kv_swap.demote_suspended = bool(flag)

    def pressure_signals(self) -> Dict[str, Any]:
        """Cheap cross-thread gauges for the gateway's admission and
        brownout controllers: plain int/len reads only (atomic enough
        under the GIL for control decisions — no locks, no device
        touches).  ``kv_free_ratio`` counts reclaimable cached pages as
        free (a warm prefix cache must not shed admissions);
        ``kv_truly_free_ratio`` excludes them — the gap between the two
        is the reclaimable cache."""
        total = max(1, self.allocator.num_allocatable)
        swap_block = (
            self.kv_swap.signal_block() if self.kv_swap is not None else {}
        )
        return {
            **swap_block,
            "kv_free_ratio": round(self.allocator.num_free / total, 4),
            "kv_truly_free_ratio": round(
                self.allocator.num_truly_free / total, 4
            ),
            "prefix_cached_ratio": round(
                self.allocator.num_cached / total, 4
            ),
            # capacity identity for admission (auto_token_budget scales
            # the token backlog limit with it) and attribution: int8 KV
            # roughly doubles both vs bf16 at the same HBM budget
            "kv_token_capacity": self.geometry.total_tokens,
            "kv_dtype": self.geometry.kv_dtype,
            "engine_queue_depth": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
        }

    def device_health(self) -> Dict[str, Any]:
        try:
            device = self.mesh.devices.flat[0]
            value = float(jnp.asarray([1.0]).sum())
            return {
                "alive": value == 1.0,
                "platform": device.platform,
                "device_kind": getattr(device, "device_kind", "unknown"),
                "num_devices": int(self.mesh.devices.size),
            }
        except Exception as exc:  # pragma: no cover
            return {"alive": False, "error": str(exc)}

    def _state_args(self, slots=None) -> Dict[str, Any]:
        """The step programs' extra arguments for a spec with recurrent
        layers (the state, and for a prompt pass each row's slot);
        nothing for the others."""
        if self.state is None or slots is None:
            return _state_kw(self.state)
        return _state_kw(self.state, jnp.asarray(slots, jnp.int32))

    def _state_cache_kind(self) -> Dict[str, Any]:
        """What ``/stats -> engine.state_cache`` holds a slot: the
        recurrent layers' rows, or the window layers' rings."""
        name = dtype_short_name(self._state_dtype)
        if self.spec.eva_layers:
            return {
                "kind": "eva_window",
                "layers": self.spec.eva_layers,
                "tokens_per_slot": self.spec.eva_window,
                "pages_per_slot": eva_window_pages(
                    self.spec, self.geometry.page_size),
                "window": self.spec.eva_window,
                "chunk": self.spec.eva_chunk,
                "dtype": f"{name} K and V, pages of the pool arrays "
                         "behind the allocator's",
            }
        if self.spec.swa_layers:
            return {
                "kind": "ring",
                "layers": self.spec.swa_layers,
                "tokens_per_slot": self._ring_tokens,
                "window": self.spec.sliding_window,
                "dtype": f"{name} K and V",
            }
        if self.spec.conv_layers:
            return {
                "kind": "conv",
                "conv_layers": self.spec.conv_layers,
                "rows_per_slot": self.spec.conv_L_cache - 1,
                "dtype": f"{name} convolution tail, no tile",
            }
        out = {
            "linear_layers": self.spec.linear_layers,
            "kind": self.spec.recurrent_kind,
            "dtype": f"float32 state, {name} convolution tail",
        }
        if self.spec.recurrent_kind == "mamba":
            # heads a program of the step kernel takes (ops/pallas/
            # ssd.py): what a trace's ``ssd_step_pallas`` time was made
            # at; 0: the jax.numpy twin, no kernel
            from vgate_tpu.ops.pallas.ssd import block_heads

            out["step_block_heads"] = block_heads(
                self.spec.mamba_num_heads, self.spec.mamba_n_groups
            ) if self.use_pallas else 0
        return out

    def _ring_tick(self) -> Dict[str, int]:
        """A flight tick's cache line beside ``kv_used``: the bytes the
        running sequences' rings hold (none for a spec without them),
        or those of the used pages' index keys (a spec that picks)."""
        if self.spec.is_dsa:  # the index keys among the pages' bytes
            geo = self.geometry
            return {"index_bytes": (
                self.allocator.num_used * geo.page_size * geo.index_layers
                * geo.index_dim * geo.dtype_bytes)}
        if self.spec.eva_layers:
            return {"window_bytes": (
                self._state_slot_bytes * len(self.scheduler.running))}
        if not self.spec.swa_layers:
            return {}
        return {"ring_bytes": (
            self._state_slot_bytes * len(self.scheduler.running))}

    def _set_cache(self, cache) -> None:
        """Take back what a step program returned of the cache."""
        self.k_pages, self.v_pages, *rest = cache
        if rest:
            (self.state,) = rest

    def _device_memory(self, *keys: str) -> List[Dict[str, int]]:
        """What each chip of the mesh itself reports of ``keys``, as far
        as its runtime gives them (nothing on a CPU)."""
        return [
            {
                "id": int(dev.id),
                **{
                    k: int(v)
                    for k, v in (dev.memory_stats() or {}).items()
                    if k in keys
                },
            }
            for dev in self.mesh.devices.flat
        ]

    def get_stats(self) -> Dict[str, Any]:
        """Engine counters for /stats.  ``steps`` counts *dispatched decode
        steps* (chunk lengths summed, including overshoot steps discarded at
        readback); prefills are reported separately under ``prefills`` and
        per-request token deliveries under ``decode_tokens``."""
        return {
            "scheduler": self.scheduler.get_stats(),
            "steps": self.total_steps,
            "prefills": self.total_prefills,
            "decode_tokens": self.total_decode_tokens,
            "state_rebuilds": self.total_state_rebuilds,
            "flight": self.flight.get_stats(),
            "perf": self.perf.get_stats(),
            "kv_pages_total": self.allocator.num_allocatable,
            "kv_pool_bytes": (
                self.geometry.num_pages * self.geometry.page_bytes
            ),
            "kv_sized_by": self._kv_sized_by,
            "kv_token_capacity": self.geometry.total_tokens,
            # the pool in ROWS and in tokens: a row is a token, but for
            # a spec whose row stands for several (row_tokens)
            "kv_pool": {
                "row_tokens": self.geometry.row_tokens,
                "rows": self.allocator.num_allocatable
                * self.geometry.page_size,
                "rows_used": self.allocator.num_used
                * self.geometry.page_size,
                "tokens": self.geometry.total_tokens,
                "tokens_used": self.allocator.num_used
                * self.geometry.page_tokens,
            },
            # KV storage attribution: drills and bench artifacts read
            # these so every recorded number names its KV config
            "kv_dtype": self.geometry.kv_dtype,
            "kv_page_bytes": self.geometry.page_bytes,
            # what a page holds: K and V of every KV head, or latent
            # attention's one row a token ("latent": values used of the
            # row's lanes)
            "kv_layout": {
                "pools": self.geometry.pools,
                "heads": self.geometry.kv_heads,
                "row_lanes": self.geometry.head_dim,
                **({"latent": self.spec.latent_dim}
                   if self.spec.is_mla else {}),
                # heads of 64 two to a row (ops/head_pack.py)
                **({"heads_per_row": self.spec.kv_head_pack}
                   if self.spec.kv_head_pack > 1 else {}),
                # a spec that picks: the latent rows by pairs of tokens,
                # what its decode kernel fetches a pick
                **({"row_pairs": True} if by_pairs(self.k_pages)
                   and not self.spec.kv_rows else {}),
                # GQA under a selection: a token's K over its V, one
                # pair of rows, what its decode kernel fetches a pick
                **({"kv_rows": True} if self.spec.kv_rows else {}),
                # the index keys a page holds beside them (a spec that
                # picks): one row a token in each picking layer
                **({"index": {
                    "layers": self.geometry.index_layers,
                    "row_bytes": (self.geometry.index_dim
                                  * self.geometry.dtype_bytes),
                }} if self.geometry.index_layers else {}),
            },
            **(
                {
                    "state_cache": {
                        "slots": self.max_slots,
                        "bytes_per_slot": self._state_slot_bytes,
                        "bytes": self._state_slot_bytes * self.max_slots,
                        **self._state_cache_kind(),
                    }
                }
                if self.spec.slot_state_layers else {}
            ),
            "weights_bytes": self._params_bytes,
            "model": self.spec.name,
            # the spec's published multipliers as the program applies
            # them (a preset that lost one shows here); none: not there
            **({"multipliers": self.spec.multipliers}
               if self.spec.multipliers else {}),
            # a window stack's rotary by layer type as served (type,
            # theta, factor, amplitude; "none": a kind that takes no
            # positions), so a run's record says which layers took which
            **({"rotary": self.spec.rotary_by_kind}
               if self.spec.rotary_by_kind else {}),
            "mesh": {
                axis: int(size) for axis, size in self.mesh.shape.items()
            },
            # kernel or jnp twin, per step program compiled so far
            "use_pallas": self.use_pallas,
            "attention": {
                prog: sorted(impls)
                for prog, impls in self._attention.items()
            },
            # the decode kernel or XLA's scatter before it
            "kv_write": self._decode_kv_write(),
            "load_time_s": round(self.load_time_s, 2),
            # what each chip of the mesh itself reports (empty on CPU)
            "device_memory": self._device_memory(
                "bytes_in_use", "bytes_limit"
            ),
            **(
                {"kv_swap": self.kv_swap.get_stats()}
                if self.kv_swap is not None
                else {}
            ),
            **(
                {"integrity": self.integrity.stats()}
                if self.integrity is not None
                else {}
            ),
            **(
                {
                    "speculative": {
                        "k": self.spec_k,
                        "drafter": (
                            f"draft-model:{self.draft_model.spec.name}"
                            if self.draft_model is not None
                            else f"ngram:{self.spec_ngram}"
                        ),
                        **(
                            {
                                "draft_calls":
                                    self.draft_model.total_draft_calls
                            }
                            if self.draft_model is not None
                            else {}
                        ),
                        "drafted": self.total_spec_drafted,
                        "accepted": self.total_spec_accepted,
                        "acceptance_rate": round(
                            self.total_spec_accepted
                            / max(1, self.total_spec_drafted),
                            3,
                        ),
                    }
                }
                if self.spec_k > 0
                else {}
            ),
        }
