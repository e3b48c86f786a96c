"""The in-house TPU inference backend (`engine_type: jax_tpu`).

Implements the reference's 4-method backend seam
(vgate/backends/base.py:21-34) — but where vLLM/SGLang adapters delegate to
external GPU engines (vllm_backend.py:48-70), this backend owns the whole
stack: JAX model runner, paged KV cache, continuous-batching scheduler and
device-side sampling (runtime/engine_core.py).  Additional capabilities the
gateway exploits when present: ``generate_async`` (sequences join the running
engine between decode steps), ``stream_async`` (SSE, one delta per engine
readback), ``embed`` (real encoder embeddings) and ``device_health``.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import os
import threading
import time
from typing import (
    Any,
    AsyncIterator,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from vgate_tpu import faults
from vgate_tpu.backends.base import GenerationResult, SamplingParams
from vgate_tpu.config import get_config
from vgate_tpu.errors import state_is_alive, state_is_ready
from vgate_tpu.logging_config import get_logger
from vgate_tpu.models.specs import ModelSpec, spec_for_model_id
from vgate_tpu.observability.perf import GATEWAY
from vgate_tpu.runtime.engine_core import EngineCore
from vgate_tpu.runtime.sequence import SeqStatus
from vgate_tpu.runtime.tokenizer import IncrementalDetokenizer
from vgate_tpu.utils.math import bucket_for, round_up
from vgate_tpu.analysis.witness import named_lock

logger = get_logger(__name__)


class Embedder:
    """Encoder-model wrapper for /v1/embeddings."""

    BUCKETS = (32, 128, 512)

    def __init__(self, model_id: str, checkpoint_path: Optional[str], dtype):
        from vgate_tpu.models.encoder import (
            encode_forward,
            init_encoder_params,
        )
        from vgate_tpu.runtime.tokenizer import get_tokenizer

        self.spec = spec_for_model_id(model_id)
        if not self.spec.is_encoder:
            raise ValueError(f"{model_id} is not an encoder model")
        self.tokenizer = get_tokenizer(self.spec, checkpoint_path)
        if checkpoint_path and os.path.isdir(checkpoint_path):
            from vgate_tpu.models.encoder import (
                encoder_params_from_safetensors,
            )

            self.params = encoder_params_from_safetensors(
                self.spec, checkpoint_path, dtype
            )
        else:
            # zero-egress fallback: architecturally real, semantically
            # meaningless vectors (logged so operators can't mistake them
            # for bge embeddings)
            logger.warning(
                "no embedding checkpoint found; using random-init weights",
                extra={"extra_data": {"model": model_id,
                                      "path": checkpoint_path}},
            )
            self.params = init_encoder_params(
                self.spec, jax.random.PRNGKey(0), dtype
            )
        self._forward = jax.jit(
            functools.partial(encode_forward, spec=self.spec)
        )
        self._lock = named_lock("Embedder._lock")

    def embed(self, inputs: Sequence[str]) -> List[List[float]]:
        max_len = self.spec.max_position_embeddings
        ids = [self.tokenizer.encode(t)[: max_len - 2] for t in inputs]
        longest = max(1, max(len(i) for i in ids))
        S = bucket_for(
            min(longest + 2, max_len),
            [b for b in self.BUCKETS if b <= max_len] + [max_len],
        )
        B = max(1, min(64, 1 << (len(ids) - 1).bit_length()))
        out: List[List[float]] = []
        with self._lock:
            for chunk_start in range(0, len(ids), B):
                chunk = ids[chunk_start : chunk_start + B]
                tokens = np.zeros((B, S), np.int32)
                mask = np.zeros((B, S), np.int32)
                for row, seq_ids in enumerate(chunk):
                    full = (
                        [self.tokenizer.bos_id] + seq_ids + [self.tokenizer.eos_id]
                    )
                    tokens[row, : len(full)] = full
                    mask[row, : len(full)] = 1
                vecs = self._forward(
                    self.params,
                    tokens=jnp.asarray(tokens),
                    mask=jnp.asarray(mask),
                )
                out.extend(
                    np.asarray(vecs[: len(chunk)], np.float32).tolist()
                )
        return out


class _LoopHandoff:
    """The seam between the engine thread(s) and ONE event loop.  A
    readback posts what it appended to each of the loop's streams and
    then wakes the loop once (``Sequence.deliver`` gathers the wakes,
    the engine calls them after its loop over the sequences); the one
    callback that wake-up schedules fans the deliveries out to the
    streams' queues.  A wake-up already on its way serves whatever is
    posted before it runs, so no second one is issued for it."""

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self._lock = threading.Lock()
        self._pending: List[tuple] = []  # (queue, item), in post order
        self._armed = False
        # when the wake-up on its way was armed (GATEWAY's clock; None
        # with observability off): _drain books how long it waited
        self._armed_t: Optional[float] = None

    def post(self, q: "asyncio.Queue", item: Any) -> None:
        with self._lock:
            self._pending.append((q, item))

    def wake(self) -> None:
        with self._lock:
            if self._armed or not self._pending:
                return
            self._armed = True
            self._armed_t = GATEWAY.handoff_armed()
        try:
            self._loop.call_soon_threadsafe(self._drain)
        except RuntimeError:
            # loop closed: its streams are gone, aborts follow
            with self._lock:
                self._pending.clear()
                self._armed = False

    def _drain(self) -> None:
        with self._lock:
            batch, self._pending = self._pending, []
            self._armed = False
            armed_t = self._armed_t
        GATEWAY.note_handoff(armed_t)
        for q, item in batch:
            q.put_nowait(item)


class JaxTPUBackend:
    """Continuous-batching TPU backend behind the 4-method protocol."""

    def __init__(self) -> None:
        # EngineCore (dp=1) or runtime.dp_engine.ReplicatedEngine (dp>1);
        # both expose the same serving surface
        self.core: Optional[Any] = None
        self._embedder: Optional[Embedder] = None
        self._config = None
        self._handoffs: Dict[Any, _LoopHandoff] = {}

    def _handoff_for(
        self, loop: asyncio.AbstractEventLoop
    ) -> _LoopHandoff:
        handoff = self._handoffs.get(loop)
        if handoff is None:
            self._handoffs = {
                lp: h for lp, h in self._handoffs.items()
                if not lp.is_closed()
            }
            handoff = self._handoffs[loop] = _LoopHandoff(loop)
        return handoff

    # -- protocol --

    def load_model(self, config: Any) -> None:
        # accept the full VGTConfig through the seam; fall back to the global
        # for callers that still pass only the model section
        self._config = config if hasattr(config, "tpu") else get_config()
        if getattr(self._config, "pod", None) and self._config.pod.workers > 0:
            # process-isolated workers: the gateway routes over N engine
            # processes with fencing/failover; takes precedence over
            # in-process dp (each worker is its own full engine stack)
            from vgate_tpu.runtime.pod_engine import PodEngine

            self.core = PodEngine(self._config)
        elif self._config.tpu.dp > 1:
            # dp replicas have their own failover; unsupervised
            from vgate_tpu.runtime.dp_engine import ReplicatedEngine

            self.core = ReplicatedEngine(self._config)
        elif self._config.recovery.enabled:
            from vgate_tpu.runtime.supervisor import EngineSupervisor

            self.core = EngineSupervisor(self._config)
        else:
            self.core = EngineCore(self._config)
        self.core.start()
        logger.info(
            "jax_tpu backend ready",
            extra={
                "extra_data": {
                    "model": self.core.spec.name,
                    "mesh": {
                        k: int(v) for k, v in self.core.mesh.shape.items()
                    },
                    "kv_pages": self.core.geometry.num_pages,
                }
            },
        )

    def create_sampling_params(self, **kwargs: Any) -> SamplingParams:
        return SamplingParams(**kwargs)

    def generate(
        self,
        prompts: Sequence[str],
        sampling_params: Sequence[SamplingParams],
    ) -> List[GenerationResult]:
        assert self.core is not None, "load_model not called"
        faults.check("backend_generate")
        raw = self.core.generate(prompts, sampling_params)
        return [GenerationResult(**r) for r in raw]

    def shutdown(self) -> None:
        if self.core is not None:
            self.core.stop()
            self.core = None

    def abort_in_flight(self, reason: str = "drain") -> None:
        """Graceful-drain straggler sweep: ask the engine thread to
        request-abort every resident sequence at its next tick
        (supervised cores delegate to the live EngineCore)."""
        if self.core is None:
            return
        fn = getattr(self.core, "abort_in_flight", None)
        if fn is not None:
            fn(reason)

    def set_spec_suspended(self, flag: bool) -> None:
        """Brownout L3 (vgate_tpu/admission.py): suspend/resume
        speculative decoding on the live core (supervised cores
        delegate; dp routers fan out to every replica)."""
        fn = getattr(self.core, "set_spec_suspended", None) if (
            self.core is not None
        ) else None
        if fn is not None:
            try:
                fn(bool(flag))
            except Exception:  # pragma: no cover - mid-rebuild race
                logger.warning("set_spec_suspended failed", exc_info=True)

    def set_prefix_insert_suspended(self, flag: bool) -> None:
        """Brownout L4 (vgate_tpu/admission.py "bypass cache writes"):
        stop prefix-tree inserts, keep serving hits (supervised cores
        delegate; dp routers fan out to every replica)."""
        fn = getattr(
            self.core, "set_prefix_insert_suspended", None
        ) if self.core is not None else None
        if fn is not None:
            try:
                fn(bool(flag))
            except Exception:  # pragma: no cover - mid-rebuild race
                logger.warning(
                    "set_prefix_insert_suspended failed", exc_info=True
                )

    def pressure_signals(self) -> Dict[str, Any]:
        """KV/queue gauges for gateway admission + brownout; empty while
        the core is loading or mid-rebuild (the controllers then fall
        back to gateway-side signals alone)."""
        fn = getattr(self.core, "pressure_signals", None) if (
            self.core is not None
        ) else None
        if fn is None:
            return {}
        try:
            return fn() or {}
        except Exception:  # pragma: no cover - mid-rebuild race
            return {}

    # -- async extensions used by the gateway --

    async def generate_settled_async(
        self,
        prompts: Sequence[str],
        sampling_params: Sequence[SamplingParams],
        cancel_tokens: Optional[Sequence[Any]] = None,
        request_meta: Optional[Sequence[Any]] = None,
    ) -> List[Any]:
        """Like ``generate_async`` but failures are returned per slot (the
        exception object in place of a GenerationResult) instead of failing
        the whole batch — one deadline-shed or failed sequence must not
        discard its co-batched neighbours' completed generations.

        ``cancel_tokens`` (one ``lifecycle.CancelToken`` or None per
        prompt) is the request-scoped cancellation plumbing: a token
        cancelled while its sequence decodes aborts exactly that
        sequence — slot and KV pages free within one engine tick — and
        its slot settles with finish_reason "abort" while batchmates
        keep decoding.  This closes the gap where batched gateway
        traffic ran under the batcher's own task and a client
        disconnect left the sequence decoding to completion.

        ``request_meta`` (one ``observability.RequestMeta`` or None per
        prompt) carries the gateway request id and the captured OTel
        context: the engine parents its queue/prefill/decode phase
        spans on it and stamps flight-recorder records with the
        request/trace ids."""
        assert self.core is not None
        faults.check("backend_generate")
        loop = asyncio.get_running_loop()
        seqs = []
        for i, (p, sp) in enumerate(zip(prompts, sampling_params)):
            try:
                seq = self.core.submit_prompt(
                    p, sp,
                    meta=request_meta[i] if request_meta else None,
                )
            except Exception as exc:  # queue full / dead engine
                seqs.append(exc)
                continue
            token = cancel_tokens[i] if cancel_tokens else None
            if token is not None:
                # fires immediately when the client vanished between
                # enqueue and dispatch (add_callback runs late
                # registrants inline)
                token.add_callback(
                    lambda s=seq, t=token: s.request_abort(
                        t.reason or "client_disconnect"
                    )
                )
            seqs.append(seq)

        def wait_all():
            for seq in seqs:
                if not isinstance(seq, BaseException):
                    seq.done_event.wait()

        try:
            await loop.run_in_executor(None, wait_all)
        except asyncio.CancelledError:
            # the awaiting task died (client disconnect on a direct
            # caller, or the whole batch task torn down) — release the
            # engine work it was waiting on
            for seq in seqs:
                if not isinstance(seq, BaseException):
                    seq.request_abort()
            raise
        results: List[Any] = []
        for seq in seqs:
            if isinstance(seq, BaseException):
                results.append(seq)
            elif seq.status is SeqStatus.FAILED:
                results.append(seq.error)
            else:
                # the final-text assembly (tokenizer decode + stop
                # truncation) is the request's last serving phase
                with (
                    seq.trace.span(
                        "detokenize", tokens=seq.num_output_tokens
                    )
                    if seq.trace is not None
                    else contextlib.nullcontext()
                ):
                    text = self.core.final_text(seq)
                results.append(
                    GenerationResult(
                        text=text,
                        token_ids=list(seq.generated_ids),
                        num_tokens=seq.num_output_tokens,
                        prompt_tokens=seq.orig_prompt_len,
                        finish_reason=seq.finish_reason,
                        metrics={
                            "ttft": seq.ttft or 0.0,
                            "tpot": seq.tpot or 0.0,
                            "gen_time": (
                                (seq.finish_t or 0.0) - seq.arrival_t
                            ),
                            **seq.resume_metrics(),
                        },
                        logprobs=(
                            self.core.logprob_entries(seq)
                            if seq.params.logprobs
                            else None
                        ),
                    )
                )
        return results

    async def generate_async(
        self,
        prompts: Sequence[str],
        sampling_params: Sequence[SamplingParams],
    ) -> List[GenerationResult]:
        """Submit into the running engine and await completion without
        blocking the event loop (sequences from concurrent batches share
        decode steps — this is where continuous batching pays off).  Raises
        the first failure; callers batching unrelated requests should use
        ``generate_settled_async``."""
        settled = await self.generate_settled_async(prompts, sampling_params)
        for item in settled:
            if isinstance(item, BaseException):
                raise item
        return settled

    async def stream_async(
        self,
        prompt: str,
        params: SamplingParams,
        on_finish: Optional[Any] = None,
        on_usage: Optional[Any] = None,
        request_meta: Optional[Any] = None,
    ) -> AsyncIterator[str]:
        """Text deltas for SSE streaming, one per delivery: what ONE
        engine readback appended to this stream (a decode chunk's
        tokens, several at a time under load) is detokenised and
        yielded as a unit.  ``on_finish`` (if
        given) is called with the sequence's finish_reason after the last
        delta, so the gateway can close the stream with the true reason;
        ``on_usage`` (if given) receives the request's token usage dict
        just before that (OpenAI stream_options.include_usage).

        With ``params.logprobs`` each yield is a dict ``{"text": delta,
        "logprobs": [entries for the tokens consumed since the previous
        yield]}``; plain requests yield bare strings, the original
        contract."""
        assert self.core is not None
        handoff = self._handoff_for(asyncio.get_running_loop())
        # one item per delivery: (what a readback appended, settled?)
        q: "asyncio.Queue[Tuple[List[int], bool]]" = asyncio.Queue()

        clock = GATEWAY.stream_clock()  # None outside the gateway

        def on_tokens(tokens: List[int], done: bool):
            if clock is not None and clock.t_first_token is None:
                # engine thread: first tokens handed to the gateway
                # (gateway.first_chunk_* measures from here to the wire)
                clock.t_first_token = time.perf_counter()
            handoff.post(q, (tokens, done))
            return handoff.wake

        seq = self.core.submit_prompt(
            prompt, params, stream_cb=on_tokens, meta=request_meta
        )
        GATEWAY.ingress_end()

        detok = IncrementalDetokenizer(self.core.tokenizer)
        held = ""  # text the tokens gave that no delta has carried yet
        n_emitted = 0  # characters the deltas have carried
        n_ids = 0
        pending_lp: List[Any] = []

        def wrap(delta: str):
            if not params.logprobs:
                return delta
            out = {"text": delta, "logprobs": pending_lp[:]}
            pending_lp.clear()
            return out

        # a stop-length tail is held back, so that a stop string that
        # arrives across several tokens or deliveries is never partly
        # emitted; WHERE the text is cut is the engine's verdict alone
        # (final_text), which the stream's last delivery brings along
        hold = max((len(s) for s in params.stop or []), default=0)
        completed = False
        try:
            while True:
                tokens, done = await q.get()
                t_detok = GATEWAY.detok_begin(clock)
                if params.logprobs:
                    for i, token in enumerate(tokens, start=n_ids):
                        if len(seq.logprob_data) > i:
                            lp, top = seq.logprob_data[i]
                            pending_lp.append(
                                self.core.lp_entry(token, lp, top)
                            )
                n_ids += len(tokens)
                if done:
                    # the held-back tail and whatever this delivery
                    # adds, truncated where the engine stopped
                    delta = self.core.final_text(seq)[n_emitted:]
                else:
                    held += detok.feed(tokens)
                    delta = held[: max(0, len(held) - hold)]
                    held = held[len(delta):]
                    n_emitted += len(delta)
                if t_detok is not None:
                    GATEWAY.detok_end(clock, t_detok, len(tokens))
                if delta or (done and pending_lp):
                    # even a zero-length last delta: the entries of the
                    # tokens that completed a stop must not vanish
                    yield wrap(delta)
                if done:
                    break
            completed = True
        finally:
            if not completed and not seq.done_event.is_set():
                # the consumer went away mid-stream (SSE client
                # disconnect cancels the handler, closing this
                # generator) — stop burning decode steps on it
                seq.request_abort()
        if seq.status is SeqStatus.FAILED:
            raise seq.error  # type: ignore[misc]
        # streamed requests bypass the batcher, whose _normalize is
        # where non-streaming TTFT/TPOT land — observe here so the
        # vgt_* histograms cover the latency-sensitive path too (the
        # loadlab smoke drill asserts the server's TTFT view tracks the
        # client-observed one; before this, streams never fed it)
        from vgate_tpu import metrics as vgt_metrics
        from vgate_tpu.tracing import context_trace_id

        trace_id = (
            context_trace_id(request_meta.trace_ctx)
            if request_meta is not None
            and getattr(request_meta, "trace_ctx", None) is not None
            else None
        )
        for hist, value in (
            (vgt_metrics.TTFT, seq.ttft),
            (vgt_metrics.TPOT, seq.tpot),
        ):
            if value is None:
                continue
            if trace_id:
                vgt_metrics.observe_with_exemplar(
                    hist, value, trace_id=trace_id
                )
            else:
                hist.observe(value)
        if on_usage is not None:
            on_usage({
                "prompt_tokens": seq.orig_prompt_len,
                "completion_tokens": seq.num_output_tokens,
                "total_tokens": (
                    seq.orig_prompt_len + seq.num_output_tokens
                ),
            })
        if on_finish is not None:
            on_finish(seq.finish_reason)

    # -- embeddings --

    def embed(self, inputs: Sequence[str]) -> List[List[float]]:
        if self._embedder is None:
            config = self._config or get_config()
            self._embedder = Embedder(
                config.model.embedding_model_id,
                config.model.embedding_checkpoint_path,
                jnp.float32,
            )
        return self._embedder.embed(inputs)

    # -- introspection --

    def device_health(self) -> Dict[str, Any]:
        if self.core is None:
            return {"alive": False, "error": "not loaded"}
        return self.core.device_health()

    def serving_state(self) -> str:
        """Health-state-machine position ("serving" | "degraded" |
        "recovering" | "dead"); unsupervised cores are "serving" while
        alive and "dead" after a fatal."""
        if self.core is None:
            return "dead"
        state = getattr(self.core, "state", None)
        if state is not None:
            return state.value
        if getattr(self.core, "_fatal", None) is not None:
            return "dead"
        return "serving"

    def serving_health(self) -> Dict[str, Any]:
        """Engine liveness block for /health: always present, regardless
        of whether the device exposes health (satellite: app.py must not
        depend on device_health existing)."""
        health_fn = getattr(self.core, "health", None)
        if health_fn is not None:
            return health_fn()
        state = self.serving_state()
        body: Dict[str, Any] = {
            "state": state,
            "alive": state_is_alive(state),
            "ready": state_is_ready(state),
        }
        stats_fn = getattr(self.core, "get_stats", None)
        if stats_fn is not None:
            try:
                sched = (stats_fn() or {}).get("scheduler", {})
                body["queue_depth"] = sched.get("waiting", 0)
                body["running"] = sched.get("running", 0)
            except Exception:
                pass
        return body

    def get_stats(self) -> Dict[str, Any]:
        if self.core is None:
            return {}
        return self.core.get_stats()
