"""Optional SGLang comparison backend.

The reference's headline benchmark tables vLLM AND SGLang side by side;
the vLLM half landed in r3 (backends/vllm_backend.py) and this adapter
completes the pair, so that a machine that has those wheels can serve
either behind the same gateway and be driven over HTTP by the same load
(``model.engine_type: "sglang"``).

SGLang is deliberately NOT a dependency — this image has no GPU and no
egress — so the import is lazy and the error explicit.  The adapter
drives ``sglang.Engine`` (the offline engine API, the analog of
``vllm.LLM``) through OUR 4-method seam with per-request sampling
params.  Select with ``model.engine_type: "sglang"``.
"""

from __future__ import annotations

import time
from typing import Any, List, Sequence

from vgate_tpu.backends.base import GenerationResult, SamplingParams
from vgate_tpu.logging_config import get_logger

logger = get_logger(__name__)


class SGLangBackend:
    """``sglang.Engine`` behind the engine seam (comparison use)."""

    def __init__(self) -> None:
        self._engine = None
        self.model_id = ""

    def load_model(self, config: Any) -> None:
        try:
            import sglang
        except ImportError as exc:  # pragma: no cover - not in image
            raise RuntimeError(
                "engine_type 'sglang' needs the sglang package (not "
                "bundled: this deployment is TPU-native; install sglang "
                "in a GPU image to benchmark side by side)"
            ) from exc
        model_cfg = getattr(config, "model", config)
        self.model_id = getattr(model_cfg, "model_id", "")
        kwargs = {}
        max_len = getattr(model_cfg, "max_model_len", None)
        if max_len:
            kwargs["context_length"] = max_len
        quant = getattr(model_cfg, "quantization", None)
        if quant:
            logger.warning(
                "sglang backend ignores quantization=%s (no mapping to "
                "an sglang scheme); it will serve the model unquantized",
                quant,
            )
        self._engine = sglang.Engine(model_path=self.model_id, **kwargs)
        logger.info(
            "sglang backend ready",
            extra={"extra_data": {"model": self.model_id}},
        )

    def create_sampling_params(self, **kwargs: Any) -> SamplingParams:
        return SamplingParams(**kwargs)

    def generate(
        self,
        prompts: Sequence[str],
        sampling_params: Sequence[SamplingParams],
    ) -> List[GenerationResult]:
        assert self._engine is not None, "load_model first"
        sgl_params = [
            {
                "max_new_tokens": p.max_tokens,
                "temperature": p.temperature,
                "top_p": p.top_p,
                "top_k": p.top_k if p.top_k > 0 else -1,
                "stop": list(p.stop) if p.stop else None,
                "stop_token_ids": (
                    list(p.stop_token_ids) if p.stop_token_ids else None
                ),
                "frequency_penalty": p.frequency_penalty,
                "presence_penalty": p.presence_penalty,
                "min_new_tokens": p.min_tokens,
            }
            for p in sampling_params
        ]
        start = time.perf_counter()
        outs = self._engine.generate(list(prompts), sgl_params)
        wall = time.perf_counter() - start
        if isinstance(outs, dict):  # single-prompt shape
            outs = [outs]
        results = []
        for out in outs:
            meta = out.get("meta_info", {})
            n = int(meta.get("completion_tokens", 0)) or len(
                out.get("output_ids", ())
            )
            # sglang reports per-request e2e/ttft latencies in meta_info
            # when available; the batch wall is the last-resort fallback
            ttft = meta.get("ttft", meta.get("first_token_latency", wall))
            gen_time = meta.get("e2e_latency", wall)
            results.append(
                GenerationResult(
                    text=out.get("text", ""),
                    token_ids=list(out.get("output_ids", ())),
                    num_tokens=n,
                    prompt_tokens=int(meta.get("prompt_tokens", 0)),
                    metrics={
                        "ttft": ttft,
                        "gen_time": gen_time,
                        "tpot": (
                            (gen_time - ttft) / (n - 1)
                            if n > 1
                            else gen_time
                        ),
                    },
                    finish_reason=(
                        (meta.get("finish_reason") or {}).get(
                            "type", "stop"
                        )
                        if isinstance(meta.get("finish_reason"), dict)
                        else (meta.get("finish_reason") or "stop")
                    ),
                )
            )
        return results

    def shutdown(self) -> None:
        if self._engine is not None:
            shutdown = getattr(self._engine, "shutdown", None)
            if shutdown is not None:
                shutdown()
        self._engine = None
