"""Optional vLLM comparison backend.

The reference's headline benchmark runs vLLM and SGLang side by side
(both backends in one table); this adapter restores that capability for
apples-to-apples GPU-vs-TPU comparisons when a ``vllm`` wheel is
present.  It is a thin
adapter over ``vllm.LLM.generate`` mapped onto OUR 4-method seam and
per-request ``SamplingParams`` (the reference applies the first
request's temperature to the whole batch, vgate/batcher.py:271; vLLM
itself supports per-request params, so we pass them through per
prompt).

vLLM is deliberately NOT a dependency — this image has no GPU and no
egress — so the import is lazy and the error is explicit.  Select with
``model.engine_type: "vllm"``; the gateway, and so the workload lab
and the benchmark (perfbench/README.md), then drive it over HTTP like
any other engine.
"""

from __future__ import annotations

import time
from typing import Any, List, Sequence

from vgate_tpu.backends.base import GenerationResult, SamplingParams
from vgate_tpu.logging_config import get_logger

logger = get_logger(__name__)


class VLLMBackend:
    """``vllm.LLM`` behind the engine seam (comparison use)."""

    def __init__(self) -> None:
        self._llm = None
        self.model_id = ""

    def load_model(self, config: Any) -> None:
        try:
            from vllm import LLM
        except ImportError as exc:  # pragma: no cover - no vllm in image
            raise RuntimeError(
                "engine_type 'vllm' needs the vllm package (not bundled: "
                "this deployment is TPU-native; install vllm in a GPU "
                "image to benchmark side by side)"
            ) from exc
        model_cfg = getattr(config, "model", config)
        self.model_id = getattr(model_cfg, "model_id", "")
        kwargs = {}
        quant = getattr(model_cfg, "quantization", None)
        if quant:
            # our int8/int4 schemes don't map onto vLLM's awq/gptq
            # checkpoints — say so loudly instead of silently comparing
            # quantized TPU numbers against fp16 vLLM numbers
            logger.warning(
                "vllm backend ignores quantization=%s (no mapping to a "
                "vLLM scheme); it will serve the model unquantized",
                quant,
            )
        max_len = getattr(model_cfg, "max_model_len", None)
        if max_len:
            kwargs["max_model_len"] = max_len
        self._llm = LLM(model=self.model_id, **kwargs)
        logger.info(
            "vllm backend ready",
            extra={"extra_data": {"model": self.model_id}},
        )

    def create_sampling_params(self, **kwargs: Any) -> SamplingParams:
        return SamplingParams(**kwargs)

    def generate(
        self,
        prompts: Sequence[str],
        sampling_params: Sequence[SamplingParams],
    ) -> List[GenerationResult]:
        from vllm import SamplingParams as VSP

        assert self._llm is not None, "load_model first"
        vsp = [
            VSP(
                max_tokens=p.max_tokens,
                min_tokens=p.min_tokens,
                temperature=p.temperature,
                top_p=p.top_p,
                top_k=p.top_k if p.top_k > 0 else -1,
                stop=p.stop,
                stop_token_ids=p.stop_token_ids,
                seed=p.seed,
                logprobs=(p.top_logprobs or 1) if p.logprobs else None,
                frequency_penalty=p.frequency_penalty,
                presence_penalty=p.presence_penalty,
            )
            for p in sampling_params
        ]
        start = time.perf_counter()
        outs = self._llm.generate(list(prompts), vsp)
        wall = time.perf_counter() - start
        results = []
        for out in outs:
            comp = out.outputs[0]
            n = len(comp.token_ids)
            # per-request timings from vLLM's own RequestMetrics when
            # present (first_token_time etc.); the batch wall is only
            # the last-resort fallback so side-by-side tables compare
            # real TTFT/TPOT, not a shared wall-clock smear
            m = getattr(out, "metrics", None)
            arrival = getattr(m, "arrival_time", None)
            first = getattr(m, "first_token_time", None)
            finished = getattr(m, "finished_time", None)
            ttft = (
                first - arrival
                if first is not None and arrival is not None
                else wall
            )
            gen_time = (
                finished - arrival
                if finished is not None and arrival is not None
                else wall
            )
            results.append(
                GenerationResult(
                    text=comp.text,
                    token_ids=list(comp.token_ids),
                    num_tokens=n,
                    prompt_tokens=len(out.prompt_token_ids or ()),
                    metrics={
                        "ttft": ttft,
                        "gen_time": gen_time,
                        "tpot": (
                            (gen_time - ttft) / (n - 1)
                            if n > 1
                            else gen_time
                        ),
                    },
                    finish_reason=comp.finish_reason or "stop",
                )
            )
        return results

    def shutdown(self) -> None:
        self._llm = None
