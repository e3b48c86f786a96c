"""Is the served path computing the model?  A few fixed prompts go
through the gateway (prefill, then decode through the paged cache) with
``logprobs``; the served top-k log-probabilities are compared with the
plain reference's full forward on the same token ids (teacher-forced on
the ids the server chose, so a rounding flip of an argmax cannot fail
the check).

Expected values are cached in the checkout under a key of everything
they depend on (configuration, weights seed, the token ids, the
reference's source), so only the first run of a cell pays the reference.

Tolerance: absolute difference of log-probabilities, from the
configuration file: one on the largest difference and a tighter one on
the mean (rounding scatters, a lost precision shifts everything).  With N(0, 0.02) weights the logits have a standard
deviation near 0.8 and the served path computes in bfloat16 (8 mantissa
bits) through every layer, against float32 here: differences of a few
hundredths are rounding.  A wrong shape, a missing layer, a cache that
returns another sequence's pages or int8 arithmetic in place of bf16
moves the top-k log-probabilities by tenths or more.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import subprocess
import sys
from typing import Any, Dict, List, Optional

from . import traffic
from .server import ROOT, BenchFailure, post_json

BYTE_OFFSET = 3  # runtime/tokenizer.py ByteTokenizer.OFFSET


def prompts(config: Dict[str, Any]) -> List[str]:
    ref = config["reference"]
    rng = random.Random(f"{config['name']}:reference")
    floor = traffic.min_prompt_tokens()
    return [traffic.text(rng, n - floor + 1) for n in ref["prompt_tokens"]]


def ask(base: str, model: str, config: Dict[str, Any]) -> Dict[str, Any]:
    """Send the check prompts; returns the token sequences, where the
    generated part starts, and the served top-k ids and log-probabilities
    per generated position."""
    ref = config["reference"]
    job: Dict[str, Any] = {"weights_seed": ref["weights_seed"],
                           "sequences": [], "first": [], "top_ids": [],
                           "served": []}
    for content in prompts(config):
        messages = [{"role": "user", "content": content}]
        resp = post_json(base, "/v1/chat/completions", {
            "model": model, "messages": messages, "temperature": 0,
            "max_tokens": ref["decode_tokens"], "logprobs": True,
            "top_logprobs": ref["top_logprobs"],
        })
        flat = "User: " + content + "\nAssistant:"
        prompt_ids = [BYTE_OFFSET + b for b in flat.encode()]
        if resp["usage"]["prompt_tokens"] != len(prompt_ids):
            raise BenchFailure(
                f"prompt of {len(prompt_ids)} byte tokens counted as "
                f"{resp['usage']['prompt_tokens']}: the gateway's chat "
                "flattening or the byte tokenizer changed"
            )
        entries = resp["choices"][0]["logprobs"]["content"]
        gen = [e["token_id"] for e in entries]
        job["sequences"].append(prompt_ids + gen)
        job["first"].append(len(prompt_ids))
        job["top_ids"].append(
            [[t["token_id"] for t in e["top_logprobs"]] for e in entries]
        )
        job["served"].append(
            [[t["logprob"] for t in e["top_logprobs"]] for e in entries]
        )
    return job


def _cache_path(config: Dict[str, Any], job: Dict[str, Any]) -> str:
    with open(os.path.join(ROOT, "perfbench", "reference.py"), "rb") as fh:
        source = fh.read()
    with open(config["_path"], "rb") as fh:
        cfg_bytes = fh.read()
    key = hashlib.sha256(
        source + cfg_bytes + json.dumps(
            [job["weights_seed"], job["sequences"], job["top_ids"]]
        ).encode()
    ).hexdigest()[:24]
    return os.path.join(ROOT, ".perfbench_cache", "reference",
                        f"{config['name']}.{key}.json")


class Reference:
    """The reference's values for ``job``: from the cache, or from a CPU
    process started now and joined later (it overlaps the warm-up)."""

    def __init__(self, config: Dict[str, Any], job: Dict[str, Any],
                 rehearse_cfg: Optional[Dict[str, Any]] = None) -> None:
        self.config, self.job = config, job
        self.path = _cache_path(config, job)
        self.proc: Optional[subprocess.Popen] = None
        self.cached = os.path.exists(self.path)
        if self.cached:
            return
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        job_path = self.path + ".job"
        with open(job_path, "w") as fh:
            json.dump(job, fh)
        cfg_path = config["_path"]
        if rehearse_cfg is not None:  # the tiny preset's sizes
            cfg_path = self.path + ".cfg"
            with open(cfg_path, "w") as fh:
                json.dump(rehearse_cfg, fh)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "perfbench.reference", cfg_path,
             job_path, self.path + ".tmp"],
            cwd=ROOT, env=env,
        )

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def compare(self, timeout_s: float = 900.0) -> Dict[str, Any]:
        if self.proc is not None:
            try:
                rc = self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.stop()
                raise BenchFailure("the reference did not finish") from None
            if rc != 0:
                raise BenchFailure(f"the reference exited rc={rc}")
            os.replace(self.path + ".tmp", self.path)
        with open(self.path) as fh:
            expected = json.load(fh)["logprobs"]
        diffs = [
            abs(a - b)
            for served_seq, ref_seq in zip(self.job["served"], expected)
            for served, ref in zip(served_seq, ref_seq)
            for a, b in zip(served, ref)
        ]
        ref = self.config["reference"]
        tol, mean_tol = float(ref["tolerance"]), float(ref["mean_tolerance"])
        worst = max(diffs, default=0.0)
        mean = sum(diffs) / max(1, len(diffs))
        return {"ok": bool(diffs) and worst <= tol and mean <= mean_tol,
                "max_abs_diff": worst, "mean_abs_diff": mean,
                "compared": len(diffs), "tolerance": tol,
                "mean_tolerance": mean_tol, "cached": self.cached}
