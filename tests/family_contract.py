"""What a layer family owes the ENGINE, written once (PR 43).

Each family of ``models/hybrid.py`` serves its tiny preset through
``EngineCore`` and holds the served top log-probabilities to the plain
reference's full forward on the same seeded weights, in a test file of
its own (``test_hybrid_model``, ``test_nemotron_h_model``,
``test_mla_model``, ``test_exaone_moe_engine``, ``test_glm_dsa_engine``:
one file is one xdist worker's (``tests/conftest.py`` has the rule), so
one worker holds one family's compiles).  A ``Family`` says what
differs; the functions below are the behaviours every family is held
to, each a body that takes the record.
A family's own checks (what ``/stats`` reports, its counters) stay in
its file, after the call.  ROADMAP.md D0 lists the (family, behaviour)
pairs no file calls yet.
"""

import contextlib
import dataclasses
import functools
import json
import os
import subprocess
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.runtime.engine_core import EngineCore, replay_into
from vgate_tpu.runtime.sequence import Sequence


@dataclasses.dataclass(frozen=True)
class Family:
    # the benchmark configuration whose rehearsal serves the tiny preset
    # (perfbench/configs): the preset's name, and the preset under the
    # published config's keys for the reference
    file: str
    ref: Any  # the plain reference's module (perfbench/references)
    tol: dict  # dtype -> the largest |served - reference| a test allows
    tpu: dict  # what every engine of the family starts from
    max_model_len: int = 128
    # the reference takes weights drawn ahead (``draw_weights``); the
    # others draw from (seed, dtype) themselves
    draws_weights: bool = False
    # what the family keeps beside pages, in the words of the engine's
    # refusals ("" = pages alone)
    keeps: str = ""

    @functools.cached_property
    def _rehearsal(self) -> dict:
        return manifest.load_json(
            manifest.HERE, "configs", self.file)["rehearse"]

    @property
    def model_id(self) -> str:
        return self._rehearsal["preset"]

    @property
    def cfg(self) -> dict:
        return self._rehearsal["model"]

    def config(self, tpu=None, dtype="float32", model_id=None, **sections):
        base = {"dp": 1, "tp": 1, "ep": 1, "sp": 1, "use_pallas": False,
                **self.tpu, **(tpu or {})}
        return load_config(
            model={"model_id": model_id or self.model_id,
                   "engine_type": "jax_tpu", "dtype": dtype,
                   "max_model_len": self.max_model_len},
            tpu=base, scheduler={"max_queue_size": 16},
            logging={"level": "WARNING"}, **sections,
        )

    def reference(self, cfg, dtype, full, n_prompt):
        """The reference's log-probabilities of ``full[n_prompt:]``."""
        if self.draws_weights:
            logprobs = functools.partial(self.ref.logprobs, cfg, _drawn(
                self.ref, json.dumps(cfg, sort_keys=True), dtype))
        else:
            logprobs = functools.partial(
                self.ref.logprobs, cfg, 0, jnp.dtype(dtype))
        return one_length(logprobs, full, n_prompt, self.max_model_len)


def one_length(logprobs, seq, first, length):
    """``logprobs([seq], [first])[0]`` by way of ONE shape: the sequence
    padded with token 0 to ``length``, and the rows of what was padded
    dropped.  The references are causal (no row reads a later token),
    and dispatch their operations one by one, each a program compiled a
    shape: at its own length every sequence pays for all of them again
    (``tests/test_granite_hybrid_engine.py`` alone: 86 s, 66 s at one
    length; an expert layer's loop still compiles a count of rows)."""
    assert first <= len(seq) <= length, (first, len(seq), length)
    padded = list(seq) + [0] * (length - len(seq))
    return logprobs([padded], [first])[0][:len(seq) - first]


@functools.lru_cache(maxsize=None)
def _drawn(ref, cfg_json, dtype):
    return ref.draw_weights(json.loads(cfg_json), 0, jnp.dtype(dtype))


@contextlib.contextmanager
def booted(family, tpu=None, *, spec=None, devices=1, **config):
    """A started engine of the family, stopped on the way out."""
    core = EngineCore(family.config(tpu, **config), spec=spec,
                      devices=jax.devices()[:devices])
    core.start()
    try:
        yield core
    finally:
        core.stop()


def lp_params(max_tokens):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          logprobs=True, top_logprobs=5)


def tokens(rng, n):
    return [int(t) for t in rng.integers(3, 259, size=n)]


def run(core, prompts, max_tokens=6):
    seqs = [core.submit_tokens(p, lp_params(max_tokens)) for p in prompts]
    for s in seqs:
        assert s.done_event.wait(timeout=600)
        assert s.error is None, s.error
    return seqs


def agree(family, core, seq, prompt, cfg=None, dtype="float32"):
    """The served top log-probabilities of every generated token against
    the reference's full forward on prompt + generated."""
    full = list(prompt) + list(seq.generated_ids)
    want = family.reference(cfg or family.cfg, dtype, full, len(prompt))
    entries = core.logprob_entries(seq)
    assert len(entries) == len(seq.generated_ids)
    diffs = [abs(t["logprob"] - want[pos, t["token_id"]])
             for pos, e in enumerate(entries) for t in e["top_logprobs"]]
    assert diffs and max(diffs) < family.tol[dtype], (
        max(diffs), np.mean(diffs))


# ---- the rehearsal: a family's benchmark cell end to end on the CPU

# ONE window: in 4 s a loaded host answers nothing and the window closes
# with ``attempted: 0`` (PERF.md section 7, PR 43 item 2)
REHEARSAL_WINDOW_S = "12"
# ONE limit: alone a rehearsal takes 60-260 s; beside five other workers
# one has taken five times its time alone (CHANGES.md, PR 31)
REHEARSAL_LIMIT_S = 1400


def rehearse(cell, seed):
    """``cell`` rehearsed as the benchmark runs it: the family's tiny
    preset behind the real gateway in a process of its own, every phase
    of a traced run, the answers held to the configuration's own plain
    reference.  Returns the run's result line, which ended correct, with
    nothing failed and something attempted; the family's file asserts
    what is its own (``compared``, its tolerance, the metrics it must
    and must not report).  Every family's rehearsal is tier-1, and the
    files are apart so that they land on different workers
    (``conftest.py LONGEST_FIRST``); ``tests/test_rehearsal_lint.py``
    keeps this the only place that spells the command."""
    # the server's own XLA flags, not the tests' eight virtual devices
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", cell,
         "--seed", str(seed), "--seconds", REHEARSAL_WINDOW_S,
         "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=REHEARSAL_LIMIT_S,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, {
        k: result[k] for k in ("attempted", "failed", "reference",
                               "in_window")}
    assert result["attempted"] >= 1 and result["rehearsal"] is True
    assert result["reference"]["ok"]
    return result


# ---- the contract


def unequal_rows(family, core, lens, max_tokens=6, dtype="float32"):
    """Prompts of unequal length in ONE wave, each a whole-prompt pass
    and decode steps, match the reference."""
    rng = np.random.default_rng(1)
    prompts = [tokens(rng, n) for n in lens]
    for p, s in zip(prompts, run(core, prompts, max_tokens)):
        agree(family, core, s, p, dtype=dtype)


def chunked_prefill_and_slot_reuse(family, chunk, lens, max_tokens=(8, 6),
                                   tpu=None, cfg=None, **variant):
    """ONE slot.  A prompt longer than ``chunk`` goes in chunk by chunk,
    what the family keeps carried from one to the next; then a short
    prompt takes the same slot: the longer tenant must have left nothing
    behind, and every page is given back."""
    tpu = {"prefill_chunk": chunk, "prefill_buckets": [chunk // 2, chunk],
           "max_batch_slots": 1, **(tpu or {})}
    with booted(family, tpu, **variant) as core:
        rng = np.random.default_rng(4)
        long_prompt, short_prompt = tokens(rng, lens[0]), tokens(rng, lens[1])
        (a,) = run(core, [long_prompt], max_tokens[0])
        (b,) = run(core, [short_prompt], max_tokens[1])
        agree(family, core, a, long_prompt, cfg)
        agree(family, core, b, short_prompt, cfg)
        assert core.allocator.num_used == 0 or core.prefix_cache_enabled


def preemption_by_recompute(family, tpu, cfg=None, **variant):
    """A pool too small for three sequences: one is preempted and
    recomputed, which rebuilds what the family keeps beside pages."""
    with booted(family, {"decode_chunk": 1, **tpu}, **variant) as core:
        rng = np.random.default_rng(5)
        prompts = [tokens(rng, n) for n in (17, 18, 16)]
        seqs = run(core, prompts, max_tokens=10)
        assert core.scheduler.total_preemptions >= 1
        assert any(s.preempt_count for s in seqs)
        for p, s in zip(prompts, seqs):
            assert s.num_output_tokens == 10
            agree(family, core, s, p, cfg)


def journal_replay(family, core, prompt_len=11):
    """A request caught after three tokens and replayed from its
    checkpoint (what the family keeps is rebuilt by prefilling prompt +
    partial) ends in the same tokens and the reference's logits."""
    rng = np.random.default_rng(6)
    prompt = tokens(rng, prompt_len)
    (whole,) = run(core, [prompt], max_tokens=8)
    partial = Sequence(prompt_ids=list(prompt), params=lp_params(8))
    for t in whole.generated_ids[:3]:
        partial.append_token(t)
    restored = Sequence.from_checkpoint(partial.checkpoint())
    assert replay_into(core, restored, set()) == "replayed"
    assert restored.done_event.wait(timeout=600)
    assert restored.generated_ids == whole.generated_ids
    want = family.reference(family.cfg, "float32",
                            prompt + whole.generated_ids, len(prompt))
    tail = core.logprob_entries(restored)[-5:]
    diffs = [abs(t["logprob"] - want[3 + pos, t["token_id"]])
             for pos, e in enumerate(tail) for t in e["top_logprobs"]]
    assert max(diffs) < family.tol["float32"]


def prefix_hit_on_whole_pages(family, counter, cfg=None, **variant):
    """Two prompts share their first 64 tokens (whole pages).  The
    second is a prefix hit: its suffix (21 tokens) alone goes through the
    prompt pass, against what the first left on the shared pages, and
    its answer is the reference's all the same.  Returns how far
    ``counter(core)`` moved over the second request."""
    with booted(family, **variant) as core:
        assert core.prefix_cache_enabled
        rng = np.random.default_rng(9)
        shared = tokens(rng, 64)
        first, second = shared + tokens(rng, 7), shared + tokens(rng, 21)
        (a,) = run(core, [first])
        before = counter(core)
        (b,) = run(core, [second])
        moved = counter(core) - before
        agree(family, core, a, first, cfg)
        agree(family, core, b, second, cfg)
        assert core.allocator.prefix_hits > 0 or (
            core.radix_cache is not None
            and core.radix_cache.get_stats()["hits"] > 0)
        return moved


# what knows K and V pages only, each (config sections, devices, the
# name the refusal gives it)
REFUSALS = [
    ({"tpu": {"speculative_k": 2}}, 1, "speculative decoding"),
    ({"kv_cache": {"host_swap_bytes": 1 << 20}}, 1, "host swap"),
    ({"kv_cache": {"dtype": "int8"}}, 1, "int8"),
    ({"model": {"quantization": "int8"}}, 1, "model.quantization"),
    ({"pod": {"workers": 2, "roles": ["prefill", "decode"]}}, 1,
     "handoff of a live sequence"),
    ({"tpu": {"tp": 2}}, 2, "'tp': 2"),
    ({"tpu": {"pp": 2}}, 2, "'pp': 2"),
    ({"tpu": {"sp": 2}}, 2, "'sp': 2"),
]


def construction_refuses(family, sections, devices, named):
    """Engine construction refuses by name, and says what the family
    keeps beside pages."""
    sections = dict(sections)
    model = sections.pop("model", {})
    cfg = family.config(sections.pop("tpu", None), **sections)
    if model:
        cfg.model.quantization = model["quantization"]
    with pytest.raises(ValueError, match=family.keeps) as exc:
        EngineCore(cfg, devices=jax.devices()[:devices])
    assert named in str(exc.value)
