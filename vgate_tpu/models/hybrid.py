"""The layer stack of several kinds: residual sub-blocks ``h <- h +
f(norm(h))``, each ONE of a few kinds, given by the spec as data
(``ModelSpec.stack``: an entry a layer, its sub-blocks' kinds in order,
whichever way the spec's fields spell them; the walker takes from it
``lead_blocks``, ``period_blocks`` and ``num_periods``):

* ``gdn``   Gated DeltaNet linear attention (Qwen3-Next),
* ``mamba`` a Mamba-2 state-space layer (Nemotron-H's, with eight B/C
  groups; Granite 4.0-H's, with one for all its heads),
* ``attn``  softmax attention over the paged pool (gated, with per-head
  norms and partial rotary, or plain without rotary: the spec says),
* ``mla``   multi-head latent attention over the LATENT paged pool
  (Mistral-Small-4, DeepSeek's form): the pool holds one row ``[c_kv |
  k_rope]`` a token and no K or V; a prompt pass expands K and V from
  the rows (non-absorbed), a decode step folds the expansion into the
  query and the output and reads the rows alone (absorbed),
* ``dsa``   the same latent attention under a learned SELECTION
  (GLM-5.2, DeepSeek-V3.2's DSA; ops/dsa.py): the layer's indexer scores
  every cached token against ONE index key a token (the pool's second
  array, same page table) and picks ``index_topk``; its attention, and
  that of the ``mla`` layers behind it up to the next ``dsa`` layer,
  runs over the pick alone.  The pick rides the walker's carry (``"sel"``
  in the state dict for the walk: positions ``[B, k]`` in a decode step,
  a mask ``[B, S, T]`` in a prompt pass): it is activations, not cache.
  Under ``ModelSpec.kv_rows`` (Keye-VL-2.0's language model) the kind is
  GQA attention (``attn``'s front half) under a selection that EVERY
  layer makes for itself from its normed input: the pool holds a token's
  K over its V as one pair of rows, nothing rides the carry
  (``_kv_dsa_prompt`` / ``_kv_dsa_step``),
* ``swa``   softmax attention over the last ``sliding_window`` tokens
  (K-EXAONE's window layers), whose K/V is the decode slot's RING and
  holds no page of the pool (below),
* ``eva``   EVA attention (EvaByte; ops/eva.py): a token is held exactly
  while its window of ``eva_window`` is open, in the slot's pages BEHIND
  the allocator's in the same pool arrays, and as its chunk's summary
  row in the sequence's pages once the window has closed; exact rows
  and summary rows meet in one softmax,
* ``conv``  LFM2's gated short convolution: ``[B | C | X] = u W_in``, a
  depth-wise causal convolution of three taps over ``B * X`` with no
  activation, ``(C * c) W_out``.  What it carries from token to token
  is the last two rows of ``B * X``: a convolution tail a decode slot
  and NO tile (``{"conv": [conv layers, slots, K-1, D]}`` is the whole
  state),
* ``mlp``   a dense SwiGLU feed-forward (a leading layer's, or every
  layer's of an ``eva`` or a ``mamba_pattern`` stack),
* ``moe``   the expert layer of ``ops/moe.py``.

Qwen3-Next's layer is two sub-blocks (a mixer, then experts); its period
is ``gdn moe gdn moe gdn moe attn moe``.  A Nemotron-H layer is one:
``EMEMEMEMEM*`` is ``moe mamba`` five times, then ``attn``.  A
Mistral-Small-4 layer is ``mla moe``, and its stack has no recurrent
layer: the state is then ``None`` and the second pool too.  A Granite
4.0-H layer is ``mamba mlp`` or ``attn mlp``, ten to a period, and every
sub-block's output is scaled by ``residual_multiplier`` at the walker's
ONE residual add.  A K-EXAONE
layer is ``swa moe`` three times to one ``attn moe``, behind LEADING
layers that the walker runs once, ahead of the scan (layer 0, ``swa
mlp``, and as many more as leave whole periods: ``ModelSpec.lead_layers``,
one search over the stack for every spelling).

``models/decoder.py``'s forwards hand a hybrid spec's work here after
they have chosen the attention implementation, so the step programs,
their cache threading and the kernels are the dense models' own.  What
differs:

* ONE walker (``_period_scan``) scans PERIODS and, inside one, runs the
  sub-blocks in order: a unit of sub-blocks that repeats (``gdn moe`` x
  3, ``moe mamba`` x 5) as an inner scan, one body compiled for all its
  repeats, the rest unrolled.  The K/V pools of the attention sub-blocks
  only (``[attention layers, KV, P, ps, hd]``) and the recurrent state
  of the recurrent ones ride the carry, all updated in place;
* the recurrent state (``{"S": [Lr, slots, heads, ., .] float32, "conv":
  [Lr, slots, K-1, C]}``: ``[32, 128, 128]`` tiles over 8,192 channels
  for ``gdn``; for ``mamba`` ``[128, 64, 128]`` over 10,240 at
  Nemotron-H's published sizes and ``[64, 64, 128]`` over 4,352 at
  Granite 4.0-H's) is indexed by decode SLOT.  A prompt pass starts from
  zeros (or, for a later chunk of a chunked prefill, from the slot's
  row), runs the chunk-wise recurrence and overwrites the row whole; a
  decode step updates the rows of active slots in place and leaves idle
  rows alone.  Padded prompt positions get ``g = 0, beta = 0`` (``dt =
  0``) and the convolution tail is taken at the row's real length, so a
  bucket's padding never moves the state;
* a window layer's K/V is a RING, and a ring is state: ``{"ring_k",
  "ring_v": [window layers, KV, 1 + slots x R, ps, hd]}``, a pool of
  its own whose page 0 is trash and whose pages ``1 + slot x R ..`` are
  the slot's ``R = ceil(window / ps) + 1`` ring pages.  The ring's page
  table is arithmetic, not allocation: a sequence's page ``p`` is its
  slot's ring page ``p mod R`` (``ring_tables``), so the decode kernel
  with its ``window`` argument fetches the live pages alone and writes
  the step's token where ``kv_write_tokens`` would; the page a token
  lands in held positions ``R x ps`` back, all below the window.  A
  prompt pass attends in flight and writes only the pages that survive
  (the last ``R`` that hold a real token; the others go to the trash
  page: a scatter that hits one ring page twice has no defined order);
  a later chunk reads the ring BEFORE it writes, beside its own rows;
* the experts' matrices never ride the scan's per-period slices: the
  grouped product's kernel takes the full stack and a layer index;
* a long whole prompt's pass (a bucket of two ``PROMPT_ROW_BLOCK`` or
  more) does its position-wise work in a counted loop over the blocks
  of rows its longest prompt reaches (``_by_row_blocks``), and its
  attention kernels skip the query blocks past them: a 5,500-token
  prompt in the 8,192 bucket pays for 6,144 rows.  What engages it is
  the traced shape alone; a decode step, a short wave, a suffix and a
  chunk are traced as ever.  A stack of ONE kind (models/decoder.py
  ``_packed_prompt_pass``) runs the same loop over its group's real
  rows laid end to end; ``prompt_rows`` is the rule for both.

Parameters (``init_layers``): ``layers = {group: {name: [P, n, ...]}}``
with ``P`` periods and ``n`` layers of the group a period.  Qwen3-Next's
groups are ``linear`` (a Gated DeltaNet layer and its experts) and
``full`` (the attention layer and its experts, ``[P, ...]``: it is one a
period); a pattern's groups are its kinds, ``mamba`` / ``attn`` /
``moe``; K-EXAONE's are ``window`` and ``global`` (attention and
experts of a window or a full layer) and ``lead``, a TUPLE of the
leading layers' own trees; an ``indexer_pattern`` spec's are ``pick``
and ``reuse`` (latent attention with and without an indexer, and the
layer's experts) and ``lead``; a ``kv_rows`` spec's is ``layer``
(attention, indexer and experts); a ``conv_pattern`` spec's are ``conv``
and ``attn`` (the mixer and the layer's experts) and ``lead``; a
``mamba_pattern`` spec's are ``mamba`` and ``attn`` (the mixer and the
layer's dense SwiGLU).
"""

from __future__ import annotations

import functools
import math
from collections.abc import Mapping
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.ops import dsa, eva
from vgate_tpu.ops import gated_delta as gd
from vgate_tpu.ops import ssd
from vgate_tpu.ops.kv_quant import (
    by_pairs, gather_pages, kv_write_pages, kv_write_tokens, page_tokens,
)
from vgate_tpu.ops.moe import STAT_NAMES, combine_stats, expert_layer
from vgate_tpu.ops.norms import rms_norm
from vgate_tpu.ops.attention import flash_prefill_attention, mla_gather_rows
from vgate_tpu.ops.rope import apply_rope, position_scale
from vgate_tpu.utils.math import cdiv


def init_layers(spec: ModelSpec, key, dtype, normal, norm_init
                ) -> Dict[str, Any]:
    """Random draw of a hybrid spec's layer tensors, each family from
    keys of its own."""
    if spec.kv_rows:
        return _init_kv_dsa_layers(spec, key, dtype, normal)
    if spec.is_dsa:
        return _init_dsa_layers(spec, key, dtype, normal)
    if spec.eva_layers:
        return _init_eva_layers(spec, key, dtype, normal, norm_init)
    if spec.is_mla:
        return _init_mla_layers(spec, key, dtype, normal, norm_init)
    if spec.window_pattern:
        return _init_window_layers(spec, key, dtype, normal)
    if spec.conv_pattern:
        return _init_conv_layers(spec, key, dtype, normal)
    if spec.mamba_pattern:
        return _init_mamba_mlp_layers(spec, key, dtype, normal)
    if spec.layer_pattern:
        return _init_pattern_layers(spec, key, dtype, normal)
    return _init_paired_layers(spec, key, dtype, normal, norm_init)


def _per_layer(spec: ModelSpec, group: str, fn):
    """fn(key) -> one layer's tensor; returns key -> the group's
    ``[P, n, ...]``, one fused program that draws a layer at a time
    (tensor of layer i from ``fold_in(key, i)``): no draw stands wider
    than one layer's tensor."""
    P, n = spec.num_periods, spec.group_layers(group)
    return jax.jit(lambda k: jax.lax.map(
        lambda i: fn(jax.random.fold_in(k, i)), jnp.arange(P * n)
    ).reshape((P, n) + jax.eval_shape(fn, k).shape))


def _init_eva_layers(spec: ModelSpec, key, dtype, normal, norm_init
                     ) -> Dict[str, Any]:
    """An EVA stack's tensors (every layer ``eva mlp``) from
    ``fold_in(key, 44)`` split 16 ways, tensor ``j`` of layer ``i`` from
    ``fold_in(key j, i)``.  The matrices N(0, 0.02); the norms at their
    identity.  ``q`` and ``k`` N(0, 1 / hidden): a query's and a key's
    elements then have a standard deviation near 1 and so have the
    scores ``s q . k``, at the published width and at a toy one (at 0.02
    and a hidden size of 64 they spread by 0.03: attention is flat, and
    a wrong window, a stale summary row or a summary of the wrong chunk
    would hide under a comparison's tolerance).  The two learned vectors
    of a head N(0, 1): ``s k . phi`` spreads by about 1 inside a chunk,
    so the chunk softmax is far from uniform, and ``mu`` is of the keys'
    own size."""
    ek = jax.random.split(jax.random.fold_in(key, 44), 16)
    D, H, KV, hd = (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    F = spec.intermediate_size
    lead = (spec.num_periods, 1)
    draw = lambda j, shape, scale=0.02: _per_layer(
        spec, "layer", lambda kk: normal(kk, shape, scale))(ek[j])
    return {"layer": {
        "input_norm": norm_init(lead + (D,), dtype),
        "post_norm": norm_init(lead + (D,), dtype),
        "q": {"w": draw(0, (D, H * hd), D ** -0.5)},
        "k": {"w": draw(1, (D, KV * hd), D ** -0.5)},
        "v": {"w": draw(2, (D, KV * hd))},
        "o": {"w": draw(3, (H * hd, D))},
        "eva_phi": draw(4, (KV, hd), 1.0),
        "eva_mu": draw(5, (KV, hd), 1.0),
        "gate": {"w": draw(6, (D, F))},
        "up": {"w": draw(7, (D, F))},
        "down": {"w": draw(8, (F, D))},
    }}


def _init_mla_layers(spec: ModelSpec, key, dtype, normal, norm_init
                     ) -> Dict[str, Any]:
    """The tensors of a latent-attention stack (every layer ``mla
    moe``), from ``fold_in(key, 33)`` split 32 ways, a layer at a time.
    N(0, 0.02) but for the two up-projections ``q_b`` and ``kv_b``,
    N(0, 0.05), and the norms at 1: at 0.02 throughout the scores are so
    flat that a wrong rotary, a wrong softmax scale or a stale latent
    row would hide under a comparison's tolerance.  ``kv_b`` is drawn as
    the checkpoint holds it, ``[kv_lora_rank, heads x (nope + v)]``, and
    split per head into ``kv_b_k`` (W_uk) and ``kv_b_v`` (W_uv)."""
    mk = jax.random.split(jax.random.fold_in(key, 33), 32)
    D, H = spec.hidden_size, spec.num_heads
    ql, kl = spec.q_lora_rank, spec.kv_lora_rank
    nope, rope, vd = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    Fs = spec.shared_expert_intermediate_size
    lead = (spec.num_periods, 1)

    def draw(k, shape, scale=0.02):
        return _per_layer(spec, "layer",
                          lambda kk: normal(kk, shape, scale))(k)

    kv_b = draw(mk[3], (kl, H, nope + vd), 0.05)
    out = {
        "input_norm": norm_init(lead + (D,), dtype),
        "post_norm": norm_init(lead + (D,), dtype),
        "q_a": {"w": draw(mk[0], (D, ql))},
        "q_a_norm": norm_init(lead + (ql,), dtype),
        "q_b": {"w": draw(mk[1], (ql, H * (nope + rope)), 0.05)},
        "kv_a": {"w": draw(mk[2], (D, kl + rope))},
        "kv_a_norm": norm_init(lead + (kl,), dtype),
        "kv_b_k": {"w": kv_b[..., :nope]},
        "kv_b_v": {"w": kv_b[..., nope:]},
        "o": {"w": draw(mk[4], (H * vd, D))},
        "router": draw(mk[5], (D, R)),
        "gate": {"w": draw(mk[6], (E, D, Fe))},
        "up": {"w": draw(mk[7], (E, D, Fe))},
        "down": {"w": draw(mk[8], (E, Fe, D))},
    }
    if Fs:
        out["shared_gate"] = {"w": draw(mk[9], (D, Fs))}
        out["shared_up"] = {"w": draw(mk[10], (D, Fs))}
        out["shared_down"] = {"w": draw(mk[11], (Fs, D))}
    if Fs and spec.shared_expert_gate:
        out["shared_router"] = draw(mk[12], (D,))
    return {"layer": out}


def _init_dsa_layers(spec: ModelSpec, key, dtype, normal
                     ) -> Dict[str, Any]:
    """An ``indexer_pattern`` spec's tensors from ``fold_in(key, 40)``
    split 32 ways: tensor ``j`` of layer ``i`` (its index in the WHOLE
    stack, leading layers included) from ``fold_in(key j, i)``.  The
    latent attention as ``_init_mla_layers`` draws it (N(0, 0.02) but
    the two up-projections N(0, 0.05), norms 1); the indexer N(0, 0.02),
    its key's LayerNorm weight 1 and bias 0: with the query latent and
    the key both normed an index score's dot products have a standard
    deviation near 10, so the pick is far from the first 2,048 or the
    last, and a wrong rotation or a stale index key moves it.  The
    router's selection bias N(0, 0.02) and NOT zero."""
    dk = jax.random.split(jax.random.fold_in(key, 40), 32)
    D, H = spec.hidden_size, spec.num_heads
    ql, kl = spec.q_lora_rank, spec.kv_lora_rank
    nope, rope, vd = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
    Hi, di = spec.index_n_heads, spec.index_head_dim
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    F, Fs = spec.intermediate_size, spec.shared_expert_intermediate_size
    ones = lambda *shape: jnp.ones(shape, dtype)
    attn = {"q_a": (0, (D, ql), 0.02), "q_b": (1, (ql, H * (nope + rope)), 0.05),
            "kv_a": (2, (D, kl + rope), 0.02), "o": (4, (H * vd, D), 0.02)}
    index = {"index_q": (16, (ql, Hi * di), 0.02),
             "index_k": (17, (D, di), 0.02), "index_w": (18, (D, Hi), 0.02)}
    ff = {
        "mlp": {"gate": (5, (D, F), 0.02), "up": (6, (D, F), 0.02),
                "down": (7, (F, D), 0.02)},
        "moe": {"gate": (9, (E, D, Fe), 0.02), "up": (10, (E, D, Fe), 0.02),
                "down": (11, (E, Fe, D), 0.02),
                "shared_gate": (13, (D, Fs), 0.02),
                "shared_up": (14, (D, Fs), 0.02),
                "shared_down": (15, (Fs, D), 0.02)},
    }

    def draw(layers, j, shape, scale, rnd=normal):
        """Tensor ``j`` of the ``layers`` (stack indices), stacked."""
        return jax.jit(lambda k: jax.lax.map(
            lambda i: rnd(jax.random.fold_in(k, i), shape, scale),
            jnp.asarray(layers)))(dk[j])

    def tree(layers, lead, mixer, kind):
        """The tensors of ``layers`` (all of one mixer and one
        feed-forward kind), each ``lead + its shape``."""
        put = lambda a: a.reshape(lead + a.shape[1:])
        out = {"input_norm": ones(*lead, D), "post_norm": ones(*lead, D),
               "q_a_norm": ones(*lead, ql), "kv_a_norm": ones(*lead, kl)}
        kv_b = put(draw(layers, 3, (kl, H, nope + vd), 0.05))
        out["kv_b_k"] = {"w": kv_b[..., :nope]}
        out["kv_b_v"] = {"w": kv_b[..., nope:]}
        tensors = {**attn, **ff[kind], **(index if mixer == "dsa" else {})}
        for name, (j, shape, scale) in tensors.items():
            if 0 not in shape:  # no shared expert: no tensor
                out[name] = {"w": put(draw(layers, j, shape, scale))}
        if mixer == "dsa":
            out["index_k_norm"] = ones(*lead, di)
            out["index_k_bias"] = jnp.zeros(lead + (di,), dtype)
        if kind == "moe":
            out["router"] = put(draw(layers, 8, (D, R), 0.02))
            out["router_bias"] = put(draw(
                layers, 12, (R,), 0.02,
                lambda k, shape, scale: jax.random.normal(
                    k, shape, jnp.float32) * scale))
        return out

    lead, P = spec.lead_layers, spec.num_periods
    kinds = spec.stack
    out: Dict[str, Any] = {"lead": tuple(
        tree([i], (), *kinds[i]) for i in range(lead))}
    for group, mixer in (("pick", "dsa"), ("reuse", "mla")):
        layers = [i for i in range(lead, spec.num_layers)
                  if kinds[i][0] == mixer]
        if layers:
            out[group] = tree(layers, (P, len(layers) // P), mixer, "moe")
    return out


def _init_kv_dsa_layers(spec: ModelSpec, key, dtype, normal
                        ) -> Dict[str, Any]:
    """The tensors of a stack of GQA attention under a selection (every
    layer ``dsa moe``; ``ModelSpec.kv_rows``) from ``fold_in(key, 53)``
    split 32 ways, tensor ``j`` of layer ``i`` from ``fold_in(key j,
    i)``.  N(0, 0.02) (q and k meet a per-head norm, so their scores
    spread by about 1 whatever the draw) but the indexer's queries and
    head weights, N(0, 1 / hidden): on normed rows an index query's
    elements then have a standard deviation near 1 at the published
    width AND at a toy one, against a LayerNormed key: a dot product
    spreads by the root of the index head's size, so the pick is neither
    the first ``index_topk`` positions nor the last, and a wrong
    rotation or a stale index key moves it.  Norm weights 1, the
    LayerNorm's bias 0."""
    sk = jax.random.split(jax.random.fold_in(key, 53), 32)
    D, H, KV, hd = (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    Hi, di = spec.index_n_heads, spec.index_head_dim
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    lead = (spec.num_periods, 1)
    ones = lambda n: jnp.ones(lead + (n,), dtype)
    draw = lambda j, shape, scale=0.02: _per_layer(
        spec, "layer", lambda kk: normal(kk, shape, scale))(sk[j])
    return {"layer": {
        "input_norm": ones(D), "post_norm": ones(D),
        "q": {"w": draw(0, (D, H * hd))},
        "k": {"w": draw(1, (D, KV * hd))},
        "v": {"w": draw(2, (D, KV * hd))},
        "o": {"w": draw(3, (H * hd, D))},
        "q_norm": ones(hd), "k_norm": ones(hd),
        "index_q": {"w": draw(4, (D, Hi * di), D ** -0.5)},
        "index_k": {"w": draw(5, (D, di))},
        "index_w": {"w": draw(6, (D, Hi), D ** -0.5)},
        "index_k_norm": ones(di),
        "index_k_bias": jnp.zeros(lead + (di,), dtype),
        "router": draw(7, (D, R)),
        "gate": {"w": draw(8, (E, D, Fe))},
        "up": {"w": draw(9, (E, D, Fe))},
        "down": {"w": draw(10, (E, Fe, D))},
    }}


def _init_window_layers(spec: ModelSpec, key, dtype, normal
                        ) -> Dict[str, Any]:
    """A ``window_pattern`` spec's tensors from ``fold_in(key, 38)``
    split 32 ways: tensor ``j`` of layer ``i`` (its index in the WHOLE
    stack, leading layers included) from ``fold_in(key j, i)``, N(0,
    0.02), every norm weight 1.  With per-head norms on q and k a
    query's scores have a standard deviation near 1, so attention is
    not flat and a wrong window, a stale ring row or a rotary on the
    wrong kind of layer shows.  A sigmoid router's selection bias N(0,
    0.02) and NOT zero, as for a pattern's; a softmax router has none."""
    wk = jax.random.split(jax.random.fold_in(key, 38), 32)
    D, H, KV, hd = (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    F, Fs = spec.intermediate_size, spec.shared_expert_intermediate_size
    ones = lambda *shape: jnp.ones(shape, dtype)
    attn = {"q": (0, (D, H * hd)), "k": (1, (D, KV * hd)),
            "v": (2, (D, KV * hd)), "o": (3, (H * hd, D))}
    ff = {
        "mlp": {"gate": (4, (D, F)), "up": (5, (D, F)), "down": (6, (F, D))},
        "moe": {"gate": (8, (E, D, Fe)), "up": (9, (E, D, Fe)),
                "down": (10, (E, Fe, D)), "shared_gate": (12, (D, Fs)),
                "shared_up": (13, (D, Fs)), "shared_down": (14, (Fs, D))},
    }

    def draw(layers, j, shape):
        """Tensor ``j`` of the ``layers`` (stack indices), stacked."""
        return jax.jit(lambda k: jax.lax.map(
            lambda i: normal(jax.random.fold_in(k, i), shape, 0.02),
            jnp.asarray(layers)))(wk[j])

    def tree(layers, lead, kind):
        """The tensors of ``layers`` (all of one feed-forward ``kind``),
        each ``lead + its shape``."""
        put = lambda a: a.reshape(lead + a.shape[1:])
        out = {"input_norm": ones(*lead, D), "post_norm": ones(*lead, D)}
        if spec.qk_norm:
            out.update(q_norm=ones(*lead, hd), k_norm=ones(*lead, hd))
        for name, (j, shape) in {**attn, **ff[kind]}.items():
            if 0 not in shape:  # no shared expert: no tensor
                out[name] = {"w": put(draw(layers, j, shape))}
        if kind == "moe":
            out["router"] = put(draw(layers, 7, (D, R)))
        if kind == "moe" and spec.router_scoring == "sigmoid":
            out["router_bias"] = put(jax.jit(lambda k: jax.lax.map(
                lambda i: jax.random.normal(
                    jax.random.fold_in(k, i), (R,), jnp.float32) * 0.02,
                jnp.asarray(layers)))(wk[11]))
        return out

    lead, P = spec.lead_layers, spec.num_periods
    kinds = spec.stack
    out: Dict[str, Any] = {"lead": tuple(
        tree([i], (), kinds[i][1]) for i in range(lead))}
    for group, mixer in (("window", "swa"), ("global", "attn")):
        layers = [i for i in range(lead, spec.num_layers)
                  if kinds[i][0] == mixer]
        if layers:
            out[group] = tree(layers, (P, len(layers) // P), "moe")
    return out


def _init_conv_layers(spec: ModelSpec, key, dtype, normal
                      ) -> Dict[str, Any]:
    """A ``conv_pattern`` spec's tensors from ``fold_in(key, 47)`` split
    32 ways: tensor ``j`` of layer ``i`` (its index in the WHOLE stack,
    leading layers included) from ``fold_in(key j, i)``, N(0, 0.02),
    every norm weight 1, but for the short convolution's input
    projection, N(0, 1 / hidden), and its taps, N(0, 0.5): ``B``, ``C``
    and ``X`` then have a standard deviation near 1 at any width and so
    has the mixer's output before its projection (at 0.02 and a hidden
    size of 64 the product of the three spreads by 0.004, and a wrong
    tap order, a stale tail or an activation that is not there would
    hide under a comparison's tolerance).  The router's selection bias
    N(0, 0.02) and NOT zero."""
    ck = jax.random.split(jax.random.fold_in(key, 47), 32)
    D, H, KV, hd = (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    F, K = spec.intermediate_size, spec.conv_L_cache
    ones = lambda *shape: jnp.ones(shape, dtype)
    mixer = {
        "conv": {"in_proj": (0, (D, 3 * D), D ** -0.5),
                 "out_proj": (2, (D, D), 0.02)},
        "attn": {"q": (4, (D, H * hd), 0.02), "k": (5, (D, KV * hd), 0.02),
                 "v": (6, (D, KV * hd), 0.02), "o": (7, (H * hd, D), 0.02)},
    }
    ff = {
        "mlp": {"gate": (8, (D, F), 0.02), "up": (9, (D, F), 0.02),
                "down": (10, (F, D), 0.02)},
        "moe": {"gate": (12, (E, D, Fe), 0.02), "up": (13, (E, D, Fe), 0.02),
                "down": (14, (E, Fe, D), 0.02)},
    }

    def draw(layers, j, shape, scale, rnd=normal):
        """Tensor ``j`` of the ``layers`` (stack indices), stacked."""
        return jax.jit(lambda k: jax.lax.map(
            lambda i: rnd(jax.random.fold_in(k, i), shape, scale),
            jnp.asarray(layers)))(ck[j])

    def tree(layers, lead, kind, feed):
        """The tensors of ``layers`` (all of one mixer ``kind`` and one
        feed-forward), each ``lead + its shape``."""
        put = lambda a: a.reshape(lead + a.shape[1:])
        out = {"input_norm": ones(*lead, D), "post_norm": ones(*lead, D)}
        for name, (j, shape, scale) in {**mixer[kind], **ff[feed]}.items():
            out[name] = {"w": put(draw(layers, j, shape, scale))}
        if kind == "conv":
            out["conv"] = put(draw(layers, 1, (D, K), 0.5))
            if spec.conv_bias:
                out["conv_bias"] = put(draw(layers, 3, (D,), 0.02))
        else:
            out.update(q_norm=ones(*lead, hd), k_norm=ones(*lead, hd))
        if feed == "moe":
            out["router"] = put(draw(layers, 11, (D, R), 0.02))
            out["router_bias"] = put(draw(
                layers, 15, (R,), 0.02,
                lambda k, shape, scale: jax.random.normal(
                    k, shape, jnp.float32) * scale))
        return out

    lead, P = spec.lead_layers, spec.num_periods
    kinds = spec.stack
    out: Dict[str, Any] = {"lead": tuple(
        tree([i], (), *kinds[i]) for i in range(lead))}
    for group in ("conv", "attn"):
        layers = [i for i in range(lead, spec.num_layers)
                  if kinds[i][0] == group]
        if layers:
            out[group] = tree(layers, (P, len(layers) // P), group, "moe")
    return out


def _init_mamba_mlp_layers(spec: ModelSpec, key, dtype, normal
                           ) -> Dict[str, Any]:
    """A ``mamba_pattern`` spec's tensors (every layer a Mamba-2 or an
    attention mixer, then a dense SwiGLU) from ``fold_in(key, 51)`` split
    32 ways: tensor ``j`` of layer ``i`` (its index in the WHOLE stack)
    from ``fold_in(key j, i)``, N(0, 0.02), every norm weight 1.  The
    Mamba-2 draws are ``_init_pattern_layers``': the taps N(0, 0.5),
    ``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly in [0.001, 0.1] floored at 1e-4, ``D = 1``, so
    that a wrong or a stale state shows.  The multipliers are the spec's
    and no tensor's."""
    gk = jax.random.split(jax.random.fold_in(key, 51), 32)
    D, H, KV, hd = (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    F = spec.intermediate_size
    Hm, di, C = spec.mamba_num_heads, spec.mamba_inner, spec.mamba_conv_dim
    f32 = jnp.float32
    mixer = {
        "mamba": {"in_proj": (0, (D, di + C + Hm)), "out": (5, (di, D))},
        "attn": {"q": (8, (D, H * hd)), "k": (9, (D, KV * hd)),
                 "v": (10, (D, KV * hd)), "o": (11, (H * hd, D))},
    }
    ff = {"gate": (12, (D, F)), "up": (13, (D, F)), "down": (14, (F, D))}

    def draw(layers, j, fn):
        """Tensor ``j`` of the ``layers`` (stack indices), stacked:
        ``fn(key)`` a layer."""
        return jax.jit(lambda k: jax.lax.map(
            lambda i: fn(jax.random.fold_in(k, i)),
            jnp.asarray(layers)))(gk[j])

    def tree(layers, lead, kind):
        put = lambda a: a.reshape(lead + a.shape[1:])
        gauss = lambda j, shape, scale=0.02: put(draw(
            layers, j, lambda k: normal(k, shape, scale)))
        out = {"input_norm": jnp.ones(lead + (D,), dtype),
               "post_norm": jnp.ones(lead + (D,), dtype)}
        for name, (j, shape) in {**mixer[kind], **ff}.items():
            out[name] = {"w": gauss(j, shape)}
        if kind == "mamba":
            out["in_proj"]["w"] = mamba_proj_pad(out["in_proj"]["w"])
            out.update(
                conv=gauss(1, (C, spec.mamba_conv_kernel), 0.5),
                a_log=put(draw(layers, 3, lambda k: jnp.log(
                    jax.random.uniform(k, (Hm,), f32, 1.0, 16.0)))),
                dt_bias=put(draw(layers, 4, lambda k: _mamba_dt_bias(k, Hm))),
                d=jnp.ones(lead + (Hm,), f32),
                ssm_norm=jnp.ones(lead + (di,), dtype))
            if spec.mamba_conv_bias:
                out["conv_bias"] = gauss(2, (C,))
        return out

    P, out = spec.num_periods, {}
    for group in ("mamba", "attn"):
        layers = [i for i, kinds in enumerate(spec.stack)
                  if kinds[0] == group]
        if layers:
            out[group] = tree(layers, (P, len(layers) // P), group)
    return out


def _mamba_dt_bias(key, heads: int):
    """A Mamba-2 layer's ``dt_bias`` [heads] float32: the inverse
    softplus of a step drawn log-uniformly in [0.001, 0.1], floored at
    1e-4 (the published initialisation)."""
    step = jnp.maximum(1e-4, jnp.exp(
        jnp.log(1e-3) + jax.random.uniform(key, (heads,))
        * (jnp.log(0.1) - jnp.log(1e-3))))
    return jnp.log(jnp.expm1(step)).astype(jnp.float32)


def mamba_proj_pad(w):
    """A ``mamba_pattern`` layer's input projection ``[..., D, z | x B C
    | dt]`` with zero columns behind ``dt`` up to whole 128-lane groups
    (Granite's 8,512 -> 8,576).  A matrix whose minor dimension is no
    whole number of lane groups the TPU holds transposed, and a step
    program then opens with a copy of ALL of it into the layout its
    products read: 1.26 GB of temporaries and 2.5 GB of traffic a decode
    chunk at the published sizes (the AOT memory analysis, tests/
    test_tpu_aot.py -k granite).  Works on numpy and jax arrays."""
    pad = -w.shape[-1] % 128
    if not pad:
        return w
    xp = jnp if isinstance(w, jax.Array) else np
    return xp.pad(w, ((0, 0),) * (w.ndim - 1) + ((0, pad),))


def _init_pattern_layers(spec: ModelSpec, key, dtype, normal
                         ) -> Dict[str, Any]:
    """A ``layer_pattern`` spec's tensors from ``fold_in(key, 31)`` split
    32 ways.  Every tensor is drawn a LAYER at a time (``fold_in(tensor's
    key, layer of its group)``), so that no draw stands wider than one
    layer's tensor.  The published initialisation where it matters:
    ``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly in [0.001, 0.1], ``D = 1`` (per-step decays from
    about 0.2 to 0.9999 across heads, so that a wrong or a stale state
    shows); the router's selection bias N(0, 0.02) and NOT zero, so that
    a program that forgets it, or lets it into the weights, differs."""
    nk = jax.random.split(jax.random.fold_in(key, 31), 32)
    D, P = spec.hidden_size, spec.num_periods
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    E, R, Fe, W = (spec.num_experts, spec.router_experts, spec.expert_width,
                   spec.expert_in)
    Fs = spec.shared_expert_intermediate_size
    Hm, di, C = spec.mamba_num_heads, spec.mamba_inner, spec.mamba_conv_dim
    f32 = jnp.float32

    per_layer = lambda group, fn: _per_layer(spec, group, fn)

    def draw(group, k, shape, scale=0.02):
        return per_layer(group, lambda kk: normal(kk, shape, scale))(k)

    out: Dict[str, Any] = {}
    if spec.group_layers("mamba"):
        lead = (P, spec.group_layers("mamba"))
        out["mamba"] = {
            "norm": jnp.ones(lead + (D,), dtype),
            "in_proj": {"w": draw("mamba", nk[0], (D, di + C + Hm))},
            "conv": draw("mamba", nk[1], (C, spec.mamba_conv_kernel), 0.5),
            "a_log": per_layer("mamba", lambda k: jnp.log(
                jax.random.uniform(k, (Hm,), f32, 1.0, 16.0)))(nk[3]),
            "dt_bias": per_layer(
                "mamba", lambda k: _mamba_dt_bias(k, Hm))(nk[4]),
            "d": jnp.ones(lead + (Hm,), f32),
            "ssm_norm": jnp.ones(lead + (di,), dtype),
            "out": {"w": draw("mamba", nk[5], (di, D))},
        }
        if spec.mamba_conv_bias:
            out["mamba"]["conv_bias"] = draw("mamba", nk[2], (C,))
    if spec.group_layers("attn"):
        out["attn"] = {
            "norm": jnp.ones((P, spec.group_layers("attn"), D), dtype),
            "q": {"w": draw("attn", nk[8], (D, H * hd))},
            "k": {"w": draw("attn", nk[9], (D, KV * hd))},
            "v": {"w": draw("attn", nk[10], (D, KV * hd))},
            "o": {"w": draw("attn", nk[11], (H * hd, D))},
        }
    if spec.group_layers("moe"):
        moe = {
            "norm": jnp.ones((P, spec.group_layers("moe"), D), dtype),
            "router": draw("moe", nk[16], (D, R)),
            "up": {"w": draw("moe", nk[20], (E, W, Fe))},
            "down": {"w": draw("moe", nk[21], (E, Fe, W))},
        }
        if spec.router_scoring == "sigmoid":
            moe["router_bias"] = per_layer("moe", lambda k: (
                jax.random.normal(k, (R,), f32) * 0.02))(nk[17])
        if spec.moe_latent_size:
            moe["latent_in"] = {"w": draw("moe", nk[18], (D, W))}
            moe["latent_out"] = {"w": draw("moe", nk[19], (W, D))}
        if Fs:
            moe["shared_up"] = {"w": draw("moe", nk[22], (D, Fs))}
            moe["shared_down"] = {"w": draw("moe", nk[23], (Fs, D))}
        if spec.moe_gated:
            moe["gate"] = {"w": draw("moe", nk[24], (E, W, Fe))}
            if Fs:
                moe["shared_gate"] = {"w": draw("moe", nk[25], (D, Fs))}
        if Fs and spec.shared_expert_gate:
            moe["shared_router"] = draw("moe", nk[26], (D,))
        out["moe"] = moe
    return out


def _init_paired_layers(spec: ModelSpec, key, dtype, normal, norm_init
                ) -> Dict[str, Any]:
    """Random draw of Qwen3-Next's layer tensors, from keys of its own
    (``fold_in(key, 27)`` split 32 ways: the dense and Mixtral draws
    stay what they were).  ``dt_bias`` is drawn so that a head's
    per-step decay ``exp(g)`` lies log-uniformly between about 0.5 and
    0.999 (at ``a = 0``): with every head forgetting in a token or two,
    neither a wrong state nor a stale one could show in a comparison."""
    hk = jax.random.split(jax.random.fold_in(key, 27), 32)
    D, P, n = spec.hidden_size, spec.num_periods, spec.linear_per_period
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    E, R, Fe = spec.num_experts, spec.router_experts, spec.expert_width
    Fs = spec.shared_expert_intermediate_size
    Hv, dv = spec.linear_num_value_heads, spec.linear_value_head_dim
    kd, vd, C = spec.linear_key_dim, spec.linear_value_dim, spec.linear_conv_dim
    K = spec.linear_conv_kernel_dim

    def moe(lead, ks):
        out = {
            "router": normal(ks[0], lead + (D, R)),
            "gate": {"w": normal(ks[1], lead + (E, D, Fe))},
            "up": {"w": normal(ks[2], lead + (E, D, Fe))},
            "down": {"w": normal(ks[3], lead + (E, Fe, D))},
        }
        if Fs:
            out.update({
                "shared_gate": {"w": normal(ks[4], lead + (D, Fs))},
                "shared_up": {"w": normal(ks[5], lead + (D, Fs))},
                "shared_down": {"w": normal(ks[6], lead + (Fs, D))},
                "shared_router": normal(ks[7], lead + (D,)),
            })
        return out

    q_width = H * hd * (2 if spec.attn_output_gate else 1)
    full = {
        "input_norm": norm_init((P, D), dtype),
        "post_norm": norm_init((P, D), dtype),
        "q": {"w": normal(hk[0], (P, D, q_width))},
        "k": {"w": normal(hk[1], (P, D, KV * hd))},
        "v": {"w": normal(hk[2], (P, D, KV * hd))},
        "o": {"w": normal(hk[3], (P, H * hd, D))},
        **moe((P,), hk[4:12]),
    }
    if spec.qk_norm:
        full["q_norm"] = norm_init((P, hd), dtype)
        full["k_norm"] = norm_init((P, hd), dtype)
    lead = (P, n)
    rate = jnp.exp(
        jnp.log(1e-3) + jax.random.uniform(hk[16], lead + (Hv,))
        * (jnp.log(0.693) - jnp.log(1e-3))
    )  # -log(decay) a step at a = 0
    linear = {
        "input_norm": norm_init(lead + (D,), dtype),
        "post_norm": norm_init(lead + (D,), dtype),
        "in_qkvz": {"w": normal(hk[12], lead + (D, C + vd))},
        "in_ba": {"w": normal(hk[13], lead + (D, 2 * Hv))},
        "conv": normal(hk[14], lead + (C, K), scale=0.5),
        "a_log": jax.random.normal(hk[15], lead + (Hv,), jnp.float32) * 0.02,
        "dt_bias": jnp.log(jnp.expm1(rate)).astype(jnp.float32),
        "gdn_norm": jnp.ones(lead + (dv,), dtype),
        "out": {"w": normal(hk[17], lead + (vd, D))},
        **moe(lead, hk[18:26]),
    }
    return {"linear": linear, "full": full}


def _state_shapes(spec: ModelSpec):
    """(a slot's tile [heads, ., .] in one recurrent layer, or None
    where the kind keeps none; its convolution tail [K-1, C]), by the
    kind of the layers that carry a state."""
    if spec.recurrent_kind == "conv":
        return None, (spec.conv_L_cache - 1, spec.hidden_size)
    if spec.recurrent_kind == "mamba":
        return ((spec.mamba_num_heads, spec.mamba_head_dim,
                 spec.mamba_state_size),
                (spec.mamba_conv_kernel - 1, spec.mamba_conv_dim))
    return ((spec.linear_num_value_heads, spec.linear_key_head_dim,
             spec.linear_value_head_dim),
            (spec.linear_conv_kernel_dim - 1, spec.linear_conv_dim))


def ring_pages(spec: ModelSpec, page_size: int) -> int:
    """Pages of a slot's ring in one window layer: the window and one
    page of slack, so that the page a token lands in holds nothing the
    window still reaches."""
    return cdiv(spec.sliding_window, page_size) + 1


def _ring_shape(spec: ModelSpec, slots: int, page_size: int) -> tuple:
    return (spec.swa_layers, spec.num_kv_heads,
            1 + slots * ring_pages(spec, page_size), page_size,
            spec.head_dim)


def eva_window_pages(spec: ModelSpec, page_size: int) -> int:
    """Pool pages a slot's open window holds in an EVA layer."""
    return spec.eva_window // page_size


def make_state(spec: ModelSpec, slots: int, dtype, page_size: int = 0,
               pool_pages: int = 0) -> Dict[str, jax.Array]:
    """What a spec keeps a decode SLOT beside the paged pool, zeros: the
    recurrent state of every recurrent layer (one row a slot; a gated
    short convolution's is its tail alone), the K
    and V rings of every window layer (``page_size`` the pool's).  An
    EVA spec's open windows are pages of the POOL arrays behind the
    allocator's ``pool_pages`` (runtime/kv_cache.py ``slot_pages``);
    its state is their ids alone (``ops/eva.py window_pages``)."""
    out = {}
    if spec.eva_layers:
        out.update(eva_pages=eva.window_pages(
            pool_pages, slots, eva_window_pages(spec, page_size)))
    if spec.recurrent_kind:
        tile, tail = _state_shapes(spec)
        lead = (spec.recurrent_layers, slots)
        out.update(conv=jnp.zeros(lead + tail, dtype))
        if tile is not None:
            out.update(S=jnp.zeros(lead + tile, jnp.float32))
    if spec.swa_layers:
        shape = _ring_shape(spec, slots, page_size)
        out.update(ring_k=jnp.zeros(shape, dtype),
                   ring_v=jnp.zeros(shape, dtype))
    return out


def state_bytes_per_slot(spec: ModelSpec, dtype_bytes: int,
                         page_size: int = 0) -> int:
    """Bytes one slot holds over all recurrent layers and all rings."""
    out = 0
    if spec.recurrent_kind:
        tile, tail = _state_shapes(spec)
        out += spec.recurrent_layers * (
            math.prod(tile or (0,)) * 4 + math.prod(tail) * dtype_bytes)
    if spec.swa_layers:
        _, KV, _, ps, hd = _ring_shape(spec, 1, page_size)
        out += (spec.swa_layers * 2 * KV * ring_pages(spec, page_size)
                * ps * hd * dtype_bytes)
    if spec.eva_layers:  # K and V of the open window's rows
        out += (spec.eva_layers * 2 * spec.num_kv_heads * spec.eva_window
                * spec.head_dim * dtype_bytes)
    return out


def ring_tables(slots, n_pages: int, rings: int, R: int, first=None,
                last=None):
    """The ring's page table: ``[len(slots), n_pages]``, a sequence's
    page ``p`` (counted from ``first``, default 0) -> its slot's ring
    page ``1 + slot x R + p mod R``; page 0 (trash) for a slot that is
    none of the ``rings`` slots (a padding row).  With ``last`` ([B]:
    the page of each row's last real token) only the pages ``last - R +
    1 .. last`` keep their place, which are ``R`` different ring pages,
    and every other goes to the trash: what a prompt pass WRITES."""
    p = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    if first is not None:
        p = p + first[:, None]
    slots = slots.astype(jnp.int32)[:, None]
    keep = (slots >= 0) & (slots < rings)
    if last is not None:
        keep &= (p <= last[:, None]) & (p > last[:, None] - R)
    return jnp.where(keep, 1 + slots * R + p % R, 0)


# rows of a long prompt program that a position-wise sub-block takes at
# a time: the flash kernel's query block at those buckets and a whole
# row tile of the grouped product, so that a block is either real rows
# (and a bucket's padding in the last one) or nothing
PROMPT_ROW_BLOCK = 1024


def prompt_rows(spec: ModelSpec, S: int, lens, whole: bool = True):
    """What the position-wise sub-blocks of a prompt program over
    ``[len(lens), S]`` rows work on when its prompts hold ``lens`` tokens
    (an array of ints on the host, a traced one in the program: ONE rule
    for both), as ``(arrays, rows)``: so many arrays of so many rows
    each.  A ``whole`` prompt (not a suffix or a chunk, and all of a
    sequence's rows in one place) that spans two blocks of rows or more
    works on the blocks that hold a real row, ``rows`` then traced:

    * a stack of several kinds, every sequence as far as the longest
      reaches: ``(B, ceil(max(lens) / R) x R)``;
    * a stack of one kind (models/decoder.py's own pass), the group's
      real rows laid end to end: ``(1, ceil(sum(lens) / R) x R)``.

    Anything else is traced as it always was, ``(B, S)`` in ints."""
    B, R = len(lens), PROMPT_ROW_BLOCK
    if whole and spec.is_hybrid and S >= 2 * R and S % R == 0:
        return B, cdiv(lens.max(), R) * R
    if whole and not spec.is_hybrid and B * S >= 2 * R and B * S % R == 0:
        return 1, cdiv(lens.sum(), R) * R
    return B, S


def _as_is(rows):
    """The norm of rows that come normed."""
    return rows


def _by_row_blocks(fn, rows, n_rows, axis: int = 1):
    """``fn`` (position-wise along ``axis``: arrays ``[B, S, ...]``, or
    None, -> a tree of arrays ``[B, S, ...]``) over the blocks of
    ``PROMPT_ROW_BLOCK`` rows that hold one of the first ``n_rows`` (a
    traced scalar), in a counted loop: a prompt that fills two thirds of
    its bucket pays for two thirds of the rows.  The rows of the blocks
    past them come out zero.  ``n_rows`` None: ``fn(*rows)``."""
    if n_rows is None:
        return fn(*rows)
    R = PROMPT_ROW_BLOCK
    S = rows[0].shape[axis]
    take = lambda i: jax.tree.map(
        lambda t: jax.lax.dynamic_slice_in_dim(t, i * R, R, axis),
        list(rows))
    out = jax.tree.map(
        lambda s: jnp.zeros(
            s.shape[:axis] + (S,) + s.shape[axis + 1:], s.dtype),
        jax.eval_shape(fn, *take(0)))

    def block(i, out):
        return jax.tree.map(
            lambda o, part: jax.lax.dynamic_update_slice_in_dim(
                o, part, i * R, axis),
            out, fn(*take(i)))

    return jax.lax.fori_loop(0, cdiv(n_rows, R), block, out)


def _rope(x, positions, spec: ModelSpec, kind: str = "attn"):
    rotary = spec.rotary(kind)
    if rotary is None:
        return x
    return apply_rope(x, positions, rotary.theta, rotary.scaling,
                      rotary_dim=spec.rotary_dim,
                      sections=spec.mrope_section,
                      amplitude=rotary.amplitude)


@jax.named_scope("qkv")
def _gated_qkv(normed, lp, spec: ModelSpec, positions, kind: str = "attn"):
    """Attention front half on the normed rows: q (with its gate beside
    it, per head ``[query | gate]``, where the spec has one), k, v,
    per-head norms on q and k, and the rotary of the layer's ``kind``
    (``ModelSpec.rotary``: its frequencies and amplitude, or none).
    normed: [..., S, D] with positions [..., S]."""
    eps, uo = spec.rms_eps, spec.unit_offset_norm
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = jnp.einsum("...d,dh->...h", normed, lp["q"]["w"])
    k = jnp.einsum("...d,dh->...h", normed, lp["k"]["w"])
    v = jnp.einsum("...d,dh->...h", normed, lp["v"]["w"])
    gate = None
    if spec.attn_output_gate:
        q = q.reshape(*q.shape[:-1], H, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
    else:
        q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], KV, hd)
    v = v.reshape(*v.shape[:-1], KV, hd)
    if spec.qk_norm:
        q = rms_norm(q, lp["q_norm"], eps, uo)
        k = rms_norm(k, lp["k_norm"], eps, uo)
    return (_rope(q, positions, spec, kind),
            _rope(k, positions, spec, kind), v, gate)


def _heads_flat(t):
    """[..., H, hd] -> [..., H x hd] (None stays None).  Ahead of a loop
    over blocks of rows: the attention kernels leave their result
    head-major, and a block of rows cut from THAT is 64 strided pieces
    the output projection reads at half its pace (27.1 ms a K-EXAONE
    prompt program against 19.5 for the whole bucket; chip, PR 42)."""
    return None if t is None else t.reshape(*t.shape[:-2], -1)


@jax.named_scope("o_proj")
def _gated_out(attn, gate, lp, dtype):
    """The output side of an attention sub-block on ``_heads_flat`` rows
    (and their gate's)."""
    if gate is not None:
        attn = (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    return jnp.einsum("...h,hd->...d", attn, lp["o"]["w"])


def _linear_inputs(normed, lp, spec: ModelSpec):
    """The two input projections of a Gated DeltaNet layer: the
    pre-convolution (q, k, v) channels, z, b and a."""
    C, Hv = spec.linear_conv_dim, spec.linear_num_value_heads
    qkvz = jnp.einsum("...d,dc->...c", normed, lp["in_qkvz"]["w"])
    ba = jnp.einsum("...d,dc->...c", normed, lp["in_ba"]["w"])
    return qkvz[..., :C], qkvz[..., C:], ba[..., :Hv], ba[..., Hv:]


def _linear_heads(y, spec: ModelSpec):
    """Post-convolution channels -> q, k ([..., Hv, dk], normalised, q
    scaled, key heads repeated to the value heads) and v [..., Hv, dv]."""
    Hk, Hv = spec.linear_num_key_heads, spec.linear_num_value_heads
    dk, dv, kd = (spec.linear_key_head_dim, spec.linear_value_head_dim,
                  spec.linear_key_dim)
    lead = y.shape[:-1]
    q = gd.l2_normalize(y[..., :kd].reshape(*lead, Hk, dk)) * dk ** -0.5
    k = gd.l2_normalize(y[..., kd:2 * kd].reshape(*lead, Hk, dk))
    v = y[..., 2 * kd:].reshape(*lead, Hv, dv)
    rep = Hv // Hk
    return (jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2),
            v.astype(jnp.float32))


def _linear_out(o, z, lp, spec: ModelSpec, dtype):
    """Per-head RMSNorm with the PLAIN weight, gated by SiLU(z), then
    the output projection."""
    Hv, dv = spec.linear_num_value_heads, spec.linear_value_head_dim
    z = z.reshape(*z.shape[:-1], Hv, dv).astype(jnp.float32)
    o = rms_norm(o, lp["gdn_norm"], spec.rms_eps, False) * jax.nn.silu(z)
    o = o.reshape(*o.shape[:-2], Hv * dv).astype(dtype)
    return jnp.einsum("...v,vd->...d", o, lp["out"]["w"])


# prompt tokens a recurrent layer's mixer takes at once: its float32
# temporaries are ~12 x tokens x value heads x head size x 4 bytes
PROMPT_BLOCK_TOKENS = 4096


def _in_row_groups(fn, normed, *per_row):
    """``fn(rows, *per-row arrays) -> a tuple of arrays by row`` over a
    wave normed [B, S, D] in groups of ``PROMPT_BLOCK_TOKENS // S`` rows,
    unrolled (see ops/moe.py expert_layer), each result concatenated."""
    B, S = normed.shape[:2]
    rows = max(1, PROMPT_BLOCK_TOKENS // S)
    parts = [fn(*(a[lo:lo + rows] for a in (normed,) + per_row))
             for lo in range(0, B, rows)]
    return tuple(jnp.concatenate([p[i] for p in parts])
                 for i in range(len(parts[0])))


def _linear_rows(normed, lp, spec: ModelSpec, lens, tail, S0):
    """The Gated DeltaNet mixer over normed rows [B, S, D] from (tail,
    S0): returns (out, final state, final tail).  Padded positions move
    nothing."""
    S = normed.shape[1]
    qkv, z, b, a = _linear_inputs(normed, lp, spec)
    with jax.named_scope("conv"):
        y, new_tail = gd.causal_conv(qkv, tail, lp["conv"], lens)
    q, k, v = _linear_heads(y, spec)
    g, beta = gd.gates(a, b, lp["a_log"], lp["dt_bias"])
    valid = (jnp.arange(S)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    o, S1 = gd.gated_delta_chunked(q, k, v, g, beta, S0)
    return _linear_out(o, z, lp, spec, normed.dtype), S1, new_tail


def _conv_step(tail, row, w, bias, active, act=jax.nn.silu,
               scope: str = "conv"):
    """The causal convolution for one decode step: tail [B, K-1, C] the
    rows before ``row`` [B, C].  Returns (``act`` of the convolution [B,
    C] in the row's type (``ops/gated_delta.py causal_conv``'s: None
    for none), the tail moved on by one row where ``active``)."""
    with jax.named_scope(scope):
        cat = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], 1)
        y = jnp.einsum("bkc,ck->bc", cat.astype(jnp.float32),
                       w.astype(jnp.float32))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        if act is not None:
            y = act(y)
        new_tail = jnp.where(active[:, None, None], cat[:, 1:], tail)
    return y.astype(row.dtype), new_tail


def _linear_step(normed, lp, st, li, spec: ModelSpec, active, use_pallas):
    """A Gated DeltaNet mixer for one decode step, normed [B, D], row =
    slot."""
    qkv, z, b, a = _linear_inputs(normed, lp, spec)
    y, new_tail = _conv_step(st["conv"][li], qkv, lp["conv"], None, active)
    conv = st["conv"].at[li].set(new_tail)
    q, k, v = _linear_heads(y, spec)
    g, beta = gd.gates(a, b, lp["a_log"], lp["dt_bias"])
    g = jnp.where(active[:, None], g, 0.0)
    beta = jnp.where(active[:, None], beta, 0.0)
    o, S = gd.gated_delta_step(q, k, v, g, beta, st["S"], li,
                               use_pallas=use_pallas)
    out = _linear_out(o, z, lp, spec, normed.dtype)
    return out, {"S": S, "conv": conv}


def _mamba_inputs(normed, lp, spec: ModelSpec):
    """The input projection of a Mamba-2 layer, ``[z | x B C | dt]``:
    z, the pre-convolution channels and dt."""
    di, C = spec.mamba_inner, spec.mamba_conv_dim
    proj = jnp.einsum("...d,dc->...c", normed, lp["in_proj"]["w"])
    Hm = spec.mamba_num_heads  # (columns past dt: ``mamba_proj_pad``)
    return (proj[..., :di], proj[..., di:di + C],
            proj[..., di + C:di + C + Hm])


def _mamba_heads(y, dt, lp, spec: ModelSpec, valid):
    """Post-convolution channels -> x [..., H, P], B and C [..., G, N];
    the step ``softplus(dt + dt_bias)`` in float32, 0 where not
    ``valid``; and the heads' negative ``A``."""
    H, P = spec.mamba_num_heads, spec.mamba_head_dim
    G, N, di = spec.mamba_n_groups, spec.mamba_state_size, spec.mamba_inner
    lead = y.shape[:-1]
    x = y[..., :di].reshape(*lead, H, P)
    Bm = y[..., di:di + G * N].reshape(*lead, G, N)
    Cm = y[..., di + G * N:].reshape(*lead, G, N)
    step = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    A = -jnp.exp(lp["a_log"].astype(jnp.float32))
    return x, Bm, Cm, jnp.where(valid, step, 0.0), A


def _mamba_out(y, x, z, lp, spec: ModelSpec, dtype):
    """The skip ``D x``, the gate FIRST (``y * SiLU(z)``), then RMSNorm
    over each group's channels with the plain weight, then the output
    projection."""
    G, di = spec.mamba_n_groups, spec.mamba_inner
    y = y + lp["d"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], di) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.reshape(*y.shape[:-1], G, di // G),
                 lp["ssm_norm"].reshape(G, di // G), spec.rms_eps, False)
    y = y.reshape(*y.shape[:-2], di).astype(dtype)
    return jnp.einsum("...v,vd->...d", y, lp["out"]["w"])


def _mamba_rows(normed, lp, spec: ModelSpec, lens, tail, S0):
    """The Mamba-2 mixer over normed rows [B, S, D] from (tail, S0):
    returns (out, final state, final tail).  Padded positions move
    nothing (``dt = 0`` there)."""
    S = normed.shape[1]
    z, xbc, dt = _mamba_inputs(normed, lp, spec)
    with jax.named_scope("conv"):
        y, new_tail = gd.causal_conv(xbc, tail, lp["conv"], lens,
                                     lp.get("conv_bias"))
    valid = (jnp.arange(S)[None, :] < lens[:, None])[..., None]
    x, Bm, Cm, step, A = _mamba_heads(y, dt, lp, spec, valid)
    o, S1 = ssd.ssd_chunked(x, step, A, Bm, Cm, S0, spec.mamba_chunk_size)
    return _mamba_out(o, x, z, lp, spec, normed.dtype), S1, new_tail


def _mamba_step(normed, lp, st, li, spec: ModelSpec, active, use_pallas):
    """A Mamba-2 mixer for one decode step, normed [B, D], row = slot."""
    z, xbc, dt = _mamba_inputs(normed, lp, spec)
    y, new_tail = _conv_step(st["conv"][li], xbc, lp["conv"],
                             lp.get("conv_bias"), active)
    conv = st["conv"].at[li].set(new_tail)
    x, Bm, Cm, step, A = _mamba_heads(y, dt, lp, spec, active[:, None])
    o, S = ssd.ssd_step(x, step, A, Bm, Cm, st["S"], li,
                        use_pallas=use_pallas)
    return _mamba_out(o, x, z, lp, spec, normed.dtype), {"S": S, "conv": conv}


def _conv_gates(normed, lp):
    """The gated short convolution's input projection ``[B | C | X]``:
    what the taps run over, ``B * X``, and the gate on their result."""
    b, c, x = jnp.split(
        jnp.einsum("...d,dc->...c", normed, lp["in_proj"]["w"]), 3, axis=-1)
    return b * x, c


def _conv_rows(normed, lp, lens, tail):
    """The gated short convolution over normed rows [B, S, D] from
    ``tail`` [B, K-1, D]: returns (out, final tail), the tail taken at
    the rows' real lengths."""
    z, gate = _conv_gates(normed, lp)
    with jax.named_scope("short_conv"):
        c, new_tail = gd.causal_conv(z, tail, lp["conv"], lens,
                                     lp.get("conv_bias"), act=None)
    return jnp.einsum("...c,cd->...d", gate * c,
                      lp["out_proj"]["w"]), new_tail


@jax.named_scope("conv_mixer")
def _conv_prompt(normed, lp, st, li, lens, slots, fresh):
    """A gated short convolution over prompt rows normed [B, S, D]:
    starts from the slot's tail (zeros where ``fresh``), ends with the
    tail overwritten.  A wide wave goes through in groups of rows."""
    tail = jnp.where(fresh[:, None, None], 0, st["conv"][li][slots])
    out, new_tail = _in_row_groups(
        lambda rows, lens, tail: _conv_rows(rows, lp, lens, tail),
        normed, lens, tail)
    return out, {**st, "conv": st["conv"].at[li, slots].set(
        new_tail.astype(st["conv"].dtype), mode="drop")}


@jax.named_scope("conv_mixer")
def _conv_mixer_step(normed, lp, st, li, active):
    """A gated short convolution for one decode step, normed [B, D], row
    = slot: active slots' tails move on in place, idle rows stay."""
    z, gate = _conv_gates(normed, lp)
    c, new_tail = _conv_step(st["conv"][li], z, lp["conv"],
                             lp.get("conv_bias"), active, act=None,
                             scope="short_conv")
    out = jnp.einsum("...c,cd->...d", gate * c, lp["out_proj"]["w"])
    return out, {**st, "conv": st["conv"].at[li].set(new_tail)}


# a recurrent kind's (prompt rows, decode step) and its scope in a trace
_RECURRENT = {
    "gdn": (_linear_rows, _linear_step, "linear_attn"),
    "mamba": (_mamba_rows, _mamba_step, "ssm"),
}


def _recurrent_prompt(kind, normed, lp, st, li, spec: ModelSpec, lens,
                      slots, fresh):
    """A recurrent layer's mixer over prompt rows normed [B, S, D]:
    starts from the slot's row (zeros where ``fresh``), ends with the
    row overwritten whole.  A wide wave goes through in groups of rows."""
    rows_fn, _, scope = _RECURRENT[kind]
    with jax.named_scope(scope):
        keep = jnp.logical_not(fresh)
        tail = jnp.where(keep[:, None, None], st["conv"][li][slots], 0)
        S0 = jnp.where(keep[:, None, None, None], st["S"][li][slots], 0.0)
        out, S1, new_tail = _in_row_groups(
            lambda rows, lens, tail, S0: rows_fn(
                rows, lp, spec, lens, tail, S0), normed, lens, tail, S0)
        st = {
            "S": st["S"].at[li, slots].set(S1, mode="drop"),
            "conv": st["conv"].at[li, slots].set(
                new_tail.astype(st["conv"].dtype), mode="drop"),
        }
    return out, st


def _mla_cq(normed, lp, spec: ModelSpec):
    """The queries' normed bottleneck ``c_q`` [..., S, q_lora_rank]."""
    cq = jnp.einsum("...d,dr->...r", normed, lp["q_a"]["w"])
    return rms_norm(cq, lp["q_a_norm"], spec.rms_eps, spec.unit_offset_norm)


@jax.named_scope("mla_q")
def _mla_q(normed, lp, spec: ModelSpec, positions, cq=None, q_b=None):
    """Latent attention's queries on normed rows [..., S, D]: through
    the normed bottleneck (``cq`` if the caller has it), per head
    ``[q_nope | q_rope]`` with the rotary part rotated, both scaled by
    the position's gamma; ``q_b``: some heads' columns of the
    up-projection in place of all.  Returns (q_nope [..., S, H, nope],
    q_rope [..., S, H, rope])."""
    nope = spec.qk_nope_head_dim
    if cq is None:
        cq = _mla_cq(normed, lp, spec)
    q = jnp.einsum("...r,rh->...h", cq,
                   lp["q_b"]["w"] if q_b is None else q_b)
    q = q.reshape(*q.shape[:-1], -1, nope + spec.qk_rope_head_dim)
    q_nope = q[..., :nope]
    q_rope = apply_rope(q[..., nope:], positions, spec.rope_theta,
                        spec.rope_scaling)
    if spec.llama_4_scaling_beta:
        gamma = position_scale(positions, spec.llama_4_scaling_beta,
                               spec.yarn_original_max_pos)[..., None, None]
        scaled = lambda t: (t.astype(jnp.float32) * gamma).astype(t.dtype)
        q_nope, q_rope = scaled(q_nope), scaled(q_rope)
    return q_nope, q_rope


@jax.named_scope("mla_latent")
def _mla_latent(normed, lp, spec: ModelSpec, positions, width: int):
    """What the cache holds of normed rows [..., S, D]: ``[c_kv | k_rope
    | 0]`` [..., S, width], the latent after its norm, the ONE rotary
    key after its rotation, and the pool row's unused lanes."""
    kl = spec.kv_lora_rank
    kv = jnp.einsum("...d,dc->...c", normed, lp["kv_a"]["w"])
    c = rms_norm(kv[..., :kl], lp["kv_a_norm"], spec.rms_eps,
                 spec.unit_offset_norm)
    k_rope = apply_rope(kv[..., None, kl:], positions, spec.rope_theta,
                        spec.rope_scaling)[..., 0, :]
    pad = jnp.zeros(kv.shape[:-1] + (width - spec.latent_dim,), kv.dtype)
    return jnp.concatenate([c, k_rope, pad], axis=-1)


@jax.named_scope("mla_expand")
def _mla_expand(rows, lp, spec: ModelSpec):
    """Latent rows [B, T, width] -> every head's k [B, T, H, nope + rope]
    (its own ``c_kv W_uk`` beside the shared rotary key) and v [B, T, H,
    v]: the NON-absorbed form."""
    kl, rope = spec.kv_lora_rank, spec.qk_rope_head_dim
    c = rows[..., :kl]
    k_nope = jnp.einsum("btk,khn->bthn", c, lp["kv_b_k"]["w"])
    v = jnp.einsum("btk,khv->bthv", c, lp["kv_b_v"]["w"])
    k_rope = jnp.broadcast_to(
        rows[..., None, kl:kl + rope], k_nope.shape[:-1] + (rope,))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


@jax.named_scope("o_proj")
def _mla_out(attn, lp):
    """On ``_heads_flat`` rows."""
    return jnp.einsum("...h,hd->...d", attn, lp["o"]["w"])


def _write_latent_pages(kp, tables, rows, layer, kernel: bool):
    """A prompt's latent rows [B, n, 1, ps, W] into the pool: by pairs
    on the chip through a kernel of page copies (ops/pallas/dsa.py), the
    scatter everywhere else."""
    if kernel and by_pairs(kp):
        from vgate_tpu.ops.pallas.dsa import dsa_write_pages_pallas

        return dsa_write_pages_pallas(kp, tables, rows, layer)
    return kv_write_pages(kp, tables, rows, layer=layer)


def _mla_prompt(normed, lp, spec: ModelSpec, positions, kp, vp, index,
                write_tables, ctx_tables, attend, cq=None,
                kernel: bool = False, n_rows=None, norm=_as_is):
    """Latent attention over prompt rows normed [B, S, D]: the rows'
    latent goes to the pool (whole pages), K and V are expanded from the
    prompt's own rows or, for a suffix against a cached prefix
    (``ctx_tables``), from the pool's rows of the whole context, and are
    never cached.  ``n_rows``: ``_by_row_blocks``, whose blocks come
    un-normed where the caller hands their ``norm``."""
    B, S = normed.shape[:2]
    ps, width = page_tokens(kp), kp.shape[-1]
    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)

    def front(normed, positions, *cq):
        normed = norm(normed)
        q = jnp.concatenate(
            _mla_q(normed, lp, spec, positions, *cq), axis=-1)
        return q, _mla_latent(normed, lp, spec, positions, width)

    q, rows = by_rows(front, normed, positions,
                      *(() if cq is None else (cq,)))
    kp = _write_latent_pages(
        kp, write_tables[:, :S // ps],
        rows.reshape(B, S // ps, 1, ps, width), index, kernel)
    if ctx_tables is not None:
        rows = mla_gather_rows(kp, ctx_tables, index)
    k, v = by_rows(lambda rows: _mla_expand(rows, lp, spec), rows)
    with jax.named_scope("attention"):
        attn = attend(q, k, v, kp, vp, index)
    return by_rows(lambda attn: _mla_out(attn, lp), _heads_flat(attn)), kp


def _mla_step(normed, lp, spec: ModelSpec, positions, kp, vp, index,
              write_attend, cq=None):
    """Latent attention for one decode step, normed [B, D], in the
    ABSORBED form: ``W_uk`` folded into the query, ``W_uv`` into the
    output, the step reads the pool's rows alone."""
    q_nope, q_rope = _mla_q(normed[:, None], lp, spec, positions[:, None],
                            cq)
    width = kp.shape[-1]
    row = _mla_latent(normed[:, None], lp, spec, positions[:, None],
                      width)[:, 0]
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bhn,khn->bhk", q_nope[:, 0], lp["kv_b_k"]["w"])
        pad = jnp.zeros(q_lat.shape[:-1] + (width - spec.latent_dim,),
                        q_lat.dtype)
        q_abs = jnp.concatenate([q_lat, q_rope[:, 0], pad], axis=-1)
    attn, kp, vp = write_attend(q_abs, row, None, kp, vp, index)
    with jax.named_scope("mla_absorb"):
        attn = jnp.einsum("bhk,khv->bhv", attn, lp["kv_b_v"]["w"])
    return _mla_out(_heads_flat(attn), lp), kp, vp


def _index_rotate(t, positions, spec: ModelSpec):
    """Rotary on the FIRST ``index_rotary_dim`` dimensions of index
    heads t [..., S, heads, di] (at the temporal component of a position
    of several), then zeros up to the pool row's lanes
    (``index_key_lanes``: a dot product gains nothing)."""
    if spec.mrope_section and positions.ndim == t.ndim - 1:
        positions = positions[0]
    t = apply_rope(t, positions, spec.rope_theta, spec.rope_scaling,
                   rotary_dim=spec.index_rotary_dim)
    pad = spec.index_key_lanes - spec.index_head_dim
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, pad),)) if pad else t


@jax.named_scope("dsa_index")
def _dsa_index_key(normed, lp, spec: ModelSpec, positions):
    """A picking layer's ONE index key a token on normed rows [..., S,
    D]: a LayerNorm with weight and bias, then the rotation, [..., S,
    di]."""
    k32 = jnp.einsum("...d,dk->...k", normed,
                     lp["index_k"]["w"]).astype(jnp.float32)
    k32 = k32 - jnp.mean(k32, axis=-1, keepdims=True)
    k32 = k32 * jax.lax.rsqrt(
        jnp.mean(k32 * k32, axis=-1, keepdims=True) + 1e-6)
    key = (k32 * lp["index_k_norm"].astype(jnp.float32)
           + lp["index_k_bias"].astype(jnp.float32)).astype(normed.dtype)
    return _index_rotate(key[..., None, :], positions, spec)[..., 0, :]


@jax.named_scope("dsa_index")
def _dsa_index_query(normed, cq, lp, spec: ModelSpec, positions):
    """A picking layer's index queries on normed rows [..., S, D] with
    their query latent ``cq`` (None: the attention has none, and they
    come from the normed rows themselves): [..., S, Hi, di], rotated,
    and the heads' weights [..., S, Hi] float32 with the two scales in
    (``Hi^-0.5 x di^-0.5``)."""
    Hi, di = spec.index_n_heads, spec.index_head_dim
    qi = jnp.einsum("...r,rh->...h", normed if cq is None else cq,
                    lp["index_q"]["w"])
    qi = _index_rotate(qi.reshape(*qi.shape[:-1], Hi, di), positions, spec)
    w = jnp.einsum("...d,dh->...h", normed, lp["index_w"]["w"],
                   preferred_element_type=jnp.float32)
    return qi, w * (Hi ** -0.5 * di ** -0.5)


# rows of a prompt's index scores held at once, [rows, keys] float32
DSA_SCORE_ROWS = 1024
# bytes one expanded K (or V, or Q) of a prompt may take: beyond it a
# prompt's attention goes through in groups of heads
DSA_EXPAND_BYTES = 80 << 20


@jax.named_scope("dsa_select")
def _dsa_prompt_mask(scores, live, topk: int):
    """scores [..., S, T] float32 (``-inf`` where not ``live``) -> the
    selection as an int8 mask."""
    return (dsa.select_mask(scores, topk) & live).astype(jnp.int8)


def _dsa_prompt_select(normed, cq, lp, keys, positions, total_lens,
                       spec: ModelSpec, kernel: bool, n_rows=None,
                       norm=_as_is):
    """The selection of prompt rows (normed [B, S, D] with their query
    latent, at ``positions`` [B, S]) over the context's index keys [B, T,
    di] (from position 0) as a mask [B, S, T] int8: nonzero where the
    row attends.  ``kernel``: a whole prompt's rows against their own
    keys, a block of rows at a time (its index queries, its scores
    through the scoring kernel, its threshold), so that neither an [S,
    S] float32 array nor all rows' index queries stand; with ``n_rows``
    (``_by_row_blocks``) the blocks of real rows alone, the others'
    mask zero."""
    B, S = normed.shape[:2]
    T = keys.shape[1]
    if kernel and S % DSA_SCORE_ROWS == 0:
        from vgate_tpu.ops.pallas.dsa import dsa_prompt_scores_pallas

        def block(rows, cq_rows, pos, b):
            qi, w = _dsa_index_query(norm(rows), cq_rows, lp, spec, pos)
            with jax.named_scope("dsa_index"):
                scores = dsa_prompt_scores_pallas(qi, w, keys[b], pos[0])
            return _dsa_prompt_mask(scores, scores > dsa.NEG_INF,
                                    spec.index_topk)

        if n_rows is not None:
            return jnp.stack([
                _by_row_blocks(
                    functools.partial(block, b=b),
                    (normed[b], None if cq is None else cq[b],
                     positions[b]), n_rows, axis=0)
                for b in range(B)])
        R = DSA_SCORE_ROWS
        blocks = lambda t, b: None if t is None else t[b].reshape(
            (S // R, R) + t.shape[2:])
        return jnp.stack([
            jax.lax.map(
                lambda xs, b=b: block(*xs, b),
                (blocks(normed, b), blocks(cq, b), blocks(positions, b)),
            ).reshape(S, T)
            for b in range(B)])
    qi, w = _by_row_blocks(
        lambda rows, cq, positions: _dsa_index_query(
            norm(rows), cq, lp, spec, positions),
        (normed, cq, positions), n_rows)
    k_pos = jnp.arange(T)[None, None, :]
    live = ((k_pos <= positions[..., None])
            & (k_pos < total_lens[:, None, None]))
    with jax.named_scope("dsa_index"):
        scores = jnp.where(live, dsa.index_scores(qi, w, keys), dsa.NEG_INF)
    return _dsa_prompt_mask(scores, live, spec.index_topk)


def _head_groups(spec: ModelSpec, rows: int, itemsize: int) -> int:
    """Groups of heads a prompt's attention under a selection goes
    through in: the fewest that keep one expanded K under
    ``DSA_EXPAND_BYTES``."""
    H = spec.num_heads
    per_head = rows * (spec.qk_nope_head_dim + spec.qk_rope_head_dim) * itemsize
    return next(g for g in range(1, H + 1)
                if H % g == 0 and per_head * (H // g) <= DSA_EXPAND_BYTES)


def _latent_layer(spec: ModelSpec, index, picks: bool):
    """The latent pool's layer of a sub-block under a selection: the
    reusing layers' rows first (``index`` among them), the picking
    layers' behind them (``index`` among those, which is also their
    layer of the index keys' array)."""
    return index + (spec.attn_layers - spec.index_layers if picks else 0)


def _dsa_prompt(normed, lp, spec: ModelSpec, positions, kp, vp, st, index,
                picks: bool, write_tables, ctx_tables, total_lens, attend,
                attend_selected, kernel: bool, n_rows=None, norm=_as_is):
    """Latent attention under a selection over prompt rows normed [B, S,
    D] (``_mla_prompt``'s pass: the rows' latent to the pool, K and V
    expanded and never cached).  A layer that ``picks`` writes the rows'
    index keys to the pool's second array (``vp``, the same pages),
    scores the context's and leaves the selection in ``st["sel"]``; its
    attention, as a reusing layer's, runs under that mask
    (``attend_selected(q, k, v, mask)``), a group of heads at a time
    where the expansion is large.  A context of at most ``index_topk``
    tokens is attended whole: ``_mla_prompt`` with ``attend``.
    ``n_rows``: ``_by_row_blocks``, whose blocks come un-normed where
    the caller hands their ``norm``."""
    B, S = normed.shape[:2]
    ps, width = page_tokens(kp), kp.shape[-1]
    layer = _latent_layer(spec, index, picks)
    T = S if ctx_tables is None else ctx_tables.shape[1] * ps
    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)
    cq = by_rows(lambda rows: _mla_cq(norm(rows), lp, spec), normed)
    if picks:
        key = by_rows(lambda rows, positions: _dsa_index_key(
            norm(rows), lp, spec, positions), normed, positions)
        vp = kv_write_pages(
            vp, write_tables[:, :S // ps],
            key.reshape(B, S // ps, 1, ps, key.shape[-1]), layer=index)
    if T <= spec.index_topk:  # nothing to leave out
        out, kp = _mla_prompt(normed, lp, spec, positions, kp, None, layer,
                              write_tables, ctx_tables, attend, cq=cq,
                              kernel=kernel, n_rows=n_rows, norm=norm)
        return out, kp, vp, st
    if picks:
        keys = key if ctx_tables is None else mla_gather_rows(
            vp, ctx_tables, index)
        st = {**st, "sel": _dsa_prompt_select(
            normed, cq, lp, keys, positions, total_lens, spec,
            kernel and ctx_tables is None, n_rows, norm)}
    mask = st["sel"]
    rows = by_rows(lambda rows, positions: _mla_latent(
        norm(rows), lp, spec, positions, width), normed, positions)
    kp = _write_latent_pages(
        kp, write_tables[:, :S // ps],
        rows.reshape(B, S // ps, 1, ps, width), layer, kernel)
    if ctx_tables is not None:
        rows = mla_gather_rows(kp, ctx_tables, layer)
    H, kl = spec.num_heads, spec.kv_lora_rank
    nope, rope, vd = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
    G = _head_groups(spec, T, normed.dtype.itemsize)
    by_group = lambda t, axis: jnp.moveaxis(
        t.reshape(t.shape[:axis] + (G, H // G) + t.shape[axis + 1:]), axis, 0)
    weights = (
        by_group(lp["q_b"]["w"].reshape(-1, H, nope + rope), 1),
        by_group(lp["kv_b_k"]["w"], 1), by_group(lp["kv_b_v"]["w"], 1),
        lp["o"]["w"].reshape(G, H // G * vd, -1),
    )

    def group(acc, ws):
        q_b, w_uk, w_uv, w_o = ws
        q = by_rows(lambda cq, positions: jnp.concatenate(_mla_q(
            None, lp, spec, positions, cq=cq,
            q_b=q_b.reshape(q_b.shape[0], -1)), axis=-1), cq, positions)
        k, v = by_rows(lambda rows: _mla_expand(
            rows, {"kv_b_k": {"w": w_uk}, "kv_b_v": {"w": w_uv}}, spec),
            rows)
        with jax.named_scope("dsa_attend"):
            attn = attend_selected(q, k, v, mask)

        @jax.named_scope("o_proj")
        def o_proj(acc, attn):
            return acc + jnp.einsum("bsh,hd->bsd", attn, w_o,
                                    preferred_element_type=jnp.float32)

        return by_rows(o_proj, acc, _heads_flat(attn)), None

    zero = jnp.zeros(normed.shape, jnp.float32)
    if G == 1:
        out, _ = group(zero, jax.tree.map(lambda a: a[0], weights))
    else:
        out, _ = jax.lax.scan(group, zero, weights)
    return out.astype(normed.dtype), kp, vp, st


def _dsa_step(normed, lp, spec: ModelSpec, positions, kp, vp, st, index,
              picks: bool, pick, attend):
    """Latent attention under a selection for one decode step, normed
    [B, D], absorbed as ``_mla_step``: a layer that ``picks`` hands its
    index queries, weights and the token's index key to ``pick`` (the
    caller's: the key into the pool's second array, the scores over the
    slot's pages, the ``index_topk`` positions) and leaves the positions
    in ``st["sel"]``; ``attend`` writes the token's latent row and
    attends over the selected rows alone."""
    layer = _latent_layer(spec, index, picks)
    cq = _mla_cq(normed[:, None], lp, spec)
    if picks:
        at = positions[:, None]
        qi, w = _dsa_index_query(normed[:, None], cq, lp, spec, at)
        key = _dsa_index_key(normed[:, None], lp, spec, at)
        sel, vp = pick(qi[:, 0], w[:, 0], key[:, 0], vp, index)
        st = {**st, "sel": sel}
    out, kp, _ = _mla_step(
        normed, lp, spec, positions, kp, None, layer,
        lambda q, row, _v, kp_, vp_, layer_: attend(
            q, row, st["sel"], kp_, layer_) + (None,),
        cq=cq)
    return out, kp, vp, st


def _write_kv_rows(kp, tables, k, v, layer, kernel: bool):
    """A prompt's k, v [B, S, KV, hd] into a pool of K over V, whole
    pages: through the kernel of page copies on the chip (XLA's scatter
    into a pool whose trailing dimensions are a pair re-lays the WHOLE
    pool), the scatter everywhere else."""
    pages = dsa.kv_rows_pages(k, v, kp.shape[3])
    if kernel:
        from vgate_tpu.ops.pallas.dsa import dsa_write_pages_pallas

        return dsa_write_pages_pallas(kp, tables, pages, layer)
    return kp.at[layer, 0, tables].set(pages.astype(kp.dtype))


def _kv_dsa_prompt(normed, lp, spec: ModelSpec, positions, kp, vp, index,
                   write_tables, ctx_tables, total_lens, attend,
                   attend_selected, kernel: bool, n_rows=None,
                   norm=_as_is):
    """GQA attention under the layer's OWN selection over prompt rows
    normed [B, S, D] (``ModelSpec.kv_rows``): the rows' K over V go to
    the pool ``kp`` and their index keys to ``vp`` (the same pages), the
    layer's indexer scores the context's keys and the attention runs
    under that mask (``attend_selected(q, k, v, mask)``).  A context of
    at most ``index_topk`` tokens is attended whole, with ``attend``.  A
    suffix or a later chunk (``ctx_tables``) reads the whole context's
    K, V and index keys back from the pool.  ``n_rows``:
    ``_by_row_blocks``, whose blocks come un-normed where the caller
    hands their ``norm``."""
    B, S = normed.shape[:2]
    ps = kp.shape[3]
    T = S if ctx_tables is None else ctx_tables.shape[1] * ps
    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)

    def front(rows, positions):
        rows = norm(rows)
        q, k, v, _ = _gated_qkv(rows, lp, spec, positions)
        return q, k, v, _dsa_index_key(rows, lp, spec, positions)

    q, k, v, key = by_rows(front, normed, positions)
    tables = write_tables[:, :S // ps]
    vp = kv_write_pages(
        vp, tables, key.reshape(B, S // ps, 1, ps, key.shape[-1]),
        layer=index)
    kp = _write_kv_rows(kp, tables, k, v, index, kernel)
    if ctx_tables is not None:
        k, v = dsa.kv_rows_gather(kp, ctx_tables, index, spec.num_kv_heads)
    if T <= spec.index_topk:  # nothing to leave out
        with jax.named_scope("attention"):
            attn = attend(q, k, v, kp, vp, index)
    else:
        keys = key if ctx_tables is None else mla_gather_rows(
            vp, ctx_tables, index)
        mask = _dsa_prompt_select(
            normed, None, lp, keys, positions, total_lens, spec,
            kernel and ctx_tables is None, n_rows, norm)
        with jax.named_scope("dsa_attend"):
            attn = attend_selected(q, k, v, mask)
    out = by_rows(lambda attn: _gated_out(attn, None, lp, normed.dtype),
                  _heads_flat(attn))
    return out, kp, vp


def _kv_dsa_step(normed, lp, spec: ModelSpec, positions, kp, vp, index,
                 pick, attend):
    """GQA attention under the layer's own selection for one decode
    step, normed [B, D]: the token's index key to ``pick`` (the
    caller's: into ``vp``, the scores over the slot's pages, the picks'
    places), its K over V into ``kp`` and the attention over the picked
    tokens alone by ``attend``."""
    at = positions[:, None]
    rows = normed[:, None]
    q, k, v, _ = _gated_qkv(rows, lp, spec, at)
    qi, w = _dsa_index_query(rows, None, lp, spec, at)
    key = _dsa_index_key(rows, lp, spec, at)
    sel, vp = pick(qi[:, 0], w[:, 0], key[:, 0], vp, index)
    attn, kp = attend(q[:, 0], (k[:, 0], v[:, 0]), sel, kp, index)
    out = _gated_out(_heads_flat(attn), None, lp, normed.dtype)
    return out, kp, vp


class _LayerTensors(Mapping):
    """ONE layer's tensors out of its group's stacked ``[P, n, ...]``
    ones, each sliced by ``(period, layer)`` where it is read: what the
    stack walker hands every sub-block of every pass.  A matrix sliced
    inside the product that reads it is read where it stands (the
    product's fusion takes the whole stacked parameter and the two
    indices).  A scan that carried the weights as its ``xs`` sliced
    them at the top of its body, every matrix of the layer at once, and
    a slice that feeds an inner loop, or that several layers slice
    again, is a buffer: a copy of the period's matrices every step
    (2.28 ms of the LFM2 cut's 23.6 ms decode step, 450 MB a step of
    the qwen3-next cut's, whose chunk's temporaries fell 505.7 -> 234.9
    MB; compiled for the v5e, PR 48).  Only a matrix that a loop over
    blocks of rows (``_by_row_blocks``) reads is still copied, one at a
    time: it is an operand of that loop, and XLA holds an operand as a
    copy however late the slice is taken (the K-EXAONE cut's 8,192-row
    program's temporaries 1.53 -> 1.42 GB, the GLM cut's 16,384-row
    one's 2.90 -> 2.62, PR 42)."""

    def __init__(self, tree, index):
        self._tree, self._index = tree, index

    def __getitem__(self, name):
        value = self._tree[name]
        if isinstance(value, dict):
            return _LayerTensors(value, self._index)
        return value[self._index]

    def __iter__(self):
        return iter(self._tree)

    def __len__(self):
        return len(self._tree)


def _segments(blocks):
    """A period's sub-blocks as runs ``(unit, repeats)``: the longest
    run of a repeated unit of up to four sub-blocks at each place, else
    the one sub-block once.  Blocks repeat when kind, group and norm do
    (the layer's index moves on)."""
    same = lambda a, b: a[:3] == b[:3]
    out, i = [], 0
    while i < len(blocks):
        best = (1, 1)
        for u in range(1, 5):
            r = 1
            while (i + (r + 1) * u <= len(blocks) and all(
                    same(blocks[i + j], blocks[i + r * u + j])
                    for j in range(u))):
                r += 1
            if r > 1 and u * r > best[0] * best[1]:
                best = (u, r)
        u, r = best
        out.append((blocks[i:i + u], r))
        i += u * r
    return out


def _period_scan(params, spec: ModelSpec, x0, k_pages, v_pages, state,
                 block_fn):
    """THE stack walker: the leading layers once (a ``window_pattern``
    spec's ``layers["lead"]``, each its own tensors), then a scan over
    periods, each the spec's sub-blocks in order.  ``block_fn(kind,
    rows, lp, kp, vp, st, index, stack, norm)`` -> ``(out, kp, vp, st,
    stats | None)`` computes one sub-block on the stream's rows as they
    stand, which it norms itself (``norm(rows)``: at once, or a block
    at a time in a pass that loops over blocks of rows): ``index`` is
    the layer's index among the layers of its kind over the whole stack,
    leading ones first (the pools' layer for ``attn``, the state's for a
    recurrent kind, the rings' for ``swa``); for ``moe`` it indexes
    ``stack``, the expert matrices of the layer's group ``[layers, E, .,
    .]`` (a leading layer's own, ``[1, E, ., .]``); ``lp`` is the
    layer's tensors without them: ``_LayerTensors`` over the group's
    stacked ``[P, n, ...]`` ones.  The scans carry INDICES alone (the
    period, a unit's repeat): no weight is an operand of a scan, so none
    is copied out of the parameters, and a product reads its matrix
    where it stands.
    Returns (x, k_pages, v_pages, state, stats [4])."""
    layers = dict(params["layers"])
    lead = layers.pop("lead", ())
    if "full" in layers:  # one attention layer a period: [P, ...]
        layers["full"] = jax.tree.map(lambda a: a[:, None], layers["full"])
    # (a stack without expert layers: its dense matrices are ``light``)
    names = spec.expert_stacks if spec.moe_layers else ()
    light = {g: {k: v for k, v in d.items() if k not in names}
             for g, d in layers.items()}
    flat = lambda w: jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), w)
    stacks = {g: {k: flat(d[k]) for k in names if k in d}
              for g, d in layers.items()}
    count = {g: spec.group_layers(g) for g in layers}
    uo = spec.unit_offset_norm

    def run(carry, block, lp, index, stack):
        kind, _, norm, _ = block
        h, kp, vp, st = carry
        normed = lambda h: rms_norm(h, lp[norm], spec.rms_eps, uo)
        if spec.fp32_residual:  # the products take the weights' type
            normed = lambda h: rms_norm(
                h, lp[norm], spec.rms_eps, uo).astype(lp[norm].dtype)
        out, kp, vp, st, stats = block_fn(
            kind, h, lp, kp, vp, st, index, stack, normed)
        if spec.residual_multiplier != 1.0:  # Granite's, every sub-block
            out = out * jnp.asarray(spec.residual_multiplier, out.dtype)
        return (h + out.astype(h.dtype), kp, vp, st), stats

    # the leading layers, unrolled: layer i's mixer is the i-th of its
    # kind, its experts a stack of one
    carry, lead_stats, base = (x0, k_pages, v_pages, state), [], {}
    for lp, kinds in zip(lead, spec.lead_blocks):
        for kind, norm in zip(kinds, ("input_norm", "post_norm")):
            own = kind == "moe"
            carry, s = run(
                carry, (kind, "lead", norm, 0),
                {k: v for k, v in lp.items() if not own or k not in names},
                0 if own else base.get(kind, 0),
                {k: jax.tree.map(lambda a: a[None], lp[k])
                 for k in names} if own else None)
            base[kind] = base.get(kind, 0) + 1
            if s is not None:
                lead_stats.append(s)
    base.pop("moe", None)  # a group's expert stacks start at its own 0

    def period(carry, p):
        all_stats = []
        for unit, repeats in _segments(spec.period_blocks):
            # group -> its layers that one repeat of the unit passes
            width = {g: len({b[3] for b in unit if b[1] == g})
                     for g in {b[1] for b in unit}}

            def unit_fn(c, j, unit=unit, width=width):
                stats = []
                for b in unit:
                    g = b[1]
                    layer = b[3] + j * width[g]
                    index = p * count[g] + layer
                    if base.get(b[0]):  # behind the leading layers' own
                        index = base[b[0]] + index
                    c, s = run(c, b, _LayerTensors(light[g], (p, layer)),
                               index, stacks[g])
                    if s is not None:
                        stats.append(s)
                return c, (jnp.stack(stats) if stats
                           else jnp.zeros((0, len(STAT_NAMES)), jnp.int32))

            js = jnp.arange(repeats, dtype=jnp.int32)
            if repeats > 2:
                # the unit's repeats as an inner scan: one body traced,
                # lowered and compiled for all of them
                carry, stats = jax.lax.scan(unit_fn, carry, js)
                all_stats.append(stats.reshape(-1, len(STAT_NAMES)))
            else:  # once, or twice: unrolled.  A scan of two buys
                # nothing (the LFM2 cut compiled for the v5e either way,
                # PR 48: the decode chunk's temporaries 150.5 MB
                # unrolled, 150.8 scanned; its three prompt programs
                # compile 0.5-1.3 s slower scanned, the chunk 1 s
                # faster); the reason is no longer the weights, which
                # neither form copies.  The repeat stays a traced index
                # (``js[j]``): under a constant one XLA lifts the slice
                # out of the periods' loop, + 79 MB in the K-EXAONE
                # cut's 8,192-row prompt program
                for j in range(repeats):
                    carry, stats = unit_fn(carry, js[j])
                    all_stats.append(stats)
        return carry, jnp.concatenate(all_stats)

    (x, k_pages, v_pages, state), stats = jax.lax.scan(
        period, carry, jnp.arange(spec.num_periods, dtype=jnp.int32))
    stats = stats.reshape(-1, len(STAT_NAMES))
    if lead_stats:
        stats = jnp.concatenate([jnp.stack(lead_stats), stats])
    if not spec.moe_layers:  # no expert layer: nothing counted
        stats = jnp.zeros((1, len(STAT_NAMES)), jnp.int32)
    return x, k_pages, v_pages, state, combine_stats(stats)


def _experts(normed, lp, spec: ModelSpec, row_mask, use_pallas, index,
             stack, by_rows=None):
    from vgate_tpu.models.decoder import _act

    return expert_layer(
        normed, lp, spec, lambda x32: _act(x32, spec), row_mask=row_mask,
        use_pallas=use_pallas, layer=index, stack=stack, by_rows=by_rows,
    )


# rows x width of a dense feed-forward's float32 activations held at once
DENSE_BLOCK_ELEMS = 8192 * 18432


@jax.named_scope("dense_mlp")
def _dense(normed, lp, spec: ModelSpec, n_rows=None, norm=_as_is):
    """The dense SwiGLU; a long prompt's rows [B, S, D] in blocks, so
    that the float32 gate of 16,384 rows never stands whole: the blocks
    of real rows (``n_rows``: ``_by_row_blocks``, which may ``norm`` the
    block it reads), or all of them."""
    from vgate_tpu.models.decoder import _dense_mlp

    if n_rows is not None:
        return _by_row_blocks(
            lambda rows: _dense_mlp(norm(rows), lp, spec), (normed,),
            n_rows)
    S = normed.shape[-2]
    blocks = 1
    while (S // blocks * spec.intermediate_size > DENSE_BLOCK_ELEMS
           and S % (2 * blocks) == 0):
        blocks *= 2
    if normed.ndim < 3 or blocks == 1:
        return _dense_mlp(normed, lp, spec)
    B, _, D = normed.shape
    rows = jnp.moveaxis(normed.reshape(B, blocks, S // blocks, D), 1, 0)
    out = jax.lax.map(lambda r: _dense_mlp(r, lp, spec), rows)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, D)


def _attn_scope(spec: ModelSpec) -> str:
    """The full-attention sub-block's scope in a trace."""
    return ("full_attn" if spec.window_pattern or spec.conv_pattern
            else "gated_attn")


def _swa_chunk_attend(q, k, v, ring_k, ring_v, index, spec: ModelSpec,
                      slots, prefix_lens, lens):
    """Window attention of a LATER chunk's rows (q, k, v [B, S, ., hd],
    starting at ``prefix_lens``, page-aligned): against the slot's ring
    as the chunks before left it (the ``R`` pages before the chunk's
    first, gathered in position order) and the chunk's own rows.  The
    blockwise ``jax.numpy`` attention: no kernel yet for query rows
    against a ring."""
    ps = ring_k.shape[-2]
    R = ring_pages(spec, ps)
    rings = (ring_k.shape[2] - 1) // R
    first = prefix_lens // ps
    tables = ring_tables(slots, R, rings, R, first=first - R)
    past = lambda ring: jnp.transpose(
        gather_pages(ring, tables, layer=index), (1, 2, 3, 0, 4)).reshape(
            q.shape[0], R * ps, ring.shape[1], ring.shape[-1])
    keys = jnp.concatenate([past(ring_k), k], axis=1)
    vals = jnp.concatenate([past(ring_v), v], axis=1)
    held = jnp.full_like(prefix_lens, R * ps)
    return flash_prefill_attention(
        q, keys, vals, held + lens, q_offset=held,
        window=spec.sliding_window,
        # ring rows of positions before the sequence's first are nobody's
        k_start=held - jnp.minimum(prefix_lens, held),
        block_k=ps,
    )


def _eva_prompt(normed, lp, spec: ModelSpec, positions, kp, vp, win, index,
                lens, write_tables, ctx_tables, prefix_lens, attend,
                eva_attend, n_rows=None, norm=_as_is):
    """EVA attention over prompt rows normed [B, S, D] (``win`` [B,
    pages a window]: the pool pages of each row's slot's open window).
    The rows' chunk summaries go to the sequence's pages, the rows of
    the last window they reach to the slot's window pages.  A WHOLE
    prompt of whole windows attends window by window through the
    caller's flash attention (``eva_attend(q, k, v, lens, q_offset,
    k_start)``): a window's queries meet ``[every chunk's summary,
    newest first | the window's own rows]``, of which they see the
    closed windows' summaries (``k_start``) and, causally, their own
    rows: one softmax, one launch, and no block of keys that nobody
    sees is read; a prompt inside one window is plain causal attention
    (``attend``).  Any other rows (a later chunk of a chunked prefill,
    from ``prefix_lens``; a bucket that is no whole number of windows)
    attend to ``[the pool's summary rows | the window's pages as they
    stand | their own rows]`` under ``ops/eva.py interval_attention``.
    Returns (out, k_pages, v_pages)."""
    B, S = normed.shape[:2]
    ps = page_tokens(kp)
    W, c = spec.eva_window, spec.eva_chunk
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    R, scale = W // ps, hd ** -0.5
    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)
    to_pages = lambda t: jnp.transpose(
        t.reshape(B, -1, ps, KV, hd), (0, 1, 3, 2, 4))
    q, k, v, _ = by_rows(
        lambda rows, positions: _gated_qkv(norm(rows), lp, spec, positions),
        normed, positions)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    ks, vs = eva.summarize(k, v, lp["eva_phi"], lp["eva_mu"], valid, c,
                           scale)  # [B, S / c, KV, hd]
    if prefix_lens is None and (S <= W or S % W == 0):
        n_sum = cdiv(S // c, ps)  # whole pages from the sequence's first
        pad = ((0, 0), (0, n_sum * ps - S // c), (0, 0), (0, 0))
        with jax.named_scope("eva_summarize"):
            kp = kv_write_pages(kp, write_tables[:, :n_sum],
                                to_pages(jnp.pad(ks, pad)), layer=index)
            vp = kv_write_pages(vp, write_tables[:, :n_sum],
                                to_pages(jnp.pad(vs, pad)), layer=index)
        if S <= W:
            with jax.named_scope("eva_attend"):
                attn = attend(q, k, v, kp, vp, index)
            tables, last_k, last_v = win[:, :S // ps], k, v
        else:
            nw, ns = S // W, S // c
            fold = lambda t: t.reshape((B * nw, W) + t.shape[2:])
            spread = lambda t: jnp.broadcast_to(
                jnp.flip(t, axis=1)[:, None], (B, nw) + t.shape[1:]
            ).reshape((B * nw,) + t.shape[1:])
            cat = lambda a, b: jnp.concatenate([spread(a), fold(b)], axis=1)
            first = jnp.arange(nw, dtype=jnp.int32)[None, :] * W
            own = jnp.clip(lens[:, None] - first, 0, W).reshape(-1)
            closed = jnp.broadcast_to(first // c, (B, nw)).reshape(-1)
            with jax.named_scope("eva_attend"):
                attn = eva_attend(
                    fold(q), cat(ks, k), cat(vs, v), ns + own,
                    jnp.full_like(own, ns), ns - closed,
                ).reshape(B, S, H, hd)
            at = (lens - 1) // W * W  # the last window a row reaches
            cut = lambda t: jax.vmap(
                lambda rows, lo: jax.lax.dynamic_slice_in_dim(rows, lo, W)
            )(t, at)
            tables, last_k, last_v = win, cut(k), cut(v)
    else:
        start = jnp.zeros_like(lens) if prefix_lens is None else prefix_lens
        ctx = write_tables if ctx_tables is None else ctx_tables
        # the rows' summaries into the pool rows their chunks own
        j = start[:, None] // c + jnp.arange(S // c)[None, :]
        there = jnp.arange(S // c)[None, :] < cdiv(lens, c)[:, None]
        ids = jnp.where(there, jnp.take_along_axis(
            ctx, jnp.minimum(j // ps, ctx.shape[1] - 1), axis=1), 0)
        held = (eva.gather_rows(kp, win, index),
                eva.gather_rows(vp, win, index))  # before the rows land
        with jax.named_scope("eva_summarize"):
            kp = kv_write_tokens(kp, ids, j % ps, ks, layer=index)
            vp = kv_write_tokens(vp, ids, j % ps, vs, layer=index)
        keys = jnp.concatenate(
            [eva.gather_rows(kp, ctx, index), held[0], k], axis=1)
        vals = jnp.concatenate(
            [eva.gather_rows(vp, ctx, index), held[1], v], axis=1)
        lo, hi = eva.chunk_keys(start, lens, S, ctx.shape[1] * ps, W, c)
        attn = eva.interval_attention(q, positions, keys, vals, lo, hi,
                                      scale)
        # the pages of the last window the rows reach keep their place
        page = start[:, None] // ps + jnp.arange(S // ps)[None, :]
        keep = page // R == ((start + lens - 1) // W)[:, None]
        tables = jnp.where(
            keep, jnp.take_along_axis(win, page % R, axis=1), 0)
        last_k, last_v = k, v
    with jax.named_scope("kv_write"):
        kp = kv_write_pages(kp, tables, to_pages(last_k), layer=index)
        vp = kv_write_pages(vp, tables, to_pages(last_v), layer=index)
    out = by_rows(lambda attn: _gated_out(attn, None, lp, attn.dtype),
                  _heads_flat(attn))
    return out, kp, vp


def _without_selection(state):
    """The state without the walk's own ``"sel"`` (None if that was all)."""
    if state and "sel" in state:
        state = {k: v for k, v in state.items() if k != "sel"} or None
    return state


def prompt_forward(params, spec: ModelSpec, x, lens, positions, k_pages,
                   v_pages, state, slots, fresh, write_tables, attend,
                   use_pallas: bool, ctx_tables=None, swa_attend=None,
                   prefix_lens=None, dsa_attend=None, total_lens=None,
                   eva_attend=None):
    """The prompt pass over embedded rows x [B, S, D] (a whole prompt,
    or the suffix / one chunk of one).  ``write_tables`` are the pages
    the rows' K/V go to (whole pages from the rows' first position);
    ``attend(q, k, v, kp, vp, layer)`` is the attention the caller
    chose.  ``ctx_tables`` (a suffix or a chunk of a latent-attention
    spec) are the pages of the whole context, whose rows K and V are
    then expanded from.  A window layer attends in flight
    (``swa_attend(q, k, v)``, a whole prompt's) or, for rows that start
    at ``prefix_lens``, against the ring and themselves, and leaves the
    rows that survive in the slot's ring.  A spec that picks
    (``is_dsa``): ``v_pages`` is the index keys' array,
    ``dsa_attend(q, k, v, mask)`` the attention under a selection and
    ``total_lens`` the contexts' lengths (``lens`` for a whole prompt).
    An EVA spec: ``eva_attend`` is ``_eva_prompt``'s, and a later
    chunk's rows come with ``prefix_lens`` and ``ctx_tables``.
    Returns (x, k_pages, v_pages, state)."""
    B, S = x.shape[:2]
    if spec.fp32_residual:
        x = x.astype(jnp.float32)
    ps = page_tokens(k_pages, spec.kv_rows)
    KV, hd = spec.cache_heads, spec.cache_head_dim
    n_pages = S // ps
    row_mask = jnp.arange(S)[None, :] < lens[:, None]
    # a long whole prompt: the position-wise work in blocks of rows, as
    # far as the longest prompt reaches (None: the whole bucket at once)
    _, n_rows = prompt_rows(
        spec, S, lens, prefix_lens is None and ctx_tables is None)
    n_rows = None if isinstance(n_rows, int) else n_rows
    by_rows = lambda fn, *rows: _by_row_blocks(fn, rows, n_rows)
    to_pages = lambda t: jnp.transpose(
        t.reshape(B, n_pages, ps, KV, hd), (0, 1, 3, 2, 4))
    if spec.swa_layers:
        R = ring_pages(spec, ps)
        start = 0 if prefix_lens is None else prefix_lens
        ring_write = ring_tables(
            slots, n_pages, (state["ring_k"].shape[2] - 1) // R, R,
            first=None if prefix_lens is None else prefix_lens // ps,
            last=(start + lens - 1) // ps)

    def block_fn(kind, normed, lp, kp, vp, st, index, stack, norm):
        # the rows come as the stream holds them: a loop over blocks of
        # rows norms the block it reads, any other pass all of them here
        if n_rows is None:
            normed, norm = norm(normed), _as_is
        if kind == "moe":  # gathers its rows: the normed rows stand
            out, stats = _experts(
                by_rows(norm, normed), lp, spec, row_mask, use_pallas,
                index, stack, None if n_rows is None else _by_row_blocks)
            return out, kp, vp, st, stats
        if kind == "mlp":
            return (_dense(normed, lp, spec, n_rows, norm), kp, vp, st,
                    None)
        if kind in _RECURRENT:
            out, st = _recurrent_prompt(
                kind, by_rows(norm, normed), lp, st, index, spec, lens,
                slots, fresh)
            return out, kp, vp, st, None
        if kind == "conv":
            out, st = _conv_prompt(by_rows(norm, normed), lp, st, index,
                                   lens, slots, fresh)
            return out, kp, vp, st, None
        if kind == "dsa" and spec.kv_rows:
            with jax.named_scope("gated_attn"), jax.named_scope(
                    "dsa_prompt"):
                out, kp, vp = _kv_dsa_prompt(
                    normed, lp, spec, positions, kp, vp, index,
                    write_tables, ctx_tables,
                    lens if total_lens is None else total_lens, attend,
                    dsa_attend, use_pallas, n_rows, norm)
            return out, kp, vp, st, None
        if kind in ("mla", "dsa") and spec.is_dsa:
            with jax.named_scope("mla_attn"), jax.named_scope("dsa_prompt"):
                out, kp, vp, st = _dsa_prompt(
                    normed, lp, spec, positions, kp, vp, st, index,
                    kind == "dsa", write_tables, ctx_tables,
                    lens if total_lens is None else total_lens, attend,
                    dsa_attend, use_pallas, n_rows, norm)
            return out, kp, vp, st, None
        if kind == "mla":
            with jax.named_scope("mla_attn"):
                out, kp = _mla_prompt(normed, lp, spec, positions, kp, vp,
                                      index, write_tables, ctx_tables,
                                      attend, n_rows=n_rows, norm=norm)
            return out, kp, vp, st, None
        qkv = lambda kind: by_rows(
            lambda rows, positions: _gated_qkv(
                norm(rows), lp, spec, positions, kind), normed, positions)
        o_proj = lambda attn, gate: by_rows(
            lambda attn, gate: _gated_out(attn, gate, lp, normed.dtype),
            _heads_flat(attn), _heads_flat(gate))
        if kind == "eva":
            with jax.named_scope("eva_attn"):
                out, kp, vp = _eva_prompt(
                    normed, lp, spec, positions, kp, vp,
                    st["eva_pages"][slots], index, lens, write_tables,
                    ctx_tables, prefix_lens, attend, eva_attend, n_rows,
                    norm)
            return out, kp, vp, st, None
        if kind == "swa":
            with jax.named_scope("swa_attn"):
                q, k, v, _ = qkv("swa")
                rk, rv = st["ring_k"], st["ring_v"]
                with jax.named_scope("attention"):
                    attn = (swa_attend(q, k, v) if prefix_lens is None
                            else _swa_chunk_attend(
                                q, k, v, rk, rv, index, spec, slots,
                                prefix_lens, lens))
                st = {**st,
                      "ring_k": kv_write_pages(
                          rk, ring_write, to_pages(k), layer=index),
                      "ring_v": kv_write_pages(
                          rv, ring_write, to_pages(v), layer=index)}
                out = o_proj(attn, None)
            return out, kp, vp, st, None
        with jax.named_scope(_attn_scope(spec)):
            q, k, v, gate = qkv("attn")
            pt = write_tables[:, :n_pages]
            kp = kv_write_pages(kp, pt, to_pages(k), layer=index)
            vp = kv_write_pages(vp, pt, to_pages(v), layer=index)
            with jax.named_scope("attention"):
                attn = attend(q, k, v, kp, vp, index)
            out = o_proj(attn, gate)
        return out, kp, vp, st, None

    if spec.is_dsa and not spec.kv_rows:
        # a pick that later layers reuse rides the carry: a mask [B, S, T]
        T = S if ctx_tables is None else ctx_tables.shape[1] * ps
        state = {**(state or {}), "sel": jnp.zeros(
            (B, S, T) if T > spec.index_topk else (B, 1, 1), jnp.int8)}
    x, k_pages, v_pages, state, _stats = _period_scan(
        params, spec, x, k_pages, v_pages, state, block_fn)
    return x, k_pages, v_pages, _without_selection(state)


def decode_forward(params, spec: ModelSpec, x, positions, k_pages, v_pages,
                   state, active, write_attend, use_pallas: bool,
                   ring_write_attend=None, dsa_steps=None,
                   eva_close=None):
    """One decode step over embedded rows x [B, D], row = slot.
    ``write_attend(q, k, v, kp, vp, layer)`` is the caller's cache step:
    the token's K and V into the pool and its attention over it;
    ``ring_write_attend`` the same over a window layer's rings;
    ``dsa_steps`` (a spec that picks, ``v_pages`` its index keys' array)
    ``(pick, attend, the positions a pick holds)``: ``_dsa_step``.  An
    EVA spec: ``write_attend`` runs over the step's rows as ONE paged
    sequence (ops/eva.py ``decode_view``), and ``eva_close(kp, vp, lp,
    layer)`` then writes the summary rows of every window the step
    filled (ops/eva.py ``decode_close``): on most steps, of none.
    Returns (x, k_pages, v_pages, state, stats [4])."""
    if spec.fp32_residual:
        x = x.astype(jnp.float32)
    if active is None:
        active = jnp.ones(x.shape[:1], bool)

    def attention(normed, lp, cache_step, k_cache, v_cache, index, kind):
        q, k, v, gate = _gated_qkv(
            normed[:, None], lp, spec, positions[:, None], kind)
        attn, k_cache, v_cache = cache_step(
            q[:, 0], k[:, 0], v[:, 0], k_cache, v_cache, index)
        out = _gated_out(_heads_flat(attn), _heads_flat(
            None if gate is None else gate[:, 0]),
                         lp, normed.dtype)
        return out, k_cache, v_cache

    def block_fn(kind, rows, lp, kp, vp, st, index, stack, norm):
        normed = norm(rows)
        if kind == "moe":
            out, stats = _experts(normed, lp, spec, active, use_pallas,
                                  index, stack)
            return out, kp, vp, st, stats
        if kind == "mlp":
            return _dense(normed, lp, spec), kp, vp, st, None
        if kind in _RECURRENT:
            _, step_fn, scope = _RECURRENT[kind]
            with jax.named_scope(scope):
                out, st = step_fn(normed, lp, st, index, spec, active,
                                  use_pallas)
            return out, kp, vp, st, None
        if kind == "conv":
            out, st = _conv_mixer_step(normed, lp, st, index, active)
            return out, kp, vp, st, None
        if kind == "dsa" and spec.kv_rows:
            with jax.named_scope("gated_attn"):
                out, kp, vp = _kv_dsa_step(
                    normed, lp, spec, positions, kp, vp, index,
                    *dsa_steps[:2])
            return out, kp, vp, st, None
        if kind in ("mla", "dsa") and spec.is_dsa:
            with jax.named_scope("mla_attn"):
                out, kp, vp, st = _dsa_step(
                    normed, lp, spec, positions, kp, vp, st, index,
                    kind == "dsa", *dsa_steps[:2])
            return out, kp, vp, st, None
        if kind == "mla":
            with jax.named_scope("mla_attn"):
                out, kp, vp = _mla_step(normed, lp, spec, positions, kp,
                                        vp, index, write_attend)
            return out, kp, vp, st, None
        if kind == "eva":
            with jax.named_scope("eva_attn"):
                q, k, v, _ = _gated_qkv(
                    normed[:, None], lp, spec, positions[:, None])
                with jax.named_scope("eva_attend"):
                    attn, kp, vp = write_attend(
                        q[:, 0], k[:, 0], v[:, 0], kp, vp, index)
                kp, vp = eva_close(kp, vp, lp, index)
                out = _gated_out(_heads_flat(attn), None, lp, normed.dtype)
            return out, kp, vp, st, None
        if kind == "swa":
            with jax.named_scope("swa_attn"):
                out, rk, rv = attention(
                    normed, lp, ring_write_attend, st["ring_k"],
                    st["ring_v"], index, "swa")
            return out, kp, vp, {**st, "ring_k": rk, "ring_v": rv}, None
        with jax.named_scope(_attn_scope(spec)):
            out, kp, vp = attention(normed, lp, write_attend, kp, vp,
                                    index, "attn")
        return out, kp, vp, st, None

    if spec.is_dsa and not spec.kv_rows:
        # a pick that later layers reuse rides the carry: positions [B, k]
        state = {**(state or {}), "sel": jnp.zeros(
            (x.shape[0], dsa_steps[2]), jnp.int32)}
    x, k_pages, v_pages, state, stats = _period_scan(
        params, spec, x, k_pages, v_pages, state, block_fn)
    return x, k_pages, v_pages, _without_selection(state), stats
