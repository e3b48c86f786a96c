"""Architecture specs for the supported model families.

The reference serves whatever vLLM/SGLang can load (opaque to it); here the
architectures are first-party.  Presets cover the north-star configs in
BASELINE.json — Qwen2.5 dense chat models, Mixtral-8x7B (MoE / expert
parallel), bge-base-en-v1.5 (embeddings) — plus Llama-3, Mistral and
Gemma-2 (sliding-window + softcap attention, sandwich norms).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import cached_property
from typing import Callable, Dict, NamedTuple, Optional


class Rotary(NamedTuple):
    """A layer kind's rotary: ``theta`` and ``scaling`` as ops/rope.py
    takes them, ``amplitude`` what cos and sin are multiplied by."""
    theta: float
    scaling: Optional[tuple]
    amplitude: float


@dataclass(frozen=True)
class ModelSpec:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    qkv_bias: bool = True
    tie_embeddings: bool = False
    eos_token_id: int = 151645
    bos_token_id: int = 151643
    # additional model-level stop ids (generation_config eos lists — e.g.
    # Llama-3.1's <|end_of_text|>/<|eom_id|>, Qwen's <|endoftext|>)
    extra_stop_ids: tuple = ()
    # MoE (0 experts => dense)
    num_experts: int = 0
    experts_per_token: int = 0
    # Encoder-only (embeddings) models
    is_encoder: bool = False
    max_position_embeddings: int = 32768
    # Gemma-2 family knobs (defaults reproduce the Qwen/Llama behavior)
    act: str = "silu"  # MLP activation: "silu" | "gelu_tanh"
    attn_softcap: float = 0.0  # tanh soft-capping of attention scores (0=off)
    final_softcap: float = 0.0  # tanh soft-capping of final logits (0=off)
    sliding_window: int = 0  # tokens; >0 => even layers use a local window
    query_scale: float = 0.0  # if >0: q scaled by query_scale**-0.5, not hd**-0.5
    embed_scale: bool = False  # multiply embeddings by sqrt(hidden_size)
    unit_offset_norm: bool = False  # RMSNorm weight convention (1 + w)
    ffn_sandwich: bool = False  # post-attn norm after o_proj + pre/post-FFN norms
    # Llama-3.1 rope scaling (0 = off): low-frequency components slowed by
    # `rope_scaling_factor`, interpolated between the low/high bands.
    rope_scaling_factor: float = 0.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_pos: int = 8192
    # Route 2D packed-int4 weights through the fused dequant Pallas
    # kernel (ops/pallas/quant_matmul.py).  Set per-ENGINE via
    # dataclasses.replace at EngineCore init — the spec rides every
    # forward as a static jit arg, so two engines with different
    # meshes in one process get separate compile caches instead of
    # fighting over a module global.
    quant_kernel: bool = False
    # W8A8/W4A8 (tpu.int8_native): dynamically quantize activations
    # per-token and run the projection GEMMs on the MXU's native
    # s8 x s8 -> s32 path (ops/quant.py int8_native_einsum).  Pure jnp —
    # auto-partitions under any mesh, no Pallas/Mosaic involvement.
    # Threaded per-engine like quant_kernel.
    int8_native: bool = False
    # ---- expert layer (models/decoder.py _expert_layer).  num_experts
    # is the experts HELD here; the router scores `router_width` experts
    # (0 = the held ones: a chip that holds them all) and the held ones
    # are `first_expert .. first_expert + num_experts - 1` of those.
    moe_intermediate_size: int = 0  # 0 = intermediate_size
    shared_expert_intermediate_size: int = 0  # 0 = no shared expert
    router_width: int = 0
    first_expert: int = 0
    # ---- gated full attention (Qwen3-Next): per-head RMSNorm on q and
    # k, a sigmoid gate on the attention output projected beside q, and
    # rope on the first `partial_rotary_factor` of each head
    qk_norm: bool = False
    attn_output_gate: bool = False
    partial_rotary_factor: float = 1.0
    # ---- layer kinds by period: layer l is full attention when
    # (l + 1) % full_attention_interval == 0 and Gated DeltaNet linear
    # attention otherwise (0 = every layer is full attention)
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    # ---- the layer pattern as data (Nemotron-H): one letter a layer,
    # each layer ONE residual sub-block ``h <- h + f(norm(h))``: ``M``
    # Mamba-2, ``*`` attention, ``E`` expert layer.  Empty = the layers
    # of two sub-blocks above (a mixer, then the expert layer)
    layer_pattern: str = ""
    use_rope: bool = True
    # a Mamba-2 layer's sizes, for either spelling that has one (this
    # one's ``M`` and ``mamba_pattern``'s below): heads x head size the
    # inner width, B and C ``mamba_n_groups`` rows of ``mamba_state_size``
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state_size: int = 0
    mamba_n_groups: int = 0
    mamba_conv_kernel: int = 0
    mamba_conv_bias: bool = False
    mamba_chunk_size: int = 128
    # ---- what else an expert layer is told (ops/moe.py): experts that
    # work in a latent of this width (0 = the hidden), the router's
    # scores ("softmax" | "sigmoid": selection by score + a bias, the
    # weights from the scores alone), the factor on the routed sum,
    # experts of three matrices (gated) or two, the shared expert's gate
    moe_latent_size: int = 0
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    moe_gated: bool = True
    shared_expert_gate: bool = True
    # shared experts counted in experts' widths (DeepSeek's key): the
    # shared expert is then n_shared_experts x moe_intermediate_size wide
    n_shared_experts: int = 0
    # ---- multi-head latent attention (kv_lora_rank > 0): queries
    # through a normed rank-q_lora_rank bottleneck, K and V expanded
    # from ONE normed latent of kv_lora_rank a token beside ONE rotary
    # key of qk_rope_head_dim shared by all heads.  The paged cache then
    # holds the latent row [c_kv | k_rope] and no K or V
    # (``latent_dim``, ``cache_*`` below).  ``rope_interleave``: the
    # checkpoint pairs rotary dimensions (2i, 2i+1); undone at load
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # ---- YaRN rotary scaling (yarn_factor > 0) and the position
    # scaling of the queries, gamma(pos) = 1 + beta * ln(1 + pos //
    # yarn_original_max_pos).  The softmax scale under YaRN is
    # head^-0.5 * m^2, m = 0.1 * yarn_mscale_all_dim * ln(factor) + 1
    yarn_factor: float = 0.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_original_max_pos: int = 0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    llama_4_scaling_beta: float = 0.0
    # ---- window and full attention layers in ONE stack (K-EXAONE): one
    # letter a layer by period, ``L`` a layer that attends to the last
    # ``sliding_window`` tokens and keeps them in a per-slot RING
    # (models/hybrid.py: no page a token), ``G`` a full layer over the
    # paged pool.  Empty = Gemma-2's even/odd rule above, a mask only.
    # ``global_rope`` False: the full layers take no rotary
    window_pattern: str = ""
    global_rope: bool = True
    # the rotary by layer KIND (Mellum2's ``rope_parameters``, one group
    # a layer type): the ``yarn_*`` numbers above are the FULL layers'
    # alone and a window layer rotates with the plain frequencies of
    # ``rope_theta``.  ``yarn_attention_factor`` (the published
    # ``attention_factor``; 0 = none) multiplies cos and sin of a layer
    # under YaRN, on q and on k, so its scores carry the square
    yarn_full_only: bool = False
    yarn_attention_factor: float = 0.0
    # leading layers whose feed-forward is a dense SwiGLU of
    # ``intermediate_size`` (DeepSeek's key ``first_k_dense_replace``)
    first_k_dense: int = 0
    # ---- learned sparse attention over the latent cache (GLM-5.2,
    # DeepSeek-V3.2's DSA): one letter a layer over the WHOLE stack, ``F``
    # a layer whose indexer scores every cached token (``index_n_heads``
    # query heads of ``index_head_dim`` against ONE key a token, held in
    # a second array of the paged pool) and picks the ``index_topk`` it
    # attends to, ``S`` a layer that attends to what the nearest ``F``
    # layer below it picked.  Empty = every cached token, always
    indexer_pattern: str = ""
    # the stack is layers ``first_layer .. first_layer + num_layers - 1``
    # of that pattern (and of ``first_k_dense``'s count): a pipeline
    # stage's layers, stated against the published lists
    first_layer: int = 0
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_rope_interleave: bool = False
    # ``index_topk`` WITHOUT a pattern and without a latent (Keye-VL-2.0's
    # ``sa_config``): EVERY layer is GQA attention under a selection of
    # its own, its index queries from the normed hidden state, its pages
    # a token's K over its V in ONE array beside the index keys
    # (``kv_rows``).  The rotary's frequencies by section over a
    # position's three components (M-RoPE; ops/rope.py ``sections``): a
    # text token's components are equal, the served path's
    mrope_section: tuple = ()
    # ---- EVA attention (EvaByte; ops/eva.py): every layer holds a token
    # EXACTLY while its window of ``eva_window`` tokens is open (a buffer
    # a decode slot) and, once the window has closed, as its share of ONE
    # learned summary row for every ``eva_chunk`` tokens in the paged
    # pool (``cache_row_tokens``); exact rows and summary rows meet in
    # one softmax.  0 = softmax attention over every cached token
    eva_window: int = 0
    eva_chunk: int = 0
    # heads of the untied output layer, ``vocab_size`` columns each: head
    # p scores the token at t + 1 + p; serving samples from head 0
    num_pred_heads: int = 1
    # the residual stream is float32 (the published ``fp32_skip_add``);
    # every matrix product still takes the weights' type
    fp32_residual: bool = False
    # ---- gated short convolution (LFM2; models/hybrid.py ``conv``): one
    # letter a layer over the WHOLE stack, ``C`` a layer whose mixer is
    # ``[B | C | X] = u W_in``, a depth-wise causal convolution of
    # ``conv_L_cache`` taps over ``B * X`` (no activation), ``(C * c)
    # W_out``, and which keeps the last ``conv_L_cache - 1`` rows of ``B *
    # X`` a decode slot and nothing else; ``A`` a full-attention layer
    # over the paged pool.  The leading ``first_k_dense`` layers' feed-
    # forward is dense (the published ``num_dense_layers``)
    conv_pattern: str = ""
    conv_L_cache: int = 0
    conv_bias: bool = False
    # what the sigmoid router's chosen scores' sum is kept from zero by
    # (ops/moe.py; LFM2 publishes 1e-6)
    router_norm_eps: float = 1e-20
    # KV heads a row of the paged pool holds side by side (1 | 2).  Set
    # per ENGINE like ``quant_kernel`` (``pack_kv_heads``): a head of 64
    # is half a lane tile, which Mosaic's page DMA refuses and XLA's
    # tiled HBM layout would pad to 128, so two heads share a 128-lane
    # row, ``[layers, KV / 2, pages, page, 128]`` (ops/head_pack.py)
    kv_head_pack: int = 1
    # ---- Mamba-2 beside attention, every layer followed by a dense
    # SwiGLU (Granite 4.0-H; the published ``layer_types``): one letter a
    # layer over the WHOLE stack, ``M`` a layer ``(mamba, mlp)``, ``A`` a
    # layer ``(attn, mlp)``; the Mamba-2 sizes are the ``mamba_*`` above
    mamba_pattern: str = ""
    # ---- Granite's four published multipliers, each applied in ONE
    # place: the embedded rows (models/decoder.py ``_embed``), the
    # softmax scale in place of ``head_dim ** -0.5`` (``_query_scale``;
    # 0 = that default), every sub-block's output at the stack walker's
    # residual add (models/hybrid.py ``_period_scan``), and what the
    # logits are DIVIDED by before any edit and before the log-softmax
    # (``_logits``, ``greedy_head``)
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.n_shared_experts and not self.shared_expert_intermediate_size:
            object.__setattr__(
                self, "shared_expert_intermediate_size",
                self.n_shared_experts * self.expert_width,
            )
        # a preset changed from JSON (perfbench/serve.py overrides)
        # brings lists; the spec is a static jit argument and must hash
        for name in ("extra_stop_ids", "mrope_section"):
            if not isinstance(getattr(self, name), tuple):
                object.__setattr__(self, name, tuple(getattr(self, name)))

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_hybrid(self) -> bool:
        """A stack of sub-blocks of several kinds (models/hybrid.py):
        recurrent layers beside attention ones, a per-slot recurrent
        state beside the paged pool."""
        return self._spelling is not _DENSE

    @property
    def is_dsa(self) -> bool:
        """Attention over the ``index_topk`` cached tokens an indexer
        picks, not over all of them: latent attention by a pattern of
        layers that pick and layers that reuse a pick, or GQA attention
        whose every layer picks (``kv_rows``)."""
        return self._spelling in (_INDEXER, _SELECT)

    @property
    def kv_rows(self) -> bool:
        """GQA attention under a selection: a token's cache in a layer
        is ONE pair of rows, its KV heads' K over their V, ``[2,
        num_kv_heads x head_dim]`` (2,048 B at 4 x 128 in bf16), so that
        a picked token is one fetch (runtime/kv_cache.py
        ``make_kv_buffers``; ops/pallas/dsa.py)."""
        return self._spelling is _SELECT

    @property
    def rows_cache(self) -> bool:
        """The pool holds rows a TOKEN (a latent row, or K over V) and
        no head-major K and V pools: what moves K and V pages by head
        does not know it."""
        return self.is_mla or self.kv_rows

    @property
    def is_mla(self) -> bool:
        """Multi-head latent attention over a latent paged cache."""
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values a token holds in a layer of the latent cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_pools(self) -> int:
        """Rows of ``cache_head_dim`` lanes a token holds a cache head
        and layer: K and V (two arrays; under ``kv_rows`` the two rows
        of ONE array's pairs), or the one latent pool's (the index keys
        of a spec that picks ride beside either, in an array of their
        own shape: ``index_layers`` x ``index_key_lanes``)."""
        return 1 if self.is_mla else 2

    @property
    def cache_heads(self) -> int:
        """Rows a token holds in a layer of a pool: its KV heads, by
        ``kv_head_pack`` to a row."""
        if self.rows_cache:
            return 1
        return self.num_kv_heads // self.kv_head_pack

    @property
    def cache_head_dim(self) -> int:
        """Lanes of a cached row.  The latent row is padded to whole
        128-lane tiles (320 -> 384): XLA's tiled HBM layout and the
        kernel's page DMA hold such a row either way, so the pool and
        ``kv_page_bytes`` count the padded row.  Packed heads fill
        theirs: no padding lanes."""
        if self.is_mla:
            return -(-self.latent_dim // 128) * 128
        if self.kv_rows:  # all KV heads of K (or of V) side by side
            return self.kv_dim
        return self.head_dim * self.kv_head_pack

    @property
    def index_key_lanes(self) -> int:
        """Lanes of an index key's row in the pool.  Over K and V
        (``kv_rows``) whole 128-lane tiles: a key of 64 in 128 (Mosaic
        refuses a 64-lane page, PERF.md section 6, PR 47; the other 64
        hold zeros, which add nothing to a dot product).  The latent
        form's key is 128 wide as published and is held as it is."""
        d = self.index_head_dim
        return -(-d // 128) * 128 if self.kv_rows else d

    @property
    def index_rotary_dim(self) -> int:
        """The FIRST dimensions of an index head that rotate: the latent
        attention's rope width, or the whole head where the attention
        has no such width (``kv_rows``)."""
        return self.qk_rope_head_dim if self.is_mla else self.index_head_dim

    @property
    def kv_heads_pair(self) -> bool:
        """K and V heads of 64 in even number: two fit a 128-lane row."""
        return (not self.rows_cache and self.head_dim == 64
                and self.num_kv_heads % 2 == 0
                # a ring and an open window are laid out by KV head
                and not self.swa_layers and not self.eva_layers)

    def pack_kv_heads(self) -> "ModelSpec":
        """This spec with its pool's rows holding two heads each, where
        they pair (itself where they do not)."""
        return replace(self, kv_head_pack=2) if self.kv_heads_pair else self

    @property
    def mla_softmax_scale(self) -> float:
        """sigma: head^-0.5, times m^2 under YaRN with mscale_all_dim."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn_factor > 1 and self.yarn_mscale_all_dim:
            m = 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1
            scale *= m * m
        return scale

    @property
    def rope_parameters(self) -> dict:
        """The YaRN group as the published config.json spells it (what
        perfbench/serve.py holds the program to)."""
        num = lambda v: int(v) if float(v).is_integer() else v
        if self.yarn_full_only:  # one group a layer type
            return {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": num(self.rope_theta),
                    "factor": num(self.yarn_factor),
                    "original_max_position_embeddings":
                        self.yarn_original_max_pos,
                    "beta_fast": num(self.yarn_beta_fast),
                    "beta_slow": num(self.yarn_beta_slow),
                    "attention_factor": self.yarn_attention_factor,
                },
                "sliding_attention": {
                    "rope_type": "default",
                    "rope_theta": num(self.rope_theta)},
            }
        if self.yarn_factor <= 0:
            return {"rope_theta": num(self.rope_theta),
                    "rope_type": "default"} if self._spelling in (
                        _WINDOW, _INDEXER, _CONV) else {}
        return {
            "beta_fast": num(self.yarn_beta_fast),
            "beta_slow": num(self.yarn_beta_slow),
            "factor": num(self.yarn_factor),
            "llama_4_scaling_beta": self.llama_4_scaling_beta,
            "mscale": num(self.yarn_mscale),
            "mscale_all_dim": num(self.yarn_mscale_all_dim),
            "original_max_position_embeddings": self.yarn_original_max_pos,
            "rope_theta": num(self.rope_theta),
            "rope_type": "yarn",
            "type": "yarn",
        }

    def rotary(self, kind: str) -> Optional["Rotary"]:
        """The rotary of a layer whose mixer is ``kind`` (``swa``: a
        window layer; anything else: the spec's other attention), None
        where it takes no positions: the ONE place that says which
        layers rotate, with which frequencies and at what amplitude."""
        if not self.use_rope or (kind != "swa" and not self.global_rope):
            return None
        if kind == "swa" and self.yarn_full_only:
            return Rotary(self.rope_theta, None, 1.0)
        return Rotary(self.rope_theta, self.rope_scaling,
                      self.yarn_attention_factor or 1.0)

    @property
    def rotary_by_kind(self) -> dict:
        """Each layer type's rotary as served, for a stack whose kinds
        differ in it (``/stats -> engine.rotary``); {} for every other."""
        if self._spelling is not _WINDOW:
            return {}
        def say(r):
            if r is None:
                return {"type": "none"}
            return {"type": r.scaling[0] if r.scaling else "default",
                    "theta": r.theta,
                    "factor": r.scaling[1] if r.scaling else 1.0,
                    "amplitude": r.amplitude}
        return {"sliding_attention": say(self.rotary("swa")),
                "full_attention": say(self.rotary("attn"))}

    @property
    def _spelling(self) -> "_Spelling":
        """How THIS spec states its stack: the one place that looks."""
        if self.layer_pattern:
            return _LETTERS
        if self.conv_pattern:
            return _CONV
        if self.mamba_pattern:
            return _MAMBA
        if self.eva_window:
            return _EVA
        if self.window_pattern:
            return _WINDOW
        if self.indexer_pattern:
            return _INDEXER
        if self.is_mla:
            return _LATENT
        if self.index_topk:
            return _SELECT
        if self.full_attention_interval > 1:
            return _INTERVAL
        return _DENSE

    @cached_property
    def stack(self) -> tuple:
        """The layers of this spec, one entry each (``num_layers`` of
        them, ``first_layer`` applied): the layer's residual sub-blocks'
        kinds in order, of the ten the stack walker knows
        (models/hybrid.py).  Everything below derives from it alone."""
        first = self.first_layer
        mine = self._spelling.parse(self)[first:first + self.num_layers]
        if len(mine) != self.num_layers:
            raise ValueError(f"{self.name}: its stack is stated for "
                             f"{len(mine)} of its {self.num_layers} layers")
        return mine

    @cached_property
    def _cut(self) -> tuple:
        """(leading layers, layers a period).  The walker runs the
        leading layers once and scans the rest, which is whole repeats
        of one unit (itself, where it does not repeat).  Where the
        spelling has leading layers, of the cuts behind the dense ones
        the first that leaves the fewest layers unrolled (the leading
        ones and one period)."""
        def unit(rest):
            return next(u for u in range(1, len(rest) + 1)
                        if len(rest) % u == 0
                        and rest[:u] * (len(rest) // u) == rest)

        dense = sum("mlp" in layer for layer in self.stack)
        leads = range(dense, self.num_layers) if self._spelling.leads else (0,)
        return min(((n, unit(self.stack[n:])) for n in leads), key=sum)

    @property
    def lead_layers(self) -> int:
        """Layers the stack walker runs once, ahead of the scanned
        periods: the leading dense ones, and as many more as leave a
        whole number of periods behind them."""
        return self._cut[0]

    @property
    def layers_per_period(self) -> int:
        return self._cut[1]

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.lead_layers) // self.layers_per_period

    @property
    def lead_blocks(self) -> tuple:
        """The leading layers, each its two sub-blocks' kinds."""
        return self.stack[:self.lead_layers]

    @property
    def period_blocks(self) -> tuple:
        """The residual sub-blocks of one period, in order, each ``(kind,
        group, norm, index)``: what it computes, the parameter group
        that holds its tensors (``layers[group]``, stacked ``[periods,
        layers of the group a period, ...]``; a layer's group goes by
        its first sub-block), the name of its norm weight there and its
        layer's index inside the group's period."""
        groups = self._spelling.groups
        if not groups:  # models/decoder.py's own layers: no walker
            return ()
        out, seen = [], {}
        lead, per = self._cut
        for layer in self.stack[lead:lead + per]:
            group = groups[layer[0]]
            j = seen.get(group, 0)
            seen[group] = j + 1
            norms = ("input_norm", "post_norm") if len(layer) > 1 else (
                "norm",)
            out += [(kind, group, norm, j)
                    for kind, norm in zip(layer, norms)]
        return tuple(out)

    def group_layers(self, group: str) -> int:
        """Layers a period holds in a parameter group."""
        return len({b[3] for b in self.period_blocks if b[1] == group})

    def _layers_of(self, *kinds) -> int:
        return sum(k in kinds for layer in self.stack for k in layer)

    @property
    def attn_layers(self) -> int:
        """Layers that hold pages (K/V, the latent, or EVA's summary
        rows)."""
        return self._layers_of("attn", "mla", "dsa", "eva")

    @property
    def index_layers(self) -> int:
        """Layers that pick: each holds an index key a token in the
        pool's second array."""
        return self._layers_of("dsa")

    @property
    def linear_layers(self) -> int:
        """Layers that hold a recurrent state, of either kind."""
        return self._layers_of("gdn", "mamba")

    @property
    def conv_layers(self) -> int:
        """Gated short-convolution layers: each keeps a convolution
        tail a decode slot, ``[conv_L_cache - 1, hidden]``, and no tile."""
        return self._layers_of("conv")

    @property
    def recurrent_layers(self) -> int:
        """Layers that carry a row of state a slot from token to token:
        a tile and a tail, or a tail alone."""
        return self.linear_layers + self.conv_layers

    @property
    def swa_layers(self) -> int:
        """Window layers whose K/V is the slot's ring, not pages."""
        return self._layers_of("swa")

    @property
    def eva_layers(self) -> int:
        """Layers whose open window is a buffer a decode slot and whose
        pages hold one summary row for every ``eva_chunk`` tokens."""
        return self._layers_of("eva")

    @property
    def cache_row_tokens(self) -> int:
        """Tokens ONE row of the paged pool stands for: what a
        sequence's pages are counted by (runtime/kv_cache.py
        ``KVGeometry.row_tokens``, the scheduler's ``page_tokens``)."""
        return self.eva_chunk if self.eva_layers else 1

    @property
    def slot_state_layers(self) -> int:
        """Layers whose cache is a row a decode SLOT beside the paged
        pool (recurrent state, ring or open window): what pages alone
        cannot move, share or roll back."""
        return self.recurrent_layers + self.swa_layers + self.eva_layers

    @property
    def moe_layers(self) -> int:
        """Expert layers the stack runs a step."""
        return self._layers_of("moe")

    @property
    def recurrent_kind(self) -> str:
        """``gdn`` | ``mamba`` | ``conv`` | "" : the one kind of layer
        that carries a state from token to token (``conv``: a
        convolution tail alone)."""
        kinds = {k for layer in self.stack for k in layer} & {
            "gdn", "mamba", "conv"}
        assert len(kinds) <= 1, "one kind of recurrent state a spec"
        return next(iter(kinds), "")

    @property
    def linear_per_period(self) -> int:
        return self.linear_layers // self.num_periods

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the Mamba-2 convolution runs over: x, B and C."""
        return (self.mamba_inner
                + 2 * self.mamba_n_groups * self.mamba_state_size)

    @property
    def expert_stacks(self) -> tuple:
        """Names of an expert's matrices."""
        return ("gate", "up", "down") if self.moe_gated else ("up", "down")

    @property
    def expert_in(self) -> int:
        """Width the routed experts read and write."""
        return self.moe_latent_size or self.hidden_size

    @property
    def router_experts(self) -> int:
        return self.router_width or self.num_experts

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels the causal convolution runs over: q, k and v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def _kind_params(self) -> dict:
        """Sub-block kind -> the parameters of one such sub-block, its
        norm apart (models/decoder.py init_params, models/hybrid.py
        init_layers)."""
        D, H, hd = self.hidden_size, self.num_heads, self.head_dim
        # GQA attention: q, k, v, o; the output gate beside q; the
        # per-head norms on q and k; the biases of q, k and v
        gqa = 2 * D * self.q_dim + 2 * D * self.kv_dim
        if self.attn_output_gate:
            gqa += D * self.q_dim
        if self.qk_norm:
            gqa += 2 * hd
        if self.qkv_bias:
            gqa += self.q_dim + 2 * self.kv_dim
        # latent attention: q_a, its norm, q_b, kv_a, its norm, kv_b, o
        ql, kl = self.q_lora_rank, self.kv_lora_rank
        nope, vd = self.qk_nope_head_dim, self.v_head_dim
        mla = (D * ql + ql + ql * H * (nope + self.qk_rope_head_dim)
               + D * self.latent_dim + kl + kl * H * (nope + vd)
               + H * vd * D)
        # the indexer: queries from the query latent (from the hidden
        # state where the attention has none), ONE key a token under a
        # LayerNorm, the heads' weights
        Hi, di = self.index_n_heads, self.index_head_dim
        indexer = (ql or D) * Hi * di + D * di + 2 * di + D * Hi
        Hv, lv = self.linear_num_value_heads, self.linear_value_dim
        gdn = (D * (self.linear_conv_dim + lv) + D * 2 * Hv
               + self.linear_conv_dim * self.linear_conv_kernel_dim
               + 2 * Hv + self.linear_value_head_dim + lv * D)
        Hm, C, inner = (self.mamba_num_heads, self.mamba_conv_dim,
                        self.mamba_inner)
        mamba = (D * (inner + C + Hm) + C * self.mamba_conv_kernel
                 + (C if self.mamba_conv_bias else 0) + 3 * Hm + inner
                 + inner * D)
        # the expert layer: the router (a selection bias under sigmoid
        # scores), the held experts' two or three matrices in the width
        # they work in, the projections into and out of a latent, the
        # shared expert and its gate
        W, R, m = self.expert_in, self.router_experts, len(self.expert_stacks)
        Fs = self.shared_expert_intermediate_size
        moe = (D * R + self.num_experts * m * W * self.expert_width
               + m * D * Fs)
        if self.router_scoring == "sigmoid":
            moe += R
        if self.moe_latent_size:
            moe += 2 * D * W
        if Fs and self.shared_expert_gate:
            moe += D
        # EVA attention: q, k, v, o and a head's two learned vectors
        # (the chunk softmax's query and the pooled key's shift)
        eva = 2 * D * self.q_dim + 2 * D * self.kv_dim + 2 * H * hd
        # the gated short convolution: [B | C | X], the taps, the output
        conv = (3 * D * D + D * self.conv_L_cache
                + (D if self.conv_bias else 0) + D * D)
        return {"attn": gqa, "swa": gqa, "mla": mla,
                "dsa": (mla if self.is_mla else gqa) + indexer,
                "eva": eva, "gdn": gdn, "mamba": mamba, "conv": conv,
                "mlp": 3 * D * self.intermediate_size, "moe": moe}

    @property
    def num_params(self) -> int:
        """Analytic parameter count, used for the MFU gauge
        (observability/roofline.py): every sub-block of the stack and
        its norm (two under ``ffn_sandwich``), the embedding, a head of
        its own unless tied, the final norm."""
        D, per = self.hidden_size, self._kind_params()
        norm = 2 * D if self.ffn_sandwich else D
        blocks = sum(per[k] + norm for layer in self.stack for k in layer)
        heads = 0 if self.tie_embeddings else self.num_pred_heads
        tables = (1 + heads) * self.vocab_size * D
        return blocks + tables + D

    # an indexer spec's pattern as the published config.json lists it,
    # whole (what perfbench/serve.py holds the program to)
    @property
    def indexer_types(self) -> list:
        if not self.is_dsa:
            return []
        return ["full" if layer[0] == "dsa" else "shared"
                for layer in self._spelling.parse(self)]

    @property
    def layer_windows(self) -> tuple:
        """Per-layer attention window (0 = global): the stack's window
        layers, or Gemma-2's alternation, a mask only: even-indexed
        layers are sliding-window, odd layers are global (HF
        ``Gemma2Config.layer_types``)."""
        window = max(0, self.sliding_window)
        if self.is_hybrid:
            return tuple(window if "swa" in layer else 0
                         for layer in self.stack)
        return tuple(window if i % 2 == 0 else 0
                     for i in range(self.num_layers))

    # Keye-VL-2.0's groups as its config.json spells them (what
    # perfbench/serve.py holds the program to)
    @property
    def sa_config(self) -> dict:
        if not self.kv_rows:
            return {}
        return {"indexer_head_dim": self.index_head_dim,
                "indexer_num_heads": self.index_n_heads,
                "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                "q_chunk_size": 512, "topk": self.index_topk}

    @property
    def mrope_scaling(self) -> dict:
        if not self.mrope_section:
            return {}
        return {"mrope_section": list(self.mrope_section),
                "rope_type": "default", "type": "default"}

    # the experts held, under the config's second key for them
    @property
    def num_local_experts(self) -> int:
        return self.num_experts

    # a window spec's layers as the published config.json lists them
    # (what perfbench/serve.py holds the program to)
    @property
    def layer_types(self) -> list:
        if self._spelling is _CONV:
            return ["conv" if layer[0] == "conv" else "full_attention"
                    for layer in self.stack]
        if self._spelling is _MAMBA:
            return ["mamba" if layer[0] == "mamba" else "attention"
                    for layer in self.stack]
        return ["sliding_attention" if w else "full_attention"
                for w in self.layer_windows]

    @property
    def multipliers(self) -> dict:
        """The published multipliers that are not the identity, under
        their published names (``/stats -> engine.multipliers``)."""
        out = {"embedding_multiplier": self.embedding_multiplier,
               "residual_multiplier": self.residual_multiplier,
               "logits_scaling": self.logits_scaling}
        out = {k: v for k, v in out.items() if v != 1.0}
        if self.attention_multiplier > 0:
            out["attention_multiplier"] = self.attention_multiplier
        return out

    # Granite's key for "the attention layers take no rotary embedding"
    @property
    def position_embedding_type(self) -> str:
        return "rope" if self.use_rope else "nope"

    # the sigmoid router as LFM2's config.json spells it: a selection
    # bias, the chosen scores renormalised (ops/moe.py does both)
    @property
    def use_expert_bias(self) -> bool:
        return self.router_scoring == "sigmoid"

    @property
    def norm_topk_prob(self) -> bool:
        return self.is_moe

    @property
    def sliding_windows(self) -> list:
        return list(self.layer_windows)

    @property
    def mlp_layer_types(self) -> list:
        if self._spelling is _MAMBA:  # a dense SwiGLU behind every mixer
            return ["dense"] * len(self.stack)
        # an indexer spec states the published stack's
        return ["dense" if i < self.first_k_dense else "sparse"
                for i in range(len(self._spelling.parse(self)))]

    def check_expert_share(self) -> None:
        """The held experts lie inside the router's width."""
        if self.is_moe and (
            self.first_expert < 0
            or self.first_expert + self.num_experts > self.router_experts
        ):
            raise ValueError(
                f"{self.name}: experts {self.first_expert}.."
                f"{self.first_expert + self.num_experts - 1} are not "
                f"inside the router's {self.router_experts}"
            )

    @property
    def rope_scaling(self):
        """Tuple for ops/rope.py (None when scaling is off): Llama-3's
        four numbers, or ``("yarn", factor, beta_fast, beta_slow,
        original_max_pos)``."""
        if self.yarn_factor > 0:
            return ("yarn", self.yarn_factor, self.yarn_beta_fast,
                    self.yarn_beta_slow, self.yarn_original_max_pos)
        if self.rope_scaling_factor <= 0:
            return None
        return (
            self.rope_scaling_factor,
            self.rope_low_freq_factor,
            self.rope_high_freq_factor,
            self.rope_original_max_pos,
        )

    @property
    def uses_local_attention(self) -> bool:
        """True when attention needs window/softcap/scale semantics.  The
        Pallas prefill+decode kernels and ring-attention sp prefill
        implement these natively; the one path that does NOT yet (the
        pipeline-parallel relay) rejects such specs at engine init."""
        return (
            self.sliding_window > 0
            or self.attn_softcap > 0
            or self.query_scale > 0
            or self.attention_multiplier > 0
        )

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


# ---- the spellings of a stack.  A spec states "which layer is what" in
# ONE of these (``ModelSpec._spelling`` chooses); ``ModelSpec.stack`` and
# all that derives from it read the parser's answer and no spelling.


class _Spelling(NamedTuple):
    # spec -> the layers the spelling states, each its sub-blocks' kinds
    # in order: the PUBLISHED stack where the fields hold it whole, of
    # which this spec's layers are ``first_layer ..``
    parse: Callable
    # may the walker run layers ahead of the scanned periods?  Only a
    # spelling whose initialiser draws a tree a leading layer
    # (models/hybrid.py init_layers); the others keep every layer in
    # the periods even where a later cut would unroll fewer
    leads: bool
    # a layer's first sub-block's kind -> the parameter group that holds
    # the layer's tensors (the parameter tree's names)
    groups: dict


def _depth(spec: ModelSpec) -> int:
    return spec.first_layer + spec.num_layers


def _feed_forward(spec: ModelSpec, i: int) -> str:
    return "mlp" if i < spec.first_k_dense else "moe"


def _parse_interval(spec: ModelSpec) -> tuple:
    n = spec.full_attention_interval
    if spec.num_layers % n:
        raise ValueError(
            f"{spec.name}: {spec.num_layers} layers are not whole periods "
            f"of {n - 1} linear-attention layers to one full"
        )
    return tuple(("gdn" if (i + 1) % n else "attn", "moe")
                 for i in range(_depth(spec)))


def _parse_letters(spec: ModelSpec) -> tuple:
    # ONE sub-block a layer; a layer of two (a mixer, then a dense
    # block) is ``_MAMBA``'s or another spelling's below
    kinds = {"M": "mamba", "*": "attn", "E": "moe"}
    return tuple((kinds[c],) for c in spec.layer_pattern)


def _parse_window(spec: ModelSpec) -> tuple:
    pat = spec.window_pattern
    return tuple(("swa" if pat[i % len(pat)] == "L" else "attn",
                  _feed_forward(spec, i)) for i in range(_depth(spec)))


def _parse_indexer(spec: ModelSpec) -> tuple:
    return tuple(("dsa" if c == "F" else "mla", _feed_forward(spec, i))
                 for i, c in enumerate(spec.indexer_pattern))


_INTERVAL = _Spelling(_parse_interval, False,
                      {"gdn": "linear", "attn": "full"})
_LETTERS = _Spelling(_parse_letters, False,
                     {"mamba": "mamba", "attn": "attn", "moe": "moe"})
_WINDOW = _Spelling(_parse_window, True, {"swa": "window", "attn": "global"})
_INDEXER = _Spelling(_parse_indexer, True, {"dsa": "pick", "mla": "reuse"})
# gated short convolutions beside full attention, by letter
_CONV = _Spelling(
    lambda spec: tuple(("conv" if c == "C" else "attn",
                        _feed_forward(spec, i))
                       for i, c in enumerate(spec.conv_pattern)),
    True, {"conv": "conv", "attn": "attn"})
# Mamba-2 beside attention by letter, a dense SwiGLU behind each: the
# groups go by the layer's first sub-block and hold its feed-forward too
_MAMBA = _Spelling(
    lambda spec: tuple(("mamba" if c == "M" else "attn", "mlp")
                       for c in spec.mamba_pattern),
    False, {"mamba": "mamba", "attn": "attn"})
# GQA attention under a selection of every layer's own (``kv_rows``)
_SELECT = _Spelling(lambda spec: (("dsa", "moe"),) * _depth(spec), False,
                    {"dsa": "layer"})
# latent attention without an indexer: every layer alike
_LATENT = _Spelling(lambda spec: (("mla", "moe"),) * _depth(spec), False,
                    {"mla": "layer"})
# EVA attention: every layer alike, its feed-forward a dense SwiGLU
_EVA = _Spelling(lambda spec: (("eva", "mlp"),) * _depth(spec), False,
                 {"eva": "layer"})
# models/decoder.py's own layers: a stack for counting only
_DENSE = _Spelling(
    lambda spec: (("attn", "moe" if spec.is_moe else "mlp"),) * _depth(spec),
    False, {})


# Dims follow the published HF configs for each model id.
_PRESETS: Dict[str, ModelSpec] = {}


def _register(spec: ModelSpec) -> ModelSpec:
    _PRESETS[spec.name.lower()] = spec
    return spec


QWEN25_05B = _register(
    ModelSpec(
        name="Qwen/Qwen2.5-0.5B-Instruct",
        extra_stop_ids=(151643,),  # <|endoftext|>
        vocab_size=151936,
        hidden_size=896,
        num_layers=24,
        num_heads=14,
        num_kv_heads=2,
        head_dim=64,
        intermediate_size=4864,
        tie_embeddings=True,
    )
)

QWEN25_15B = _register(
    ModelSpec(
        name="Qwen/Qwen2.5-1.5B-Instruct",
        extra_stop_ids=(151643,),  # <|endoftext|>
        vocab_size=151936,
        hidden_size=1536,
        num_layers=28,
        num_heads=12,
        num_kv_heads=2,
        head_dim=128,
        intermediate_size=8960,
        tie_embeddings=True,
    )
)

QWEN25_7B = _register(
    ModelSpec(
        name="Qwen/Qwen2.5-7B-Instruct",
        extra_stop_ids=(151643,),  # <|endoftext|>
        vocab_size=152064,
        hidden_size=3584,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=18944,
        tie_embeddings=False,
    )
)

MIXTRAL_8X7B = _register(
    ModelSpec(
        name="mistralai/Mixtral-8x7B-Instruct-v0.1",
        vocab_size=32000,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        eos_token_id=2,
        bos_token_id=1,
        num_experts=8,
        experts_per_token=2,
    )
)

LLAMA3_8B = _register(
    ModelSpec(
        name="meta-llama/Meta-Llama-3-8B-Instruct",
        vocab_size=128256,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=500_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=128009,
        bos_token_id=128000,
        extra_stop_ids=(128001,),  # <|end_of_text|>
        max_position_embeddings=8192,
    )
)

LLAMA31_8B = _register(
    ModelSpec(
        name="meta-llama/Llama-3.1-8B-Instruct",
        vocab_size=128256,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=500_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=128009,
        bos_token_id=128000,
        extra_stop_ids=(128001, 128008),  # <|end_of_text|>, <|eom_id|>
        max_position_embeddings=131072,
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_pos=8192,
    )
)

LLAMA32_1B = _register(
    ModelSpec(
        name="meta-llama/Llama-3.2-1B-Instruct",
        vocab_size=128256,
        hidden_size=2048,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        intermediate_size=8192,
        rope_theta=500_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=128009,
        bos_token_id=128000,
        extra_stop_ids=(128001, 128008),  # <|end_of_text|>, <|eom_id|>
        max_position_embeddings=131072,
        rope_scaling_factor=32.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_pos=8192,
    )
)

MISTRAL_7B = _register(
    ModelSpec(
        name="mistralai/Mistral-7B-Instruct-v0.3",
        vocab_size=32768,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=2,
        bos_token_id=1,
    )
)

GEMMA2_2B = _register(
    ModelSpec(
        name="google/gemma-2-2b-it",
        vocab_size=256000,
        hidden_size=2304,
        num_layers=26,
        num_heads=8,
        num_kv_heads=4,
        head_dim=256,
        intermediate_size=9216,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=107,  # <end_of_turn> — the -it turn-end token
        bos_token_id=2,
        extra_stop_ids=(1,),  # <eos>
        max_position_embeddings=8192,
        act="gelu_tanh",
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=4096,
        query_scale=256.0,
        embed_scale=True,
        unit_offset_norm=True,
        ffn_sandwich=True,
    )
)

GEMMA2_9B = _register(
    ModelSpec(
        name="google/gemma-2-9b-it",
        vocab_size=256000,
        hidden_size=3584,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        intermediate_size=14336,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=107,  # <end_of_turn> — the -it turn-end token
        bos_token_id=2,
        extra_stop_ids=(1,),  # <eos>
        max_position_embeddings=8192,
        act="gelu_tanh",
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=4096,
        query_scale=256.0,
        embed_scale=True,
        unit_offset_norm=True,
        ffn_sandwich=True,
    )
)

QWEN3_NEXT_80B = _register(
    ModelSpec(
        name="Qwen/Qwen3-Next-80B-A3B-Instruct",
        extra_stop_ids=(151643,),  # <|endoftext|>
        vocab_size=151936,
        hidden_size=2048,
        num_layers=48,
        num_heads=16,
        num_kv_heads=2,
        head_dim=256,
        intermediate_size=5120,  # published; no layer is dense
        rope_theta=10_000_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        max_position_embeddings=262144,
        unit_offset_norm=True,
        num_experts=512,
        experts_per_token=10,
        moe_intermediate_size=512,
        shared_expert_intermediate_size=512,
        router_width=512,
        qk_norm=True,
        attn_output_gate=True,
        partial_rotary_factor=0.25,
        full_attention_interval=4,
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
    )
)

# Published sizes.  The 88 layers' pattern repeats nowhere (runs of 7, 8,
# 8, 10, 10, 10, 10, 8 and 9 layers between attention layers), so it is
# ONE period whose repeated pairs the stack walker scans
# (models/hybrid.py); a cut states its own pattern.  Stop ids: the
# catalog row's config has none (assumed: <|im_end|> 11, <s> 1, </s> 2).
NEMOTRON3_SUPER_120B = _register(
    ModelSpec(
        name="nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16",
        vocab_size=131072,
        hidden_size=4096,
        num_layers=88,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        intermediate_size=2688,
        rope_theta=10_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=11,
        bos_token_id=1,
        extra_stop_ids=(2,),
        max_position_embeddings=262144,
        act="relu2",
        num_experts=512,
        experts_per_token=22,
        moe_intermediate_size=2688,
        shared_expert_intermediate_size=5376,
        router_width=512,
        layer_pattern=(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
        ),
        use_rope=False,
        mamba_num_heads=128,
        mamba_head_dim=64,
        mamba_state_size=128,
        mamba_n_groups=8,
        mamba_conv_kernel=4,
        mamba_conv_bias=True,
        mamba_chunk_size=128,
        moe_latent_size=1024,
        router_scoring="sigmoid",
        routed_scaling_factor=5.0,
        moe_gated=False,
        shared_expert_gate=False,
    )
)

# Published sizes (config.json, model_type mistral4; the vision encoder
# is not part of this spec).  Stop ids: the catalog row's config has
# none (assumed: </s> 2, <s> 1).
MISTRAL_SMALL4_119B = _register(
    ModelSpec(
        name="mistralai/Mistral-Small-4-119B-2603",
        vocab_size=131072,
        hidden_size=4096,
        num_layers=36,
        num_heads=32,
        num_kv_heads=32,
        head_dim=128,
        intermediate_size=12288,  # published; no layer is dense
        rope_theta=10_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=2,
        bos_token_id=1,
        max_position_embeddings=1048576,
        num_experts=128,
        experts_per_token=4,
        moe_intermediate_size=2048,
        router_width=128,
        shared_expert_gate=False,
        n_shared_experts=1,
        q_lora_rank=1024,
        kv_lora_rank=256,
        qk_nope_head_dim=64,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_interleave=True,
        yarn_factor=128.0,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_original_max_pos=8192,
        yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0,
        llama_4_scaling_beta=0.1,
    )
)

# Published sizes (config.json, model_type exaone_moe; the multi-token-
# prediction module is not part of this spec).  Stop ids: the catalog
# row's config has none (assumed: 2 / 1).  Not in the config and assumed
# (the cut's configuration file says from what): pre-norm residual
# sub-blocks, per-head RMSNorm on q and k, rotary on the window layers
# only, a selection bias on the router's sigmoid scores
K_EXAONE_236B = _register(
    ModelSpec(
        name="LGAI-EXAONE/K-EXAONE-236B-A23B",
        vocab_size=153600,
        hidden_size=6144,
        num_layers=48,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=18432,  # the leading dense layer's
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=2,
        bos_token_id=1,
        max_position_embeddings=262144,
        num_experts=128,
        experts_per_token=8,
        moe_intermediate_size=2048,
        router_width=128,
        shared_expert_gate=False,
        n_shared_experts=1,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        qk_norm=True,
        sliding_window=128,
        window_pattern="LLLG",
        global_rope=False,
        first_k_dense=1,
    )
)

# Mellum2-12B-A2.5B-Instruct (JetBrains, model_type mellum) at the
# published sizes: 28 layers, three window layers (1,024 tokens, a ring
# a slot) to one full layer, GQA 32 query heads on 4 KV heads of 128,
# EVERY layer's feed-forward 64 softmax-routed experts of 896 top 8 with
# no shared expert (``intermediate_size`` names no matrix).  The rotary
# is the layer kind's own (``rope_parameters`` by layer type): plain
# frequencies on the window layers, YaRN (factor 16 over 8,192) on the
# full layers with cos and sin times ``attention_factor``.  ASSUMED (the
# cut's configuration file says from what): pre-norm sub-blocks, per-head
# RMSNorm on q and k (the Qwen3-MoE lineage's), stop ids 2 / 1; the
# multi-token-prediction head is not part of this spec
MELLUM2_12B = _register(
    ModelSpec(
        name="JetBrains/Mellum2-12B-A2.5B-Instruct",
        vocab_size=98304,
        hidden_size=2304,
        num_layers=28,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=7168,  # the published key; read by nothing
        rope_theta=500_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=2,
        bos_token_id=1,
        max_position_embeddings=131072,
        num_experts=64,
        experts_per_token=8,
        moe_intermediate_size=896,
        router_width=64,
        qk_norm=True,
        sliding_window=1024,
        window_pattern="LLLG",
        yarn_factor=16.0,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_original_max_pos=8192,
        yarn_full_only=True,
        yarn_attention_factor=1.2772588722239782,  # 0.1 ln 16 + 1
    )
)

BGE_BASE = _register(
    ModelSpec(
        name="BAAI/bge-base-en-v1.5",
        vocab_size=30522,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        head_dim=64,
        intermediate_size=3072,
        is_encoder=True,
        qkv_bias=True,
        eos_token_id=102,
        bos_token_id=101,
        max_position_embeddings=512,
    )
)

# Tiny variants for CPU tests and compile checks.
TINY_DENSE = _register(
    ModelSpec(
        name="tiny-dense",
        vocab_size=512,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        eos_token_id=0,
        bos_token_id=1,
        tie_embeddings=False,
    )
)

TINY_MOE = _register(
    replace(
        TINY_DENSE,
        name="tiny-moe",
        num_experts=4,
        experts_per_token=2,
        qkv_bias=False,  # mixtral-family attention has no qkv bias
        rms_eps=1e-5,
    )
)

TINY_GEMMA2 = _register(
    ModelSpec(
        name="tiny-gemma2",
        vocab_size=512,
        hidden_size=64,
        num_layers=2,  # layer 0 sliding, layer 1 global
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,  # q_dim 128 != hidden 64: exercises decoupled head_dim
        intermediate_size=128,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=0,
        bos_token_id=1,
        act="gelu_tanh",
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=8,
        query_scale=16.0,  # != head_dim: exercises the custom q scale
        embed_scale=True,
        unit_offset_norm=True,
        ffn_sandwich=True,
    )
)

# one period of the hybrid stack (three Gated DeltaNet layers, one gated
# full-attention layer), every mechanism of Qwen3-Next at toy widths
TINY_HYBRID = _register(
    ModelSpec(
        name="tiny-hybrid",
        vocab_size=512,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        unit_offset_norm=True,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=32,
        router_width=8,
        qk_norm=True,
        attn_output_gate=True,
        partial_rotary_factor=0.25,
        full_attention_interval=4,
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
    )
)

# one period of a stack whose layers are ONE sub-block each, every
# mechanism of Nemotron-H at toy widths: Mamba-2, attention without
# rotary, a sigmoid-routed expert layer in a latent
TINY_NEMOTRON_H = _register(
    ModelSpec(
        name="tiny-nemotron-h",
        vocab_size=512,
        hidden_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=48,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        act="relu2",
        num_experts=8,
        experts_per_token=3,
        moe_intermediate_size=48,
        shared_expert_intermediate_size=96,
        router_width=8,
        layer_pattern="EMEM*",
        use_rope=False,
        mamba_num_heads=4,
        mamba_head_dim=16,
        mamba_state_size=16,
        mamba_n_groups=2,
        mamba_conv_kernel=4,
        mamba_conv_bias=True,
        mamba_chunk_size=16,
        moe_latent_size=32,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        moe_gated=False,
        shared_expert_gate=False,
    )
)

# every mechanism of Mistral-Small-4 at toy widths: latent attention
# over a latent cache, YaRN over an original maximum of 32 (so that a
# CPU test's contexts pass it and the queries' position scaling leaves
# 1), a softmax-routed expert layer with an ungated shared expert
TINY_MLA_MOE = _register(
    ModelSpec(
        name="tiny-mla-moe",
        vocab_size=512,
        hidden_size=64,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=48,
        router_width=8,
        shared_expert_gate=False,
        n_shared_experts=1,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_interleave=True,
        yarn_factor=4.0,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_original_max_pos=32,
        yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0,
        llama_4_scaling_beta=0.1,
    )
)

# every mechanism of K-EXAONE at toy widths: a leading dense layer, then
# two periods of three window layers (8 tokens) to one full layer, the
# window layers' K/V a per-slot ring (at page size 4 it wraps within
# tens of tokens), a sigmoid-routed expert layer with a shared expert
TINY_SWA_MOE = _register(
    ModelSpec(
        name="tiny-swa-moe",
        vocab_size=512,
        hidden_size=64,
        num_layers=9,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        router_width=8,
        shared_expert_gate=False,
        n_shared_experts=1,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        qk_norm=True,
        sliding_window=8,
        window_pattern="LLLG",
        global_rope=False,
        first_k_dense=1,
    )
)

# every mechanism of Mellum2 at toy widths: two periods of three window
# layers (8 tokens, a ring a slot) to one full layer, no leading layer,
# 8 softmax-routed experts top 2 and no shared expert, YaRN (factor 4
# over an original 32, amplitude 0.1 ln 4 + 1) on the full layers alone
TINY_MELLUM = _register(
    ModelSpec(
        name="tiny-mellum",
        vocab_size=512,
        hidden_size=64,
        num_layers=8,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        router_width=8,
        qk_norm=True,
        sliding_window=8,
        window_pattern="LLLG",
        yarn_factor=4.0,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_original_max_pos=32,
        yarn_full_only=True,
        yarn_attention_factor=1.1386294361119891,  # 0.1 ln 4 + 1
    )
)

# GLM-5.2 (zai-org, model_type glm_moe_dsa) at the published sizes: 78
# layers of latent attention under a learned selection (an indexer in
# layers 0-2 and every fourth from 6 on picks 2,048 cached tokens a
# query, the three layers after it reuse the pick), three leading dense
# layers, then 256 sigmoid-routed experts top 8 with one ungated shared
# expert.  The multi-token-prediction module is not part of the stack
_GLM52_INDEXER = "FFF" + "SSSF" * 18 + "SSS"
GLM_5_2 = _register(
    ModelSpec(
        name="zai-org/GLM-5.2",
        vocab_size=154880,
        hidden_size=6144,
        num_layers=78,
        num_heads=64,
        num_kv_heads=64,
        head_dim=192,  # the published key's value (= qk_nope_head_dim)
        intermediate_size=12288,
        rope_theta=8_000_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=154820,
        bos_token_id=154822,
        max_position_embeddings=1048576,
        num_experts=256,
        experts_per_token=8,
        moe_intermediate_size=2048,
        router_width=256,
        shared_expert_gate=False,
        n_shared_experts=1,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        q_lora_rank=2048,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
        rope_interleave=True,
        first_k_dense=3,
        indexer_pattern=_GLM52_INDEXER,
        index_topk=2048,
        index_n_heads=32,
        index_head_dim=128,
        indexer_rope_interleave=True,
    )
)

# every mechanism of GLM-5.2 at toy widths: a leading dense layer whose
# indexer picks, then two periods of three layers that reuse a pick to
# one that picks; 16 tokens picked, so that a CPU test's contexts pass
# the count within a few pages of 8
TINY_DSA_MOE = _register(
    ModelSpec(
        name="tiny-dsa-moe",
        vocab_size=512,
        hidden_size=64,
        num_layers=9,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        router_width=8,
        shared_expert_gate=False,
        n_shared_experts=1,
        router_scoring="sigmoid",
        routed_scaling_factor=2.5,
        q_lora_rank=24,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=24,
        rope_interleave=True,
        first_k_dense=1,
        indexer_pattern="F" + "SSSF" * 2,
        index_topk=16,
        index_n_heads=4,
        index_head_dim=16,
        indexer_rope_interleave=True,
    )
)

# Keye-VL-2.0-30B-A3B's LANGUAGE model (Kwai-Keye, model_type KeyeVL2) at
# the published sizes: 48 layers alike, GQA 32 query heads on 4 KV heads
# of 128 with per-head norms on q and k (ASSUMED: the Qwen3 family's),
# every layer under a selection of its own (``sa_config``: 16 index heads
# of 64 against ONE key a token pick 2,048), then 128 softmax-routed
# experts of 768 top 8, no shared expert.  The vision tower is not part
# of the stack (its sizes are not in the repository)
KEYE_VL2_30B = _register(
    ModelSpec(
        name="Kwai-Keye/Keye-VL-2.0-30B-A3B",
        vocab_size=151936,
        hidden_size=2048,
        num_layers=48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=6144,  # the published key; read by nothing
        rope_theta=10_000_000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        max_position_embeddings=262144,
        num_experts=128,
        experts_per_token=8,
        moe_intermediate_size=768,
        router_width=128,
        qk_norm=True,
        index_topk=2048,
        index_n_heads=16,
        index_head_dim=64,
        mrope_section=(16, 24, 24),
    )
)

# the same mechanisms at toy widths: 4 query heads on 2 KV heads of 16,
# 16 tokens picked by 4 index heads of 8 in each of 4 layers, 8 experts
# top 2, so that a CPU test's contexts pass the pick within a few pages
TINY_KEYE_DSA = _register(
    ModelSpec(
        name="tiny-keye-dsa",
        vocab_size=512,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-6,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        router_width=8,
        qk_norm=True,
        index_topk=16,
        index_n_heads=4,
        index_head_dim=8,
        mrope_section=(2, 3, 3),
    )
)

# EvaByte 6.5B (model_type evabyte): a byte-level model, 32 layers of
# EVA attention (32 heads of 128, MHA; windows of 2,048 tokens held
# exactly, chunks of 16 summarised once their window has closed) and a
# SwiGLU of 11,008, RMSNorm with weight 1 + w, a float32 residual
# stream, an untied output layer of 8 heads x 320 bytes
EVABYTE = _register(
    ModelSpec(
        name="EvaByte/EvaByte",
        vocab_size=320,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=32,
        head_dim=128,
        intermediate_size=11008,
        rope_theta=100_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=2,
        bos_token_id=1,
        max_position_embeddings=32768,
        unit_offset_norm=True,
        eva_window=2048,
        eva_chunk=16,
        num_pred_heads=8,
        fp32_residual=True,
    )
)

# every mechanism of EvaByte at toy widths: windows of 32 tokens and
# chunks of 4 (8 summary rows a window: two pages of 4), so that a CPU
# test's contexts close several windows
TINY_EVA = _register(
    ModelSpec(
        name="tiny-eva",
        vocab_size=320,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=False,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        unit_offset_norm=True,
        eva_window=32,
        eva_chunk=4,
        num_pred_heads=8,
        fp32_residual=True,
    )
)

# LFM2-24B-A2B (LiquidAI, model_type lfm2_moe) at the published sizes: 40
# layers, 30 gated short convolutions of 3 taps and 10 GQA attention
# layers (32 heads on 8 KV heads of 64, per-head norms on q and k before
# the rotation), two leading dense layers, then 64 sigmoid-routed experts
# top 4 of width 1,536 with a selection bias and no shared expert.
# Assumed (the row's config has no key for them; the cut's configuration
# file says from what): tied embeddings, the stop ids
_LFM2_LAYERS = "CCA" + "CCCA" * 9 + "C"
LFM2_24B_A2B = _register(
    ModelSpec(
        name="LiquidAI/LFM2-24B-A2B",
        vocab_size=65536,
        hidden_size=2048,
        num_layers=40,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        intermediate_size=11776,
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=7,
        bos_token_id=1,
        max_position_embeddings=128000,
        num_experts=64,
        experts_per_token=4,
        moe_intermediate_size=1536,
        router_width=64,
        router_scoring="sigmoid",
        routed_scaling_factor=1.0,
        router_norm_eps=1e-6,
        qk_norm=True,
        first_k_dense=2,
        conv_pattern=_LFM2_LAYERS,
        conv_L_cache=3,
    )
)

# every mechanism of LFM2-24B-A2B at toy widths: the first 12 published
# layer types (4 leading layers, the first two dense, and two periods
# ``conv conv attn conv``)
TINY_LFM2_MOE = _register(
    ModelSpec(
        name="tiny-lfm2-moe",
        vocab_size=512,
        hidden_size=64,
        num_layers=12,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        num_experts=8,
        experts_per_token=2,
        moe_intermediate_size=32,
        router_width=8,
        router_scoring="sigmoid",
        routed_scaling_factor=1.0,
        router_norm_eps=1e-6,
        qk_norm=True,
        first_k_dense=2,
        conv_pattern=_LFM2_LAYERS[:12],
        conv_L_cache=3,
    )
)

# Granite 4.0-H Micro (ibm-granite, model_type granitemoehybrid) at the
# published sizes, nothing cut: 40 layers, 36 Mamba-2 (64 heads of 64,
# ONE B/C group of 128 for all of them) and 4 GQA attention layers without
# rotary embedding (32 heads on 8 KV heads of 64) at 5, 15, 25, 35, each
# followed by a dense SwiGLU of 8,192 (``num_local_experts`` 0: the
# feed-forward is ``shared_mlp`` alone), a tied head, and the four
# multipliers.  Assumed (the row's config has no key for them): the stop
# ids
_GRANITE_LAYERS = ("MMMMMA" + "MMMM") + "MMMMMAMMMM" * 3
GRANITE_4_H_MICRO = _register(
    ModelSpec(
        name="ibm-granite/granite-4.0-h-micro",
        vocab_size=100352,
        hidden_size=2048,
        num_layers=40,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        intermediate_size=8192,
        rope_theta=10_000.0,  # published, unused: position is "nope"
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=100257,
        bos_token_id=100257,
        max_position_embeddings=131072,
        use_rope=False,
        mamba_pattern=_GRANITE_LAYERS,
        mamba_num_heads=64,
        mamba_head_dim=64,
        mamba_state_size=128,
        mamba_n_groups=1,
        mamba_conv_kernel=4,
        mamba_conv_bias=True,
        mamba_chunk_size=256,
        embedding_multiplier=12.0,
        attention_multiplier=0.015625,
        residual_multiplier=0.22,
        logits_scaling=8.0,
    )
)

# every mechanism of Granite 4.0-H at toy widths: one period with both
# kinds of layer (three Mamba-2 layers in a row, so that the walker's
# inner scan runs), ONE B/C group for the four Mamba-2 heads, all four
# multipliers at values other than 1 (and the softmax scale other than
# ``head_dim ** -0.5`` = 0.25)
TINY_GRANITE_HYBRID = _register(
    ModelSpec(
        name="tiny-granite-hybrid",
        vocab_size=512,
        hidden_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        qkv_bias=False,
        tie_embeddings=True,
        eos_token_id=0,
        bos_token_id=1,
        max_position_embeddings=4096,
        use_rope=False,
        mamba_pattern="MMMAM",
        mamba_num_heads=4,
        mamba_head_dim=16,
        mamba_state_size=16,
        mamba_n_groups=1,
        mamba_conv_kernel=4,
        mamba_conv_bias=True,
        mamba_chunk_size=16,
        embedding_multiplier=6.0,
        attention_multiplier=0.5,
        residual_multiplier=0.5,
        logits_scaling=4.0,
    )
)

TINY_ENCODER = _register(
    replace(
        TINY_DENSE,
        name="tiny-encoder",
        is_encoder=True,
        num_kv_heads=4,
        max_position_embeddings=512,
    )
)


def spec_for_model_id(model_id: str) -> ModelSpec:
    key = model_id.lower()
    if key in _PRESETS:
        return _PRESETS[key]
    # Allow bare names ("qwen2.5-1.5b-instruct") without the org prefix.
    for name, spec in _PRESETS.items():
        if name.split("/")[-1] == key:
            return spec
    raise KeyError(
        f"no architecture preset for {model_id!r}; known: "
        f"{sorted(_PRESETS)}"
    )
