"""Shape arithmetic for Mistral-Small-4 (every layer: multi-head latent
attention, then an expert layer with a shared expert): the latent paged
cache's shape, and what the latent decode kernel, the prompt pass's
attention and the grouped expert product HAVE to move and compute, for
the roofline reducers.  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``): the sizes held here, so
``n_routed_experts`` is the experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES

LANES = 128  # a pool row is whole 128-lane tiles


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold latent pages and launch the latent decode
    kernel: every layer."""
    return cfg["num_hidden_layers"]


def moe_layers(cfg: Dict[str, Any]) -> int:
    """``first_k_dense_replace`` 0: every layer has experts."""
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def latent_values(cfg: Dict[str, Any]) -> int:
    """Values the mathematics caches for a token in a layer: the normed
    latent and the ONE rotated key (256 + 64 = 320)."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of a token's row in one layer of the pool AS HELD: the 320
    values padded to whole 128-lane tiles (384), which is what the
    decode kernel's page DMA moves for it."""
    lanes = -(-latent_values(cfg) // LANES) * LANES
    return lanes * DTYPE_BYTES[dtype]


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the paged cache over all
    layers: ONE latent row a layer, no K and no V."""
    return attn_layers(cfg) * latent_row_bytes(cfg, dtype)


def mla_decode_flops_per_token_read(cfg: Dict[str, Any]) -> int:
    """Operations the absorbed decode step HAS to make for one cached
    token in one layer: every head's score over the 320 values and its
    value sum over the first 256, 2 a multiply-add (the padding lanes'
    products are the kernel's cost, not the mathematics')."""
    return cfg["num_attention_heads"] * 2 * (
        latent_values(cfg) + cfg["kv_lora_rank"])


def mla_prefill_flops_per_pair(cfg: Dict[str, Any]) -> int:
    """Operations of one (query, key) pair at or under the diagonal in
    one layer of the non-absorbed prompt pass: every head's score over
    nope + rope and its value sum over v, 2 a multiply-add."""
    return cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def expert_launches_per_layer(cfg: Dict[str, Any]) -> int:
    """Grouped products an expert layer launches: gate, up and down."""
    return 3


def held_expert_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE held expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * DTYPE_BYTES[dtype])


def held_expert_bytes_per_layer(cfg: Dict[str, Any],
                                dtype: str = "bfloat16") -> int:
    return cfg["n_routed_experts"] * held_expert_bytes(cfg, dtype)


def expert_flops_per_assignment(cfg: Dict[str, Any]) -> int:
    """Operations one (token, choice) pair costs in its expert: three
    products of hidden x expert width, 2 a multiply-add."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
