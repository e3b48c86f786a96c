"""Shape arithmetic for Qwen3-Next (three Gated DeltaNet layers to one
gated full-attention layer, an expert layer behind each): the paged
cache's shape, and what the two new kernels HAVE to move and compute,
for the roofline reducers.  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``): the sizes held here, so ``num_experts``
is the experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold K/V pages and launch the paged decode kernel:
    one a period."""
    return cfg["num_hidden_layers"] // cfg["full_attention_interval"]


def linear_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold a recurrent state and launch its step kernel."""
    return cfg["num_hidden_layers"] - attn_layers(cfg)


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V in the
    full-attention layers only."""
    return (2 * attn_layers(cfg) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * DTYPE_BYTES[dtype])


def state_bytes_per_slot_layer(cfg: Dict[str, Any]) -> int:
    """Bytes of one slot's float32 state in one linear layer: what a
    decode step reads once and writes once for it."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 4)


def held_expert_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE held expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * DTYPE_BYTES[dtype])


def held_expert_bytes_per_layer(cfg: Dict[str, Any],
                                dtype: str = "bfloat16") -> int:
    """Bytes of all the experts one layer holds here."""
    return cfg["num_experts"] * held_expert_bytes(cfg, dtype)


def expert_flops_per_assignment(cfg: Dict[str, Any]) -> int:
    """Floating-point operations one (token, choice) pair costs in its
    expert: three products of hidden x expert width, 2 a multiply-add."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
