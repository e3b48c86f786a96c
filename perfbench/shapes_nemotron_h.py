"""Shape arithmetic for Nemotron-H (every layer ONE sub-block: a Mamba-2
state-space layer, a GQA attention layer or a LatentMoE expert layer,
by ``hybrid_override_pattern``): the paged cache's shape, and what the
two new kernels HAVE to move and compute, for the roofline reducers.
``cfg`` is the configuration file (or, in a rehearsal,
``rehearse.model``): the sizes held here, so ``n_routed_experts`` is the
experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def _layers(cfg: Dict[str, Any], letter: str) -> int:
    return cfg["hybrid_override_pattern"].count(letter)


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold K/V pages and launch the paged decode kernel."""
    return _layers(cfg, "*")


def linear_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold a recurrent state and launch its step kernel."""
    return _layers(cfg, "M")


def moe_layers(cfg: Dict[str, Any]) -> int:
    return _layers(cfg, "E")


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V in the
    attention layers only."""
    return (2 * attn_layers(cfg) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * DTYPE_BYTES[dtype])


def state_bytes_per_slot_layer(cfg: Dict[str, Any]) -> int:
    """Bytes of one slot's float32 state in one Mamba-2 layer: what a
    decode step reads once and writes once for it."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"] * 4)


def expert_launches_per_layer(cfg: Dict[str, Any]) -> int:
    """Grouped products an expert layer launches: up and down."""
    return 2


def held_expert_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE held expert's two matrices, in the latent."""
    return (2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
            * DTYPE_BYTES[dtype])


def held_expert_bytes_per_layer(cfg: Dict[str, Any],
                                dtype: str = "bfloat16") -> int:
    """Bytes of all the experts one layer holds here."""
    return cfg["n_routed_experts"] * held_expert_bytes(cfg, dtype)


def expert_flops_per_assignment(cfg: Dict[str, Any]) -> int:
    """Floating-point operations one (token, choice) pair costs in its
    expert: two products of latent x expert width, 2 a multiply-add."""
    return 2 * 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
