"""Shape arithmetic for Mellum2 (window and full attention layers in one
stack, every layer followed by an expert layer with no shared expert): a
cache of TWO geometries, as K-EXAONE's (``shapes_exaone_moe``, whose
per-row and per-expert counts these are), with the layers' kinds read
from the published ``layer_types``.  The paged pool holds the FULL
layers' K and V alone (``kv_bytes_per_token``, ``attn_layers``); a window
layer's K and V is a per-slot RING of ``ring_tokens`` rows
(``ring_bytes_per_slot``).  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``).
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes_exaone_moe import (  # noqa: F401  (the reducers ask by name)
    expert_flops_per_assignment,
    expert_launches_per_layer,
    held_expert_bytes,
    held_expert_bytes_per_layer,
    ring_row_bytes,
    ring_tokens,
    swa_decode_flops_per_row_read,
    swa_prefill_flops_per_pair,
    swa_prefill_pairs,
)


def swa_layers(cfg: Dict[str, Any]) -> int:
    """Window layers: a ring a slot, a launch of their own a step."""
    return cfg["layer_types"].count("sliding_attention")


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold pages and launch the paged decode kernel: the
    full-attention ones."""
    return cfg["layer_types"].count("full_attention")


def moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["mlp_layer_types"].count("sparse")


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V in the
    full layers alone (a window layer holds no page a token)."""
    return attn_layers(cfg) * ring_row_bytes(cfg, dtype)


def ring_bytes_per_slot(cfg: Dict[str, Any], page_tokens: int,
                        dtype: str = "bfloat16") -> int:
    """Bytes the rings of all window layers hold a decode slot, whatever
    the context's length."""
    return (swa_layers(cfg) * ring_tokens(cfg, page_tokens)
            * ring_row_bytes(cfg, dtype))


def params(cfg: Dict[str, Any]) -> int:
    """Parameters of the stack as held: q, k, v, o, the per-head norms,
    the router and every expert a layer, a layer's two norms, embedding,
    head and the final norm."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    layer = (2 * D * q + 2 * D * kv + 2 * hd + D * cfg["num_experts"]
             + cfg["num_experts"] * 3 * D * cfg["moe_intermediate_size"]
             + 2 * D)
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * D + D
