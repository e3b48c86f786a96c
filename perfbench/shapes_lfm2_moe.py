"""Shape arithmetic for LFM2-24B-A2B (gated short convolutions beside
grouped-query attention at head size 64, two leading dense layers, then
an expert layer a layer): the paged cache's shape (the attention layers'
K and V alone, two heads of 64 side by side in a 128-lane row: no
padding lanes, so a token is ``2 x KV x 64`` values a layer), what a
slot keeps beside it (a convolution tail a ``conv`` layer, no tile),
and what the conv step and the grouped expert product HAVE to move and
compute, for the roofline reducers.  ``cfg`` is the configuration file
(or, in a rehearsal, ``rehearse.model``): the sizes held here, so
``num_experts`` is the experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def _head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"])


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold pages and launch the paged decode kernel."""
    return sum(t == "full_attention" for t in cfg["layer_types"])


def conv_layers(cfg: Dict[str, Any]) -> int:
    """Layers that keep a convolution tail a slot."""
    return sum(t == "conv" for t in cfg["layer_types"])


def moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - cfg.get("num_dense_layers", 0)


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V of
    every KV head in the attention layers alone."""
    return (2 * attn_layers(cfg) * cfg["num_key_value_heads"]
            * _head_dim(cfg) * DTYPE_BYTES[dtype])


def conv_state_bytes_per_slot_layer(cfg: Dict[str, Any],
                                    dtype: str = "bfloat16") -> int:
    """Bytes of one slot's tail in one conv layer: the last
    ``conv_L_cache - 1`` rows of ``B * X``."""
    return ((cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
            * DTYPE_BYTES[dtype])


def conv_state_bytes_per_slot(cfg: Dict[str, Any],
                              dtype: str = "bfloat16") -> int:
    return conv_layers(cfg) * conv_state_bytes_per_slot_layer(cfg, dtype)


def conv_step_bytes_per_row_layer(cfg: Dict[str, Any],
                                  dtype: str = "bfloat16") -> int:
    """Bytes the conv step HAS to move for one live row in one conv
    layer: the tail read once and written once, the step's three rows
    ``B``, ``C`` and ``X`` read and the gated result written (the taps'
    own ``hidden x conv_L_cache`` weights are shared by the rows and
    left out: 12 KB a layer).  The same count whether XLA fuses the step
    or a kernel runs it."""
    return (2 * conv_state_bytes_per_slot_layer(cfg, dtype)
            + 4 * cfg["hidden_size"] * DTYPE_BYTES[dtype])


def conv_step_flops_per_row_layer(cfg: Dict[str, Any]) -> int:
    """Operations of the same: the gate ``B * X``, ``conv_L_cache``
    multiply-adds a channel, the gate ``C * c``."""
    return cfg["hidden_size"] * (2 + 2 * cfg["conv_L_cache"])


def expert_launches_per_layer(cfg: Dict[str, Any]) -> int:
    """Grouped products an expert layer launches: gate, up and down."""
    return 3


def held_expert_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE held expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * DTYPE_BYTES[dtype])


def held_expert_bytes_per_layer(cfg: Dict[str, Any],
                                dtype: str = "bfloat16") -> int:
    return cfg["num_experts"] * held_expert_bytes(cfg, dtype)


def expert_flops_per_assignment(cfg: Dict[str, Any]) -> int:
    """Operations one (token, choice) pair costs in its expert: three
    products of hidden x expert width, 2 a multiply-add."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
