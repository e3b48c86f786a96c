"""Shape arithmetic for Granite 4.0-H (every layer a Mamba-2 or a GQA
attention mixer by ``layer_types``, then a dense SwiGLU): the paged
cache's shape, and what the Mamba-2 step kernel HAS to move, for the
roofline reducers.  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``): the published keys.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def _layers(cfg: Dict[str, Any], kind: str) -> int:
    return cfg["layer_types"].count(kind)


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold K/V pages and launch the paged decode kernel."""
    return _layers(cfg, "attention")


def linear_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold a recurrent state and launch its step kernel."""
    return _layers(cfg, "mamba")


def head_dim(cfg: Dict[str, Any]) -> int:
    """The published config has no key for it: hidden / heads."""
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V in the
    attention layers only (two heads of 64 fill a 128-lane row: no
    padding lane is counted, none is held)."""
    return (2 * attn_layers(cfg) * cfg["num_key_value_heads"]
            * head_dim(cfg) * DTYPE_BYTES[dtype])


def state_bytes_per_slot_layer(cfg: Dict[str, Any]) -> int:
    """Bytes of one slot's float32 state in one Mamba-2 layer: what a
    decode step reads once and writes once for it."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"] * 4)


def tail_bytes_per_slot_layer(cfg: Dict[str, Any],
                              dtype: str = "bfloat16") -> int:
    """Bytes of one slot's convolution tail in one Mamba-2 layer: the
    last ``mamba_d_conv - 1`` rows of the x, B and C channels."""
    channels = (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
                + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])
    return (cfg["mamba_d_conv"] - 1) * channels * DTYPE_BYTES[dtype]


def state_bytes_per_slot(cfg: Dict[str, Any], dtype: str = "bfloat16"
                         ) -> int:
    """Bytes a decode slot holds beside the pool, over all Mamba-2
    layers: what sets the batch on one chip."""
    return linear_layers(cfg) * (
        state_bytes_per_slot_layer(cfg)
        + tail_bytes_per_slot_layer(cfg, dtype))
