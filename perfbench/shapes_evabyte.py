"""Shape arithmetic for EvaByte (every layer EVA attention: an exact
window a decode slot, one learned summary row for every chunk of the
closed windows in the paged pool): a cache whose POOL ROW stands for
``chunk_size`` tokens.  ``kv_bytes_per_token`` is therefore the bytes of
one pool row (a chunk's ``k~`` and ``v~`` over all layers), which is
what a page of ``page size`` rows is made of and what the run checks
against the program's ``kv_page_bytes``; a token of a closed window
costs a ``chunk_size``-th of it, a token of the open window a whole
``eva_row_bytes`` a layer in the slot's window buffer
(``eva_window_bytes_per_slot``, whatever the context).  And what the
decode launch HAS to move and compute, for the roofline reducer.  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``).
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"])


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold pages and launch the decode kernel: all."""
    return cfg["num_hidden_layers"]


def eva_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE row in ONE layer, K and V: an exact row of the
    window buffer and a summary row of the pool are the same shape, 2 x
    KV heads x head size."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * DTYPE_BYTES[dtype])


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of one POOL ROW over all layers.  The harness's name says
    "token" because every other configuration's row is one; here a row
    stands for ``chunk_size`` tokens (``pool_bytes_per_token``)."""
    return attn_layers(cfg) * eva_row_bytes(cfg, dtype)


def pool_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16"
                         ) -> float:
    """What a token of a CLOSED window holds in the pool."""
    return kv_bytes_per_token(cfg, dtype) / cfg["chunk_size"]


def eva_window_bytes_per_slot(cfg: Dict[str, Any], dtype: str = "bfloat16"
                              ) -> int:
    """Bytes the open windows of all layers hold a decode slot."""
    return attn_layers(cfg) * cfg["window_size"] * eva_row_bytes(cfg, dtype)


def eva_decode_flops_per_row(cfg: Dict[str, Any]) -> int:
    """Operations the decode launch HAS to make for one row it reads
    (exact or summary): every head's score and its value sum over the
    head size, 2 a multiply-add."""
    return cfg["num_attention_heads"] * 2 * 2 * head_dim(cfg)

