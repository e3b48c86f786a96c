"""Shape arithmetic for K-EXAONE (window and full attention layers in
one stack, a leading dense layer, an expert layer with a shared expert):
a cache of TWO geometries.  The paged pool holds the FULL layers' K and V
alone (``kv_bytes_per_token``, ``attn_layers``: what the paged decode
kernel launches over); a window layer's K and V is a per-slot RING of
fixed size (``ring_bytes_per_slot``) that the window layers' own launches
read (``swa_layers``, ``ring_row_bytes``).  And what those launches, the
window layers' prompt attention and the grouped expert product HAVE to
move and compute, for the roofline reducers.  ``cfg`` is the
configuration file (or, in a rehearsal, ``rehearse.model``): the sizes
held here, so ``num_experts`` is the experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES


def _windows(cfg: Dict[str, Any]) -> list:
    """Each layer's window (0 = full attention)."""
    if "sliding_windows" in cfg:
        return list(cfg["sliding_windows"])
    pat = cfg["sliding_window_pattern"]
    return [cfg["sliding_window"] if pat[i % len(pat)] == "L" else 0
            for i in range(cfg["num_hidden_layers"])]


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold pages and launch the paged decode kernel: the
    full-attention ones."""
    return sum(1 for w in _windows(cfg) if not w)


def swa_layers(cfg: Dict[str, Any]) -> int:
    """Window layers: a ring a slot, a launch of their own a step."""
    return sum(1 for w in _windows(cfg) if w)


def moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def ring_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of one token's K and V in ONE layer (a pool's row or a
    ring's): 2 x KV heads x head size."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * DTYPE_BYTES[dtype])


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the PAGED cache: K and V in the
    full layers alone (a window layer holds no page a token)."""
    return attn_layers(cfg) * ring_row_bytes(cfg, dtype)


def ring_tokens(cfg: Dict[str, Any], page_tokens: int) -> int:
    """Tokens a slot's ring holds in a window layer: the window in whole
    pages and one page of slack."""
    return (-(-cfg["sliding_window"] // page_tokens) + 1) * page_tokens


def ring_bytes_per_slot(cfg: Dict[str, Any], page_tokens: int,
                        dtype: str = "bfloat16") -> int:
    """Bytes the rings of all window layers hold a decode slot, whatever
    the context's length."""
    return (swa_layers(cfg) * ring_tokens(cfg, page_tokens)
            * ring_row_bytes(cfg, dtype))


def swa_decode_flops_per_row_read(cfg: Dict[str, Any]) -> int:
    """Operations a window layer's decode launch HAS to make for one
    live ring row: every query head's score and its value sum over the
    head size, 2 a multiply-add."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def swa_prefill_pairs(cfg: Dict[str, Any], rows: int) -> int:
    """(query, key) pairs a window layer's prompt launch HAS to score
    for a prompt of ``rows`` live rows: row i sees min(i + 1, window)
    keys (live rows, not the bucket)."""
    w = min(cfg["sliding_window"], rows)
    return w * (w + 1) // 2 + (rows - w) * w


def swa_prefill_flops_per_pair(cfg: Dict[str, Any]) -> int:
    """Operations of one pair in one window layer: every head's score
    and value sum over the head size, 2 a multiply-add."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


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
