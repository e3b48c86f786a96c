"""Shape arithmetic for Keye-VL-2.0's language model (grouped-query
attention under a learned sparse selection in EVERY layer; an expert
layer without a shared expert): the paged cache's two rows a token (its
K over its V a layer, an index key a layer, under one page id), and what
the scoring pass, the attention over the selected tokens and the grouped
expert product HAVE to move and compute, for the roofline reducers.
``cfg`` is the configuration file (or, in a rehearsal,
``rehearse.model``): the sizes held here, so ``num_experts`` is the
experts this chip holds.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES

LANES = 128  # a pool row is whole 128-lane tiles


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold K/V pages and launch the decode attention: every
    layer."""
    return cfg["num_hidden_layers"]


def index_layers(cfg: Dict[str, Any]) -> int:
    """Layers whose indexer picks: every layer holds an index key a token
    and launches the scoring pass once a step."""
    return cfg["num_hidden_layers"]


def moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"]


def kv_values(cfg: Dict[str, Any]) -> int:
    """Values a token's K (or its V) holds in a layer: every KV head's
    (4 x 128 = 512)."""
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def latent_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """The name the selection's reducer asks for: bytes of ONE picked
    token in one layer AS HELD and as fetched, its K over its V (2 x 512
    values: 2,048 B; no padding, no partner row)."""
    return 2 * kv_values(cfg) * DTYPE_BYTES[dtype]


def index_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of a token's ONE index key in a layer AS HELD: the 64 values
    in a row of 128 lanes (a 64-lane page is no descriptor Mosaic takes):
    256 B, of which the mathematics needs 128."""
    d = cfg["sa_config"]["indexer_head_dim"]
    return -(-d // LANES) * LANES * DTYPE_BYTES[dtype]


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the paged cache over all layers:
    K over V and an index key a layer, under one page id (12 x (2,048 +
    256) = 27,648)."""
    return attn_layers(cfg) * (
        latent_row_bytes(cfg, dtype) + index_row_bytes(cfg, dtype))


def attend_flops_per_row(cfg: Dict[str, Any]) -> int:
    """Operations the decode step HAS to make for one SELECTED token in
    one layer: every query head's score against its group's key and its
    value sum, 2 a multiply-add (32 x 2 x 128 x 2 = 16,384)."""
    return cfg["num_attention_heads"] * 2 * cfg["head_dim"] * 2


def index_flops_per_row(cfg: Dict[str, Any]) -> int:
    """Operations the scoring pass HAS to make for one cached token in
    one layer: every index head's dot product over the 64 values, 2 a
    multiply-add (16 x 64 x 2 = 2,048)."""
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"] * sa["indexer_head_dim"] * 2


def expert_launches_per_layer(cfg: Dict[str, Any]) -> int:
    """Grouped products an expert layer launches: gate, up and down."""
    return 3


def held_expert_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of ONE held expert's three matrices (3 x 2,048 x 768 x 2)."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * DTYPE_BYTES[dtype])


def held_expert_bytes_per_layer(cfg: Dict[str, Any],
                                dtype: str = "bfloat16") -> int:
    return cfg["num_experts"] * held_expert_bytes(cfg, dtype)


def expert_flops_per_assignment(cfg: Dict[str, Any]) -> int:
    """Operations one (token, choice) pair costs in its expert: three
    products of hidden x expert width, 2 a multiply-add."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
