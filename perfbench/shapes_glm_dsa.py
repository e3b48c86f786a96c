"""Shape arithmetic for GLM-5.2 (latent attention under a learned sparse
selection; leading dense layers; an expert layer with a shared expert):
the paged cache's two rows a token (a latent row a layer, an index key a
picking layer), and what the scoring pass, the attention over the
selected rows and the grouped expert product HAVE to move and compute,
for the roofline reducers.  ``cfg`` is the configuration file (or, in a
rehearsal, ``rehearse.model``): the sizes held here, so
``n_routed_experts`` is the experts this chip holds; the per-layer lists
are the published ones, of which the layers held start at ``first_layer``.
"""

from __future__ import annotations

from typing import Any, Dict

from .shapes import DTYPE_BYTES

LANES = 128  # a pool row is whole 128-lane tiles


def attn_layers(cfg: Dict[str, Any]) -> int:
    """Layers that hold latent pages and launch the decode attention:
    every layer."""
    return cfg["num_hidden_layers"]


def _held(cfg: Dict[str, Any], key: str) -> list:
    """The entries of a per-layer list for the layers held here:
    ``num_hidden_layers`` of them from ``first_layer`` (a cut states the
    published list whole)."""
    first = cfg.get("first_layer", 0)
    return cfg[key][first:first + cfg["num_hidden_layers"]]


def index_layers(cfg: Dict[str, Any]) -> int:
    """Layers whose indexer picks: each holds an index key a token and
    launches the scoring pass once a step."""
    return _held(cfg, "indexer_types").count("full")


def moe_layers(cfg: Dict[str, Any]) -> int:
    return _held(cfg, "mlp_layer_types").count("sparse")


def latent_values(cfg: Dict[str, Any]) -> int:
    """Values the mathematics caches for a token in a layer: the normed
    latent and the ONE rotated key (512 + 64 = 576)."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of a token's latent row in one layer AS HELD: the 576
    values padded to whole 128-lane tiles (640): 1,280 B."""
    lanes = -(-latent_values(cfg) // LANES) * LANES
    return lanes * DTYPE_BYTES[dtype]


def index_row_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of a token's ONE index key in a picking layer: 256 B."""
    return cfg["index_head_dim"] * DTYPE_BYTES[dtype]


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes one resident token holds in the paged cache over all
    layers: a latent row a layer and an index key a picking layer, under
    one page id (5 x 1,280 + 2 x 256 = 6,912)."""
    return (attn_layers(cfg) * latent_row_bytes(cfg, dtype)
            + index_layers(cfg) * index_row_bytes(cfg, dtype))


def attend_flops_per_row(cfg: Dict[str, Any]) -> int:
    """Operations the absorbed decode step HAS to make for one SELECTED
    row in one layer: every head's score over the 576 values and its
    value sum over the first 512, 2 a multiply-add (64 x 1,088 x 2 =
    139,264)."""
    return cfg["num_attention_heads"] * 2 * (
        latent_values(cfg) + cfg["kv_lora_rank"])


def index_flops_per_row(cfg: Dict[str, Any]) -> int:
    """Operations the scoring pass HAS to make for one cached token in
    one picking layer: every index head's dot product, 2 a multiply-add
    (32 x 128 x 2 = 8,192)."""
    return cfg["index_n_heads"] * cfg["index_head_dim"] * 2


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
