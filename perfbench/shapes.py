"""Shape arithmetic the roofline metrics need, kept with the benchmark.

Origin: ``vgate_tpu/observability/roofline.py`` (``kv_bytes_per_token``,
``DEVICE_PEAKS``); here an unknown device is an error, not ``None``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def kv_bytes_per_token(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """HBM bytes one resident token's K and V occupy across all layers:
    what every later decode step must read back for it."""
    head_dim = cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"]
    )
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim * DTYPE_BYTES[dtype])


def peaks_for(device_kind: str) -> Dict[str, Any]:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in perfbench/peaks.json; "
            "add its published peaks with their source"
        )
    return table[device_kind]
