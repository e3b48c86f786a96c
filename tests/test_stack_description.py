"""The layer stack of every shipped spec, frozen (PR 43).

``ModelSpec`` states "which layer is what" in one of a few spellings (an
interval, one letter a layer, window letters, indexer letters, or none:
every layer alike) and everything the stack walker, the pools and the
parameter tree are sized from derives from that.  The table below holds
those derived values for every registered spec, for the spec each
``perfbench/configs/*.json`` makes the program run (built as
``perfbench/serve.py register`` builds it) and for the cuts other tests
run, as literals taken from the code BEFORE the derivation was unified:
a change to the derivation that moves one of them moves a traced
program, a pool's size or a parameter tree.  No compile: seconds.
"""

import dataclasses
import functools
import glob
import json
import os

import pytest

from vgate_tpu.models import specs
from vgate_tpu.models.specs import ModelSpec, spec_for_model_id

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cuts that other tests or a benchmark cell's compile checks run
VARIANTS = {
    "variant:tiny-hybrid, two periods": ("tiny-hybrid", {"num_layers": 8}),
    "variant:tiny-nemotron-h, two periods": (
        "tiny-nemotron-h", {"num_layers": 10, "layer_pattern": "EMEM*EMEM*"}),
    "variant:tiny-nemotron-h, no period": (
        "tiny-nemotron-h", {"num_layers": 9, "layer_pattern": "MEM*EMEME"}),
    "variant:tiny-swa-moe, five layers": ("tiny-swa-moe", {"num_layers": 5}),
    "variant:tiny-dsa-moe, five layers": (
        "tiny-dsa-moe", {"num_layers": 5, "indexer_pattern": "FSSSF"}),
    "variant:glm-5.2, layers 2..6 of the published lists": (
        "zai-org/GLM-5.2", {"num_layers": 5, "first_layer": 2}),
}


@functools.lru_cache(maxsize=None)
def all_specs() -> dict:
    # the presets of specs.py itself: other test files register cuts
    out = {s.name: s for s in vars(specs).values()
           if isinstance(s, ModelSpec)}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "perfbench", "configs", "*.json"))):
        with open(path) as fh:
            program = json.load(fh)["program"]
        out["config:" + os.path.basename(path)] = dataclasses.replace(
            spec_for_model_id(program["preset"]),
            name=program["model_id"], **program["overrides"])
    for name, (preset, changes) in VARIANTS.items():
        out[name] = dataclasses.replace(
            spec_for_model_id(preset), name=name, **changes)
    return out


def _runs(seq) -> str:
    """``a a a b`` as ``ax3 bx1``."""
    out = []
    for v in seq:
        if out and out[-1][0] == v:
            out[-1][1] += 1
        else:
            out.append([v, 1])
    return " ".join(f"{v}x{n}" for v, n in out)


def derived(spec: ModelSpec) -> dict:
    """A spec's row of the table: ``cut`` is (leading layers, layers a
    period, periods); ``lead`` and ``period`` the walker's blocks, a
    period's as ``kind/group/norm/index``; ``layers`` those that hold
    (pages, index keys, a recurrent state, a ring, experts)."""
    return {
        "cut": (spec.lead_layers, spec.layers_per_period, spec.num_periods),
        "lead": " ".join("+".join(p) for p in spec.lead_blocks),
        "period": " ".join("/".join(map(str, b)) for b in spec.period_blocks),
        "layers": (spec.attn_layers, spec.index_layers, spec.linear_layers,
                   spec.swa_layers, spec.moe_layers),
        "kv_pools": spec.kv_pools,
        "windows": _runs(spec.layer_windows),
        "num_params": spec.num_params,
        "hybrid": spec.is_hybrid,
        "recurrent": spec.recurrent_kind,
        "indexer": _runs(spec.indexer_types),
        "mlp": _runs(spec.mlp_layer_types),
    }


FROZEN = {
    "Qwen/Qwen2.5-0.5B-Instruct": {
        "cut": (0, 1, 24),
        "lead": "",
        "period": "",
        "layers": (24, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x24",
        "num_params": 494032768,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex24",
    },
    "Qwen/Qwen2.5-1.5B-Instruct": {
        "cut": (0, 1, 28),
        "lead": "",
        "period": "",
        "layers": (28, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x28",
        "num_params": 1543714304,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex28",
    },
    "Qwen/Qwen2.5-7B-Instruct": {
        "cut": (0, 1, 28),
        "lead": "",
        "period": "",
        "layers": (28, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x28",
        "num_params": 7615616512,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex28",
    },
    "mistralai/Mixtral-8x7B-Instruct-v0.1": {
        "cut": (0, 1, 32),
        "lead": "",
        "period": "",
        "layers": (32, 0, 0, 0, 32),
        "kv_pools": 2,
        "windows": "0x32",
        "num_params": 46702792704,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex32",
    },
    "meta-llama/Meta-Llama-3-8B-Instruct": {
        "cut": (0, 1, 32),
        "lead": "",
        "period": "",
        "layers": (32, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x32",
        "num_params": 8030261248,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex32",
    },
    "meta-llama/Llama-3.1-8B-Instruct": {
        "cut": (0, 1, 32),
        "lead": "",
        "period": "",
        "layers": (32, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x32",
        "num_params": 8030261248,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex32",
    },
    "meta-llama/Llama-3.2-1B-Instruct": {
        "cut": (0, 1, 16),
        "lead": "",
        "period": "",
        "layers": (16, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x16",
        "num_params": 1235814400,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex16",
    },
    "mistralai/Mistral-7B-Instruct-v0.3": {
        "cut": (0, 1, 32),
        "lead": "",
        "period": "",
        "layers": (32, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x32",
        "num_params": 7248023552,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex32",
    },
    "google/gemma-2-2b-it": {
        "cut": (0, 1, 26),
        "lead": "",
        "period": "",
        "layers": (26, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": (
            "4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 "
            "0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 "
            "4096x1 0x1 4096x1 0x1"
        ),
        "num_params": 2614341888,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex26",
    },
    "google/gemma-2-9b-it": {
        "cut": (0, 1, 42),
        "lead": "",
        "period": "",
        "layers": (42, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": (
            "4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 "
            "0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 "
            "4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 "
            "0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1 4096x1 0x1"
        ),
        "num_params": 9241705984,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex42",
    },
    "Qwen/Qwen3-Next-80B-A3B-Instruct": {
        "cut": (0, 4, 12),
        "lead": "",
        "period": (
            "gdn/linear/input_norm/0 moe/linear/post_norm/0 "
            "gdn/linear/input_norm/1 moe/linear/post_norm/1 "
            "gdn/linear/input_norm/2 moe/linear/post_norm/2 "
            "attn/full/input_norm/0 moe/full/post_norm/0"
        ),
        "layers": (12, 0, 36, 0, 48),
        "kv_pools": 2,
        "windows": "0x48",
        "num_params": 79674391296,
        "hybrid": True,
        "recurrent": "gdn",
        "indexer": "",
        "mlp": "sparsex48",
    },
    "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16": {
        "cut": (0, 88, 1),
        "lead": "",
        "period": (
            "mamba/mamba/norm/0 moe/moe/norm/0 mamba/mamba/norm/1 "
            "moe/moe/norm/1 mamba/mamba/norm/2 moe/moe/norm/2 "
            "mamba/mamba/norm/3 attn/attn/norm/0 moe/moe/norm/3 "
            "mamba/mamba/norm/4 moe/moe/norm/4 mamba/mamba/norm/5 "
            "moe/moe/norm/5 mamba/mamba/norm/6 moe/moe/norm/6 "
            "mamba/mamba/norm/7 attn/attn/norm/1 moe/moe/norm/7 "
            "mamba/mamba/norm/8 moe/moe/norm/8 mamba/mamba/norm/9 "
            "moe/moe/norm/9 mamba/mamba/norm/10 moe/moe/norm/10 "
            "mamba/mamba/norm/11 attn/attn/norm/2 moe/moe/norm/11 "
            "mamba/mamba/norm/12 moe/moe/norm/12 mamba/mamba/norm/13 "
            "moe/moe/norm/13 mamba/mamba/norm/14 moe/moe/norm/14 "
            "mamba/mamba/norm/15 moe/moe/norm/15 mamba/mamba/norm/16 "
            "attn/attn/norm/3 moe/moe/norm/16 mamba/mamba/norm/17 "
            "moe/moe/norm/17 mamba/mamba/norm/18 moe/moe/norm/18 "
            "mamba/mamba/norm/19 moe/moe/norm/19 mamba/mamba/norm/20 "
            "moe/moe/norm/20 mamba/mamba/norm/21 attn/attn/norm/4 "
            "moe/moe/norm/21 mamba/mamba/norm/22 moe/moe/norm/22 "
            "mamba/mamba/norm/23 moe/moe/norm/23 mamba/mamba/norm/24 "
            "moe/moe/norm/24 mamba/mamba/norm/25 moe/moe/norm/25 "
            "mamba/mamba/norm/26 attn/attn/norm/5 moe/moe/norm/26 "
            "mamba/mamba/norm/27 moe/moe/norm/27 mamba/mamba/norm/28 "
            "moe/moe/norm/28 mamba/mamba/norm/29 moe/moe/norm/29 "
            "mamba/mamba/norm/30 moe/moe/norm/30 mamba/mamba/norm/31 "
            "attn/attn/norm/6 moe/moe/norm/31 mamba/mamba/norm/32 "
            "moe/moe/norm/32 mamba/mamba/norm/33 moe/moe/norm/33 "
            "mamba/mamba/norm/34 moe/moe/norm/34 mamba/mamba/norm/35 "
            "attn/attn/norm/7 moe/moe/norm/35 mamba/mamba/norm/36 "
            "moe/moe/norm/36 mamba/mamba/norm/37 moe/moe/norm/37 "
            "mamba/mamba/norm/38 moe/moe/norm/38 mamba/mamba/norm/39 "
            "moe/moe/norm/39"
        ),
        "layers": (8, 0, 40, 0, 40),
        "kv_pools": 2,
        "windows": "0x88",
        "num_params": 120668707840,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "sparsex88",
    },
    "mistralai/Mistral-Small-4-119B-2603": {
        "cut": (0, 1, 36),
        "lead": "",
        "period": "mla/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (36, 0, 0, 0, 36),
        "kv_pools": 1,
        "windows": "0x36",
        "num_params": 118972826624,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex36",
    },
    "LGAI-EXAONE/K-EXAONE-236B-A23B": {
        "cut": (4, 4, 11),
        "lead": "swa+mlp swa+moe swa+moe attn+moe",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "swa/window/input_norm/2 moe/window/post_norm/2 "
            "attn/global/input_norm/0 moe/global/post_norm/0"
        ),
        "layers": (12, 0, 0, 36, 47),
        "kv_pools": 2,
        "windows": (
            "128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1 "
            "128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1 128x3 0x1"
        ),
        "num_params": 236571156352,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "densex1 sparsex47",
    },
    "BAAI/bge-base-en-v1.5": {
        "cut": (0, 1, 12),
        "lead": "",
        "period": "",
        "layers": (12, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x12",
        "num_params": 160174848,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex12",
    },
    "tiny-dense": {
        "cut": (0, 1, 2),
        "lead": "",
        "period": "",
        "layers": (2, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x2",
        "num_params": 139840,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex2",
    },
    "tiny-moe": {
        "cut": (0, 1, 2),
        "lead": "",
        "period": "",
        "layers": (2, 0, 0, 0, 2),
        "kv_pools": 2,
        "windows": "0x2",
        "num_params": 287552,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex2",
    },
    "tiny-gemma2": {
        "cut": (0, 1, 2),
        "lead": "",
        "period": "",
        "layers": (2, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "8x1 0x1",
        "num_params": 131648,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex2",
    },
    "tiny-hybrid": {
        "cut": (0, 4, 1),
        "lead": "",
        "period": (
            "gdn/linear/input_norm/0 moe/linear/post_norm/0 "
            "gdn/linear/input_norm/1 moe/linear/post_norm/1 "
            "gdn/linear/input_norm/2 moe/linear/post_norm/2 "
            "attn/full/input_norm/0 moe/full/post_norm/0"
        ),
        "layers": (1, 0, 3, 0, 4),
        "kv_pools": 2,
        "windows": "0x4",
        "num_params": 358312,
        "hybrid": True,
        "recurrent": "gdn",
        "indexer": "",
        "mlp": "sparsex4",
    },
    "tiny-nemotron-h": {
        "cut": (0, 5, 1),
        "lead": "",
        "period": (
            "moe/moe/norm/0 mamba/mamba/norm/0 moe/moe/norm/1 "
            "mamba/mamba/norm/1 attn/attn/norm/0"
        ),
        "layers": (1, 0, 2, 0, 2),
        "kv_pools": 2,
        "windows": "0x5",
        "num_params": 195880,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "sparsex5",
    },
    "tiny-mla-moe": {
        "cut": (0, 1, 3),
        "lead": "",
        "period": "mla/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (3, 0, 0, 0, 3),
        "kv_pools": 1,
        "windows": "0x3",
        "num_params": 347192,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex3",
    },
    "tiny-swa-moe": {
        "cut": (1, 4, 2),
        "lead": "swa+mlp",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "attn/global/input_norm/0 moe/global/post_norm/0 "
            "swa/window/input_norm/2 moe/window/post_norm/2"
        ),
        "layers": (2, 0, 0, 7, 8),
        "kv_pools": 2,
        "windows": "8x3 0x1 8x3 0x1 8x1",
        "num_params": 648736,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "densex1 sparsex8",
    },
    "zai-org/GLM-5.2": {
        "cut": (6, 4, 18),
        "lead": "dsa+mlp dsa+mlp dsa+mlp mla+moe mla+moe mla+moe",
        "period": (
            "dsa/pick/input_norm/0 moe/pick/post_norm/0 "
            "mla/reuse/input_norm/0 moe/reuse/post_norm/0 "
            "mla/reuse/input_norm/1 moe/reuse/post_norm/1 "
            "mla/reuse/input_norm/2 moe/reuse/post_norm/2"
        ),
        "layers": (78, 21, 0, 0, 75),
        "kv_pools": 1,
        "windows": "0x78",
        "num_params": 743377019904,
        "hybrid": True,
        "recurrent": "",
        "indexer": (
            "fullx3 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3"
        ),
        "mlp": "densex3 sparsex75",
    },
    "tiny-dsa-moe": {
        "cut": (1, 4, 2),
        "lead": "dsa+mlp",
        "period": (
            "mla/reuse/input_norm/0 moe/reuse/post_norm/0 "
            "mla/reuse/input_norm/1 moe/reuse/post_norm/1 "
            "mla/reuse/input_norm/2 moe/reuse/post_norm/2 "
            "dsa/pick/input_norm/0 moe/pick/post_norm/0"
        ),
        "layers": (9, 3, 0, 0, 8),
        "kv_pools": 1,
        "windows": "0x9",
        "num_params": 705880,
        "hybrid": True,
        "recurrent": "",
        "indexer": "fullx1 sharedx3 fullx1 sharedx3 fullx1",
        "mlp": "densex1 sparsex8",
    },
    "tiny-encoder": {
        "cut": (0, 1, 2),
        "lead": "",
        "period": "",
        "layers": (2, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x2",
        "num_params": 148160,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex2",
    },
    "config:glm-5.2-l5e16.json": {
        "cut": (1, 4, 1),
        "lead": "dsa+mlp",
        "period": (
            "mla/reuse/input_norm/0 moe/reuse/post_norm/0 "
            "mla/reuse/input_norm/1 moe/reuse/post_norm/1 "
            "mla/reuse/input_norm/2 moe/reuse/post_norm/2 "
            "dsa/pick/input_norm/0 moe/pick/post_norm/0"
        ),
        "layers": (5, 2, 0, 0, 4),
        "kv_pools": 1,
        "windows": "0x5",
        "num_params": 3881517056,
        "hybrid": True,
        "recurrent": "",
        "indexer": (
            "fullx3 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3"
        ),
        "mlp": "densex3 sparsex75",
    },
    "config:k-exaone-236b-a23b-l5e16.json": {
        "cut": (1, 4, 1),
        "lead": "swa+mlp",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "attn/global/input_norm/0 moe/global/post_norm/0 "
            "swa/window/input_norm/2 moe/window/post_norm/2"
        ),
        "layers": (1, 0, 0, 4, 4),
        "kv_pools": 2,
        "windows": "128x3 0x1 128x1",
        "num_params": 3712028416,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "densex1 sparsex4",
    },
    "config:mistral-small-4-119b-l4e32.json": {
        "cut": (0, 1, 4),
        "lead": "",
        "period": "mla/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (4, 0, 0, 0, 4),
        "kv_pools": 1,
        "windows": "0x4",
        "num_params": 3704660992,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex4",
    },
    "config:nemotron-3-super-120b-a12b-l11e128.json": {
        "cut": (0, 11, 1),
        "lead": "",
        "period": (
            "moe/moe/norm/0 mamba/mamba/norm/0 moe/moe/norm/1 "
            "mamba/mamba/norm/1 moe/moe/norm/2 mamba/mamba/norm/2 "
            "moe/moe/norm/3 mamba/mamba/norm/3 moe/moe/norm/4 "
            "mamba/mamba/norm/4 attn/attn/norm/0"
        ),
        "layers": (1, 0, 5, 0, 5),
        "kv_pools": 2,
        "windows": "0x11",
        "num_params": 4648163712,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "sparsex11",
    },
    "config:qwen2.5-1.5b.json": {
        "cut": (0, 1, 28),
        "lead": "",
        "period": "",
        "layers": (28, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x28",
        "num_params": 1543714304,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex28",
    },
    "config:qwen2.5-7b-l14.json": {
        "cut": (0, 1, 14),
        "lead": "",
        "period": "",
        "layers": (14, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x14",
        "num_params": 4352807424,
        "hybrid": False,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex14",
    },
    "config:qwen3-next-80b-a3b-l8e128.json": {
        "cut": (0, 4, 2),
        "lead": "",
        "period": (
            "gdn/linear/input_norm/0 moe/linear/post_norm/0 "
            "gdn/linear/input_norm/1 moe/linear/post_norm/1 "
            "gdn/linear/input_norm/2 moe/linear/post_norm/2 "
            "attn/full/input_norm/0 moe/full/post_norm/0"
        ),
        "layers": (2, 0, 6, 0, 8),
        "kv_pools": 2,
        "windows": "0x8",
        "num_params": 3667251328,
        "hybrid": True,
        "recurrent": "gdn",
        "indexer": "",
        "mlp": "sparsex8",
    },
    "variant:tiny-hybrid, two periods": {
        "cut": (0, 4, 2),
        "lead": "",
        "period": (
            "gdn/linear/input_norm/0 moe/linear/post_norm/0 "
            "gdn/linear/input_norm/1 moe/linear/post_norm/1 "
            "gdn/linear/input_norm/2 moe/linear/post_norm/2 "
            "attn/full/input_norm/0 moe/full/post_norm/0"
        ),
        "layers": (2, 0, 6, 0, 8),
        "kv_pools": 2,
        "windows": "0x8",
        "num_params": 651024,
        "hybrid": True,
        "recurrent": "gdn",
        "indexer": "",
        "mlp": "sparsex8",
    },
    "variant:tiny-nemotron-h, two periods": {
        "cut": (0, 5, 2),
        "lead": "",
        "period": (
            "moe/moe/norm/0 mamba/mamba/norm/0 moe/moe/norm/1 "
            "mamba/mamba/norm/1 attn/attn/norm/0"
        ),
        "layers": (2, 0, 4, 0, 4),
        "kv_pools": 2,
        "windows": "0x10",
        "num_params": 326160,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "sparsex10",
    },
    "variant:tiny-nemotron-h, no period": {
        "cut": (0, 9, 1),
        "lead": "",
        "period": (
            "mamba/mamba/norm/0 moe/moe/norm/0 mamba/mamba/norm/1 "
            "attn/attn/norm/0 moe/moe/norm/1 mamba/mamba/norm/2 "
            "moe/moe/norm/2 mamba/mamba/norm/3 moe/moe/norm/3"
        ),
        "layers": (1, 0, 4, 0, 4),
        "kv_pools": 2,
        "windows": "0x9",
        "num_params": 313808,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "sparsex9",
    },
    "variant:tiny-swa-moe, five layers": {
        "cut": (1, 4, 1),
        "lead": "swa+mlp",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "attn/global/input_norm/0 moe/global/post_norm/0 "
            "swa/window/input_norm/2 moe/window/post_norm/2"
        ),
        "layers": (1, 0, 0, 4, 4),
        "kv_pools": 2,
        "windows": "8x3 0x1 8x1",
        "num_params": 375680,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "densex1 sparsex4",
    },
    "variant:tiny-dsa-moe, five layers": {
        "cut": (1, 4, 1),
        "lead": "dsa+mlp",
        "period": (
            "mla/reuse/input_norm/0 moe/reuse/post_norm/0 "
            "mla/reuse/input_norm/1 moe/reuse/post_norm/1 "
            "mla/reuse/input_norm/2 moe/reuse/post_norm/2 "
            "dsa/pick/input_norm/0 moe/pick/post_norm/0"
        ),
        "layers": (5, 2, 0, 0, 4),
        "kv_pools": 1,
        "windows": "0x5",
        "num_params": 408376,
        "hybrid": True,
        "recurrent": "",
        "indexer": "fullx1 sharedx3 fullx1",
        "mlp": "densex1 sparsex4",
    },
    "variant:glm-5.2, layers 2..6 of the published lists": {
        "cut": (1, 4, 1),
        "lead": "dsa+mlp",
        "period": (
            "mla/reuse/input_norm/0 moe/reuse/post_norm/0 "
            "mla/reuse/input_norm/1 moe/reuse/post_norm/1 "
            "mla/reuse/input_norm/2 moe/reuse/post_norm/2 "
            "dsa/pick/input_norm/0 moe/pick/post_norm/0"
        ),
        "layers": (5, 2, 0, 0, 4),
        "kv_pools": 1,
        "windows": "0x5",
        "num_params": 41785573376,
        "hybrid": True,
        "recurrent": "",
        "indexer": (
            "fullx3 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3 "
            "fullx1 sharedx3 fullx1 sharedx3 fullx1 sharedx3"
        ),
        "mlp": "densex3 sparsex75",
    },
    "EvaByte/EvaByte": {
        "cut": (0, 1, 32),
        "lead": "",
        "period": "eva/layer/input_norm/0 mlp/layer/post_norm/0",
        "layers": (32, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x32",
        "num_params": 6488330240,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex32",
    },
    "tiny-eva": {
        "cut": (0, 1, 4),
        "lead": "",
        "period": "eva/layer/input_norm/0 mlp/layer/post_norm/0",
        "layers": (4, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x4",
        "num_params": 349248,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex4",
    },
    "config:evabyte-6.5b-l8.json": {
        "cut": (0, 1, 8),
        "lead": "",
        "period": "eva/layer/input_norm/0 mlp/layer/post_norm/0",
        "layers": (8, 0, 0, 0, 0),
        "kv_pools": 2,
        "windows": "0x8",
        "num_params": 1630932992,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex8",
    },
    "LiquidAI/LFM2-24B-A2B": {
        "cut": (4, 4, 9),
        "lead": "conv+mlp conv+mlp attn+moe conv+moe",
        "period": (
            "conv/conv/input_norm/0 moe/conv/post_norm/0 "
            "conv/conv/input_norm/1 moe/conv/post_norm/1 "
            "attn/attn/input_norm/0 moe/attn/post_norm/0 "
            "conv/conv/input_norm/2 moe/conv/post_norm/2"
        ),
        "layers": (10, 0, 0, 0, 38),
        "kv_pools": 2,
        "windows": "0x40",
        "num_params": 23843661440,
        "hybrid": True,
        "recurrent": "conv",
        "indexer": "",
        "mlp": "densex2 sparsex38",
    },
    "tiny-lfm2-moe": {
        "cut": (4, 4, 2),
        "lead": "conv+mlp conv+mlp attn+moe conv+moe",
        "period": (
            "conv/conv/input_norm/0 moe/conv/post_norm/0 "
            "conv/conv/input_norm/1 moe/conv/post_norm/1 "
            "attn/attn/input_norm/0 moe/attn/post_norm/0 "
            "conv/conv/input_norm/2 moe/conv/post_norm/2"
        ),
        "layers": (3, 0, 0, 0, 10),
        "kv_pools": 2,
        "windows": "0x12",
        "num_params": 766384,
        "hybrid": True,
        "recurrent": "conv",
        "indexer": "",
        "mlp": "densex2 sparsex10",
    },
    "config:lfm2-24b-a2b-e8.json": {
        "cut": (4, 4, 9),
        "lead": "conv+mlp conv+mlp attn+moe conv+moe",
        "period": (
            "conv/conv/input_norm/0 moe/conv/post_norm/0 "
            "conv/conv/input_norm/1 moe/conv/post_norm/1 "
            "attn/attn/input_norm/0 moe/attn/post_norm/0 "
            "conv/conv/input_norm/2 moe/conv/post_norm/2"
        ),
        "layers": (10, 0, 0, 0, 38),
        "kv_pools": 2,
        "windows": "0x40",
        "num_params": 3761333888,
        "hybrid": True,
        "recurrent": "conv",
        "indexer": "",
        "mlp": "densex2 sparsex38",
    },
    "ibm-granite/granite-4.0-h-micro": {
        "cut": (0, 10, 4),
        "lead": "",
        "period": (
            "mamba/mamba/input_norm/0 mlp/mamba/post_norm/0 "
            "mamba/mamba/input_norm/1 mlp/mamba/post_norm/1 "
            "mamba/mamba/input_norm/2 mlp/mamba/post_norm/2 "
            "mamba/mamba/input_norm/3 mlp/mamba/post_norm/3 "
            "mamba/mamba/input_norm/4 mlp/mamba/post_norm/4 "
            "attn/attn/input_norm/0 mlp/attn/post_norm/0 "
            "mamba/mamba/input_norm/5 mlp/mamba/post_norm/5 "
            "mamba/mamba/input_norm/6 mlp/mamba/post_norm/6 "
            "mamba/mamba/input_norm/7 mlp/mamba/post_norm/7 "
            "mamba/mamba/input_norm/8 mlp/mamba/post_norm/8"
        ),
        "layers": (4, 0, 36, 0, 0),
        "kv_pools": 2,
        "windows": "0x40",
        "num_params": 3191396096,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "densex40",
    },
    "tiny-granite-hybrid": {
        "cut": (0, 5, 1),
        "lead": "",
        "period": (
            "mamba/mamba/input_norm/0 mlp/mamba/post_norm/0 "
            "mamba/mamba/input_norm/1 mlp/mamba/post_norm/1 "
            "mamba/mamba/input_norm/2 mlp/mamba/post_norm/2 "
            "attn/attn/input_norm/0 mlp/attn/post_norm/0 "
            "mamba/mamba/input_norm/3 mlp/mamba/post_norm/3"
        ),
        "layers": (1, 0, 4, 0, 0),
        "kv_pools": 2,
        "windows": "0x5",
        "num_params": 229232,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "densex5",
    },
    "config:granite-4.0-h-micro.json": {
        "cut": (0, 10, 4),
        "lead": "",
        "period": (
            "mamba/mamba/input_norm/0 mlp/mamba/post_norm/0 "
            "mamba/mamba/input_norm/1 mlp/mamba/post_norm/1 "
            "mamba/mamba/input_norm/2 mlp/mamba/post_norm/2 "
            "mamba/mamba/input_norm/3 mlp/mamba/post_norm/3 "
            "mamba/mamba/input_norm/4 mlp/mamba/post_norm/4 "
            "attn/attn/input_norm/0 mlp/attn/post_norm/0 "
            "mamba/mamba/input_norm/5 mlp/mamba/post_norm/5 "
            "mamba/mamba/input_norm/6 mlp/mamba/post_norm/6 "
            "mamba/mamba/input_norm/7 mlp/mamba/post_norm/7 "
            "mamba/mamba/input_norm/8 mlp/mamba/post_norm/8"
        ),
        "layers": (4, 0, 36, 0, 0),
        "kv_pools": 2,
        "windows": "0x40",
        "num_params": 3191396096,
        "hybrid": True,
        "recurrent": "mamba",
        "indexer": "",
        "mlp": "densex40",
    },
    # GQA attention under a selection of EVERY layer's own (PR 53): one
    # group, K over V in one array (``kv_pools`` counts its two rows)
    "Kwai-Keye/Keye-VL-2.0-30B-A3B": {
        "cut": (0, 1, 48),
        "lead": "",
        "period": "dsa/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (48, 48, 0, 0, 48),
        "kv_pools": 2,
        "windows": "0x48",
        "num_params": 30640656384,
        "hybrid": True,
        "recurrent": "",
        "indexer": "fullx48",
        "mlp": "sparsex48",
    },
    "tiny-keye-dsa": {
        "cut": (0, 1, 4),
        "lead": "",
        "period": "dsa/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (4, 4, 0, 0, 4),
        "kv_pools": 2,
        "windows": "0x4",
        "num_params": 325376,
        "hybrid": True,
        "recurrent": "",
        "indexer": "fullx4",
        "mlp": "sparsex4",
    },
    "config:keye-vl-2.0-30b-a3b-l12e32.json": {
        "cut": (0, 1, 12),
        "lead": "",
        "period": "dsa/layer/input_norm/0 moe/layer/post_norm/0",
        "layers": (12, 12, 0, 0, 12),
        "kv_pools": 2,
        "windows": "0x12",
        "num_params": 2224347648,
        "hybrid": True,
        "recurrent": "",
        "indexer": "fullx12",
        "mlp": "sparsex12",
    },
    "JetBrains/Mellum2-12B-A2.5B-Instruct": {
        "cut": (0, 4, 7),
        "lead": "",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "swa/window/input_norm/2 moe/window/post_norm/2 "
            "attn/global/input_norm/0 moe/global/post_norm/0"
        ),
        "layers": (7, 0, 0, 21, 28),
        "kv_pools": 2,
        "windows": " ".join(["1024x3 0x1"] * 7),
        "num_params": 12149923072,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex28",
    },
    "tiny-mellum": {
        "cut": (0, 4, 2),
        "lead": "",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "swa/window/input_norm/2 moe/window/post_norm/2 "
            "attn/global/input_norm/0 moe/global/post_norm/0"
        ),
        "layers": (2, 0, 0, 6, 8),
        "kv_pools": 2,
        "windows": "8x3 0x1 8x3 0x1",
        "num_params": 562496,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex8",
    },
    "config:mellum2-12b-a2.5b-l8.json": {
        "cut": (0, 4, 2),
        "lead": "",
        "period": (
            "swa/window/input_norm/0 moe/window/post_norm/0 "
            "swa/window/input_norm/1 moe/window/post_norm/1 "
            "swa/window/input_norm/2 moe/window/post_norm/2 "
            "attn/global/input_norm/0 moe/global/post_norm/0"
        ),
        "layers": (2, 0, 0, 6, 8),
        "kv_pools": 2,
        "windows": "1024x3 0x1 1024x3 0x1",
        "num_params": 3794968832,
        "hybrid": True,
        "recurrent": "",
        "indexer": "",
        "mlp": "sparsex8",
    },
}

FIELDS = [
    ("name", "required"), ("vocab_size", "required"),
    ("hidden_size", "required"), ("num_layers", "required"),
    ("num_heads", "required"), ("num_kv_heads", "required"),
    ("head_dim", "required"), ("intermediate_size", "required"),
    ("rope_theta", 1000000.0), ("rms_eps", 1e-06), ("qkv_bias", True),
    ("tie_embeddings", False), ("eos_token_id", 151645),
    ("bos_token_id", 151643), ("extra_stop_ids", ()), ("num_experts", 0),
    ("experts_per_token", 0), ("is_encoder", False),
    ("max_position_embeddings", 32768), ("act", "silu"), ("attn_softcap", 0.0),
    ("final_softcap", 0.0), ("sliding_window", 0), ("query_scale", 0.0),
    ("embed_scale", False), ("unit_offset_norm", False),
    ("ffn_sandwich", False), ("rope_scaling_factor", 0.0),
    ("rope_low_freq_factor", 1.0), ("rope_high_freq_factor", 4.0),
    ("rope_original_max_pos", 8192), ("quant_kernel", False),
    ("int8_native", False), ("moe_intermediate_size", 0),
    ("shared_expert_intermediate_size", 0), ("router_width", 0),
    ("first_expert", 0), ("qk_norm", False), ("attn_output_gate", False),
    ("partial_rotary_factor", 1.0), ("full_attention_interval", 0),
    ("linear_num_key_heads", 0), ("linear_num_value_heads", 0),
    ("linear_key_head_dim", 0), ("linear_value_head_dim", 0),
    ("linear_conv_kernel_dim", 0), ("layer_pattern", ""), ("use_rope", True),
    ("mamba_num_heads", 0), ("mamba_head_dim", 0), ("mamba_state_size", 0),
    ("mamba_n_groups", 0), ("mamba_conv_kernel", 0),
    ("mamba_conv_bias", False), ("mamba_chunk_size", 128),
    ("moe_latent_size", 0), ("router_scoring", "softmax"),
    ("routed_scaling_factor", 1.0), ("moe_gated", True),
    ("shared_expert_gate", True), ("n_shared_experts", 0), ("q_lora_rank", 0),
    ("kv_lora_rank", 0), ("qk_nope_head_dim", 0), ("qk_rope_head_dim", 0),
    ("v_head_dim", 0), ("rope_interleave", False), ("yarn_factor", 0.0),
    ("yarn_beta_fast", 32.0), ("yarn_beta_slow", 1.0),
    ("yarn_original_max_pos", 0), ("yarn_mscale", 1.0),
    ("yarn_mscale_all_dim", 0.0), ("llama_4_scaling_beta", 0.0),
    ("window_pattern", ""), ("global_rope", True),
    ("yarn_full_only", False), ("yarn_attention_factor", 0.0),
    ("first_k_dense", 0),
    ("indexer_pattern", ""), ("first_layer", 0), ("index_topk", 0),
    ("index_n_heads", 0), ("index_head_dim", 0),
    ("indexer_rope_interleave", False), ("mrope_section", ()),
    ("eva_window", 0),
    ("eva_chunk", 0), ("num_pred_heads", 1), ("fp32_residual", False),
    ("conv_pattern", ""), ("conv_L_cache", 0), ("conv_bias", False),
    ("router_norm_eps", 1e-20), ("kv_head_pack", 1),
    ("mamba_pattern", ""), ("embedding_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0),
]


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_frozen_derived_values(name):
    assert derived(all_specs()[name]) == FROZEN[name]


def test_frozen_table_names_every_spec():
    assert sorted(all_specs()) == sorted(FROZEN)


def test_frozen_fields_of_the_spec():
    """The spec is a static jit argument, and the benchmark's files name
    its fields: name for name, default for default."""
    got = [(f.name, "required" if f.default is dataclasses.MISSING
            else f.default) for f in dataclasses.fields(ModelSpec)]
    assert got == FIELDS
    assert all(type(a) is type(b) for (_, a), (_, b) in zip(got, FIELDS))


def test_the_conv_spelling_finds_its_leading_layers_by_the_one_search():
    """LFM2's ``layer_types`` with ``num_dense_layers``: ``conv mlp`` x 2,
    then ``attn moe``, ``conv moe`` x 3 repeating, ending two layers into
    a repeat.  ``_cut`` finds 4 leading layers and 9 periods of ``conv
    conv attn conv`` (the tiny preset: the first 12 types, 2 periods);
    the state is a tail a conv layer and no tile."""
    full = spec_for_model_id("LiquidAI/LFM2-24B-A2B")
    assert full.conv_pattern == "CCA" + "CCCA" * 9 + "C"
    assert [i for i, t in enumerate(full.layer_types)
            if t == "full_attention"] == list(range(2, 40, 4))
    assert (full.lead_layers, full.layers_per_period, full.num_periods) == (
        4, 4, 9)
    assert [layer[0] for layer in full.stack[4:8]] == [
        "conv", "conv", "attn", "conv"]
    assert [layer[1] for layer in full.stack[:3]] == ["mlp", "mlp", "moe"]
    assert (full.conv_layers, full.attn_layers, full.linear_layers,
            full.slot_state_layers) == (30, 10, 0, 30)
    assert [full.group_layers(g) for g in ("conv", "attn")] == [3, 1]
    assert abs(full.num_params / 1e9 - 23.84) < 0.01  # tied
    cut = dataclasses.replace(full, num_experts=8)
    assert abs(cut.num_params / 1e6 - 3761) < 1
    kinds = full._kind_params()
    assert kinds["conv"] == 12582912 + 4194304 + 6144
    assert kinds["attn"] == 10485760 + 2 * 64  # and the per-head norms
    assert kinds["mlp"] == 72351744
    tiny = spec_for_model_id("tiny-lfm2-moe")
    assert tiny.layer_types == full.layer_types[:12]
    assert (tiny.lead_layers, tiny.num_periods, tiny.conv_layers) == (4, 2, 9)
    # a head of 64 pairs into a 128-lane row; a head of 16 does not
    assert full.kv_heads_pair and not tiny.kv_heads_pair
    packed = full.pack_kv_heads()
    assert (packed.cache_heads, packed.cache_head_dim) == (4, 128)
    assert packed.stack == full.stack and packed.num_params == full.num_params


class _Mixed(ModelSpec):
    """A stack no preset has and no field spells: a dense leading
    window layer, then (window layer, Mamba-2 layer, full layer) twice,
    layers of two sub-blocks beside a layer of one."""

    LAYERS = (("swa", "mlp"),) + (
        ("swa", "moe"), ("mamba",), ("attn", "moe")) * 2

    @property
    def _spelling(self):
        return specs._Spelling(
            lambda spec: self.LAYERS, True,
            {"swa": "window", "mamba": "mamba", "attn": "global"})


def test_a_stack_no_preset_has_parses():
    """A sixth spelling is a parser and a row of groups: the cut, the
    walker's blocks and the counts all derive from ``stack``."""
    base = spec_for_model_id("tiny-swa-moe")
    spec = _Mixed(**{**{f.name: getattr(base, f.name)
                        for f in dataclasses.fields(base)},
                     "name": "tiny-mixed", "num_layers": 7})
    assert spec.stack == _Mixed.LAYERS and spec.is_hybrid
    assert (spec.lead_layers, spec.layers_per_period, spec.num_periods) == (
        1, 3, 2)
    assert spec.lead_blocks == (("swa", "mlp"),)
    assert spec.period_blocks == (
        ("swa", "window", "input_norm", 0), ("moe", "window", "post_norm", 0),
        ("mamba", "mamba", "norm", 0),
        ("attn", "global", "input_norm", 0),
        ("moe", "global", "post_norm", 0))
    assert [spec.group_layers(g) for g in ("window", "mamba", "global")] == [
        1, 1, 1]
    assert (spec.attn_layers, spec.index_layers, spec.linear_layers,
            spec.swa_layers, spec.moe_layers) == (2, 0, 2, 3, 4)
    assert spec.recurrent_kind == "mamba" and spec.slot_state_layers == 5
    assert spec.layer_windows == (8, 8, 0, 0, 8, 0, 0)
    # every sub-block counted once: the stack less one period is a period
    # lighter, whatever the period holds
    shorter = dataclasses.replace(spec, num_layers=4)
    assert shorter.stack == _Mixed.LAYERS[:4] and shorter.num_periods == 1
    kinds = spec._kind_params()
    D = spec.hidden_size
    assert spec.num_params - shorter.num_params == (
        kinds["swa"] + kinds["mamba"] + kinds["attn"] + 2 * kinds["moe"]
        + 5 * D)


@pytest.mark.parametrize("preset, changes, why", [
    # six layers are a period of four and half a period
    ("tiny-hybrid", {"num_layers": 6}, "not whole periods"),
    # five letters for seven layers
    ("tiny-nemotron-h", {"num_layers": 7}, "5 of its 7 layers"),
    # layers 4..12 of a list of nine
    ("tiny-dsa-moe", {"first_layer": 4}, "5 of its 9 layers"),
], ids=["interval", "letters", "indexer"])
def test_a_stack_stated_for_other_layers_is_refused_by_name(
        preset, changes, why):
    spec = dataclasses.replace(
        spec_for_model_id(preset), name=preset + "-odd", **changes)
    with pytest.raises(ValueError, match=f"{preset}-odd.*{why}"):
        spec.stack
    with pytest.raises(ValueError, match=preset + "-odd"):
        spec.num_periods
