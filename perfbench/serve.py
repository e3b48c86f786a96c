"""Server entry for a configuration whose preset the program does not
ship (a depth cut): registers the changed preset, checks it against the
configuration file, then runs the program's own ``main()`` -- the same
call ``python main.py`` makes.  Nothing else differs.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# configuration-file key -> ModelSpec field, for the check below
SPEC_KEYS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
}


def register(config: dict, rehearse: bool) -> None:
    from vgate_tpu.models import specs

    program = config["rehearse" if rehearse else "program"]
    base = specs.spec_for_model_id(program["preset"])
    spec = dataclasses.replace(
        base, name=program["model_id"], **program["overrides"]
    )
    if not rehearse:
        for key, attr in SPEC_KEYS.items():
            if key in config and getattr(spec, attr) != config[key]:
                raise SystemExit(
                    f"{config['_path']}: {key}={config[key]!r} but the "
                    f"program would run {attr}={getattr(spec, attr)!r}"
                )
    specs._register(spec)


def main() -> None:
    path = os.environ["PERFBENCH_CONFIG"]
    with open(path) as fh:
        config = json.load(fh)
    config["_path"] = path
    register(config, os.environ.get("PERFBENCH_REHEARSE") == "1")
    from vgate_tpu.server.app import main as program_main

    program_main()


if __name__ == "__main__":
    main()
