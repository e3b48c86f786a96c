"""Device time by NAMED SCOPE, from a profiler trace (``.xplane.pb``).

    JAX_PLATFORMS=cpu python -m perfbench.trace_scopes TRACE_DIR OUT.json

``perfbench.trace`` names an operation by its HLO name (``fusion.12``),
which says nothing of the sub-block a fusion belongs to, and a device
event carries nothing else (on the TPU its name is the HLO text, its
stats are offsets).  The name the program traced an operation under
(``jit(_decode_chunk)/.../dense_mlp/dot_general``: the
``jax.named_scope`` path, HLO metadata ``op_name``) is in the profile all
the same: its ``/host:metadata`` plane holds every module's optimized HLO
as a serialized ``HloProto`` (stat ``Hlo Proto`` of the module's event
metadata).  ``jax.profiler.ProfileData`` shows no event metadata, so this
reads the file with the protobuf classes that TensorFlow ships
(``xplane_pb2``, ``hlo_pb2``) and writes

    {"busy_s": device busy seconds summed over devices,
     "scope_seconds": {"<scope path>": SELF seconds}}

the path without its ``jit(...)`` parts, its loop bodies and the
primitive's own name (a fusion stands under its own ``op_name``, which
XLA takes from the operation the fusion was built around).  An operation
whose module or instruction the metadata does not hold counts under
``""``.  Exits 1 where the protobuf classes cannot be imported: the
reducer then reports nothing.
"""

from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List

from .trace import (DEVICE_PLANE, MODULES_LINE, OP_NAME, OPS_LINE, merged,
                    newest_xplane, self_times)

HLO_STAT = "Hlo Proto"
# parts of a traced name that are no scope of the program's
NO_SCOPE = re.compile(r"^(jit|pjit|jvp|transpose|vmap)\(.*\)$"
                      r"|^(while|body|cond|closed_call|branch_\d+_fun)$")
MODULE_ID = re.compile(r"\(\d+\)$")


def scope_of(name: str) -> str:
    """``jit(f)/jit(main)/while/body/dense_mlp/dot_general`` ->
    ``dense_mlp``: the named scopes alone, in order."""
    parts = [p for p in name.split("/")[:-1] if p and not NO_SCOPE.match(p)]
    return "/".join(parts)


def module_scopes(space, hlo_pb2) -> Dict[str, Dict[str, str]]:
    """Module (as the modules line names it, ``jit_f(id)``, and without
    the id) -> instruction name -> scope, from the profile's HLO."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if plane.stat_metadata[stat.metadata_id].name != HLO_STAT:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                scopes = {
                    ins.name: scope_of(ins.metadata.op_name)
                    for comp in proto.hlo_module.computations
                    for ins in comp.instructions
                }
                out[meta.name] = scopes
                # the same program compiled twice: one name, two ids
                out.setdefault(MODULE_ID.sub("", meta.name), {}).update(
                    scopes)
    return out


def summarize(path: str) -> Dict[str, object]:
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    scopes = module_scopes(space, hlo_pb2)
    busy = 0.0
    seconds: Dict[str, float] = defaultdict(float)
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue

        def events(line):
            t0 = line.timestamp_ns * 1e-9
            return [(plane.event_metadata[ev.metadata_id].name,
                     t0 + ev.offset_ps * 1e-12,
                     t0 + (ev.offset_ps + ev.duration_ps) * 1e-12)
                    for ev in line.events]

        lines = {line.name: events(line) for line in plane.lines
                 if line.name in (OPS_LINE, MODULES_LINE)}
        ops = lines.get(OPS_LINE, [])
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        at = 0
        for text, start, self_s in sorted(
                self_times(ops), key=lambda e: e[1]):
            while at + 1 < len(modules) and modules[at + 1][1] <= start:
                at += 1
            module = modules[at][0] if modules and (
                modules[at][1] <= start <= modules[at][2]) else ""
            held = scopes.get(module) or scopes.get(
                MODULE_ID.sub("", module), {})
            name = OP_NAME.match(text)
            seconds[held.get(name.group(1) if name else text, "")] += self_s
        busy += sum(b - a for a, b in merged((a, b) for _, a, b in ops))
    return {"busy_s": busy, "scope_seconds": dict(seconds)}


def main(argv: List[str]) -> int:
    trace_dir, out_path = argv
    path = newest_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    try:
        result = summarize(path)
    except ImportError as exc:
        print(f"no protobuf classes for the profile: {exc}", file=sys.stderr)
        return 1
    with open(out_path, "w") as fh:
        json.dump(result, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
