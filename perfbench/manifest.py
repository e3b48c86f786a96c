"""Finds a cell's files by the names in ``BENCHMARK.json``, and checks
the manifest and the data files against the benchmark's contract.

    BENCHMARK.json  workloads[name] -> config, traffic, chips
    perfbench/configs/<config>.json     sizes, server settings, reference
    perfbench/traffic/<traffic>.json    the mix
    perfbench/workloads/<name>.json     params of the pairing (optional)
    perfbench/metrics/<metric>.json     reducer, arguments, moves
    perfbench/reducers/<reducer>.py     reduce(ctx, **args)
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(*parts)
    with open(path) as fh:
        data = json.load(fh)
    data["_path"] = path
    return data


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT, "BENCHMARK.json")


def metric_names(bench: Dict[str, Any], workload: str, kind: str
                 ) -> List[str]:
    """Names of the ``kind`` (end_to_end | per_layer) metrics that
    ``workload`` reports: those that list it, or list nothing."""
    return [
        m["name"] for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def cell(workload: str) -> Dict[str, Any]:
    """Everything one run of ``workload`` needs, from the data files."""
    bench = benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r}; known: {known}")
    entry = entries[0]
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    params = dict(traffic.get("params", {}))
    pairing = os.path.join(HERE, "workloads", workload + ".json")
    if os.path.exists(pairing):
        params.update(load_json(pairing).get("params", {}))
    missing = [k for k, v in params.items() if v is None]
    if missing:
        raise KeyError(f"{workload}: params {missing} are set nowhere")
    return {
        "bench": bench, "entry": entry, "traffic": traffic, "params": params,
        "config": load_json(HERE, "configs", entry["config"] + ".json"),
    }


def metric(name: str) -> Dict[str, Any]:
    return load_json(HERE, "metrics", name + ".json")


def reducer(name: str) -> Any:
    return importlib.import_module(f"perfbench.reducers.{name}").reduce


def problems() -> List[str]:
    """Every way the manifest or a data file breaks the contract; empty
    when all is well.  (The driver checks BENCHMARK.json itself; this
    also checks that the data files agree with it.)  Which cells report
    a metric, and an end-to-end metric's bound, stand in BENCHMARK.json
    alone: a later cell is one entry there and its name in those lists,
    and touches no file that exists."""
    out: List[str] = []
    bench = benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            if not NAME.match(w[key]):
                out.append(f"workload {w['name']}: bad {key} {w[key]!r}")
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            out.append(f"workload {w['name']}: why is {len(w['why'])} long")
        try:
            cell(w["name"])
        except (OSError, KeyError, ValueError) as exc:
            out.append(f"workload {w['name']}: {exc}")
        reported = metric_names(bench, w["name"], "end_to_end")
        if len(reported) < 2 or "setup_s" not in reported:
            out.append(f"workload {w['name']}: reports {reported}")
        if not metric_names(bench, w["name"], "per_layer"):
            out.append(f"workload {w['name']}: no per-layer metric")
    for c in bench["configs"]:
        data = load_json(ROOT, c["file"])
        if data["source"] != c["source"] or data["reduced"] != c["reduced"]:
            out.append(f"config {c['name']}: file and manifest disagree")
        for key in c["reduced"]:
            if key.endswith(("_dim", "_rank", "_size")):
                out.append(f"config {c['name']}: {key} is a width")
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            name = m["name"]
            if not NAME.match(name):
                out.append(f"metric {name!r}: bad name")
            if not UNIT.match(m["unit"]):
                out.append(f"metric {name}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {name}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"metric {name}: source={m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"metric {name}: unknown workload {w}")
            try:
                data = metric(name)
                reducer(data["reducer"])
            except (OSError, KeyError, ImportError, AttributeError) as exc:
                out.append(f"metric {name}: {exc!r}")
                continue
            for key in ("unit", "better", "source", "layer", "moves"):
                if data.get(key) != m.get(key):
                    out.append(f"metric {name}: {key} differs in its file")
            if kind == "end_to_end":
                if not 0 < m["bound"] <= 0.1:
                    out.append(f"metric {name}: bound {m['bound']}")
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"metric {name}: source {m['source']}")
                continue
            if m["moves"] not in e2e:
                out.append(f"metric {name}: moves {m['moves']!r}")
                continue
            for w in m.get("workloads", list(cells)):
                if m["moves"] not in metric_names(bench, w, "end_to_end"):
                    out.append(
                        f"metric {name}: {w} does not report {m['moves']}"
                    )
    return out
