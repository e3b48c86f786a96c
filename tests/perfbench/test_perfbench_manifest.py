"""BENCHMARK.json and the data files against the benchmark's contract."""

import json
import os
import re

import pytest

from perfbench import manifest

BENCH = manifest.benchmark()
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_no_problem_found():
    assert manifest.problems() == []


def test_top_level_keys_and_limits():
    assert set(BENCH) - {"_path"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(BENCH["_path"]) <= 64 * 1024
    assert BENCH["paths"] == ["perfbench", "tests/perfbench"]
    assert not any(w.startswith("/") or ".." in w for w in BENCH["command"])


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    assert m["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "moves" in m:
        allowed |= {"layer", "moves"}
        e2e = {e["name"]: e for e in BENCH["end_to_end"]}
        assert m["moves"] in e2e
        for w in m.get("workloads", [c["name"] for c in BENCH["workloads"]]):
            assert m["moves"] in manifest.metric_names(BENCH, w, "end_to_end")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    else:
        allowed |= {"bound"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert set(m) <= allowed
    spec = manifest.metric(m["name"])
    assert callable(manifest.reducer(spec["reducer"]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    cell = manifest.cell(w["name"])
    assert cell["traffic"]["loop"] in ("open", "closed")
    assert all(v is not None for v in cell["params"].values())
    e2e = manifest.metric_names(BENCH, w["name"], "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    want = "out_tok_s" if cell["traffic"]["loop"] == "closed" \
        else "ttft_p50_ms"
    assert want in e2e


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("perfbench/") and len(c["reduced"]) <= 16
    with open(os.path.join(manifest.ROOT, c["file"])) as fh:
        data = json.load(fh)
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size")
    assert not set(c["reduced"]) & set(widths)
    assert all(isinstance(data[k], int) for k in widths)
    # every server setting carries its reason
    assert set(data["server"]["env"]) == set(data["server"]["why"])
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_a_broken_manifest_is_noticed(monkeypatch):
    bad = json.loads(json.dumps({k: v for k, v in BENCH.items()
                                 if k != "_path"}))
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["per_layer"][1]["unit"] = "tokens per second"
    bad["workloads"][0]["traffic"] = "no-such-mix"
    monkeypatch.setattr(manifest, "benchmark", lambda: bad)
    found = "\n".join(manifest.problems())
    assert "moves 'no_such_metric'" in found
    assert "bad unit" in found and "no-such-mix" in found


def test_a_cell_is_added_without_editing_a_file(monkeypatch):
    """A later PR's cell is one ``workloads`` entry and its name in the
    ``workloads`` list of each metric it reports, all in BENCHMARK.json:
    no file under ``paths`` repeats those lists (or a bound), so none has
    to be edited."""
    more = json.loads(json.dumps({k: v for k, v in BENCH.items()
                                  if k != "_path"}))
    new = "qwen2.5-7b-l14.decode-heavy"  # files that exist, paired anew
    more["workloads"].append({
        "name": new, "config": "qwen2.5-7b-l14", "traffic": "decode-heavy",
        "chips": 1, "why": "a pairing no file knows of"})
    for m in more["end_to_end"] + more["per_layer"]:
        if "qwen2.5-1.5b.decode-heavy" in m.get("workloads", []):
            m["workloads"].append(new)
    more["end_to_end"][0]["bound"] = 0.05
    monkeypatch.setattr(manifest, "benchmark", lambda: more)
    assert manifest.problems() == []
    assert manifest.metric_names(more, new, "end_to_end") == [
        "out_tok_s", "setup_s"]
    assert manifest.cell(new)["params"]["clients"] == 320
    for name in os.listdir(os.path.join(manifest.HERE, "metrics")):
        with open(os.path.join(manifest.HERE, "metrics", name)) as fh:
            assert not {"workloads", "bound"} & set(json.load(fh)), name


def test_files_under_paths_use_only_name_characters():
    for path in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), manifest.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
