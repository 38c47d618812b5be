"""BENCHMARK.json resolves to the benchmark's files, and every entry keeps
to the form its readers expect: names, units, bounds, sources and the
run length a full check of 24 cells can afford."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(DOC["paths"]) <= 16
    for p in DOC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(DOC["command"]) <= 32
    assert all(_line(word) for word in DOC["command"])
    script = DOC["command"][1]
    assert any(script.startswith(p + "/") for p in DOC["paths"])
    assert (ROOT / script).is_file()


def test_run_seconds_fit_a_full_check():
    rs = DOC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 180 + 1200 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    entries = DOC[section]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                            "higher")
        if "why" in e:
            assert _line(e["why"]), e["name"]


def test_configs():
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in DOC["workloads"]}
    for c in DOC["configs"]:
        assert c["name"] in used and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in DOC["paths"])
        doc = json.loads((ROOT / c["file"]).read_text())
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16


def test_cells():
    configs = {c["name"] for c in DOC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)
    for w in DOC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        mix = json.loads(
            (ROOT / "bench" / "workloads" / f"{w['name']}.json").read_text())
        assert mix["config"] == w["config"] and mix["traffic"] == w["traffic"]


def test_metrics():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    cells = [w["name"] for w in DOC["workloads"]]
    assert "setup_s" in e2e
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in DOC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in DOC["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_resolves(cell):
    """Configuration, traffic mix, kernels and metric readers of each cell
    are files the harness finds by name."""
    c = spec.load_cell(cell)
    assert c.kernels and c.end_to_end and c.per_layer
    assert {m.name for m in c.end_to_end} >= {"setup_s"}
    assert all(callable(m.read) for m in c.end_to_end + c.per_layer)
    w = c.workload
    assert w["job_memories"] >= 1 and w["batch"] >= 1
    assert "pool_jobs" not in w
