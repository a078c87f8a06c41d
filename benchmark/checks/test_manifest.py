"""BENCHMARK.json against the contract's character rules, and every
name in it against the files the harness will look for."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmark"
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024
    assert all(LINE.match(w) for w in M["command"])


def test_names_units_and_lines():
    metrics = M["end_to_end"] + M["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert LINE.match(m["layer"])
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]) \
            and LINE.match(c["why"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)


def test_every_name_resolves_to_a_file():
    configs = {c["name"]: c for c in M["configs"]}
    assert {w["config"] for w in M["workloads"]} == set(configs)
    for c in configs.values():
        sizes = json.loads((ROOT / c["file"]).read_text())
        assert sizes["source"] == c["source"]
        assert sizes["reduced"] == c["reduced"]
        assert (BENCH / "configs" / f"{sizes['module']}.py").is_file()
    for w in M["workloads"]:
        assert (BENCH / "mixes" / f"{w['traffic']}.json").is_file()
    for m in M["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_what_its_layer_metrics_move():
    cells = [w["name"] for w in M["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in M["end_to_end"]}
    assert e2e["setup_s"] == cells
    for m in M["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])
