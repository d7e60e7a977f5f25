"""``BENCHMARK.json`` against the rules it is held to, and every name in
it resolving to its file."""

import json
import os
import re

import pytest

import run

SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_keys_and_names(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - KEYS[section] - {"workloads"}
        assert set(e) >= KEYS[section] and not extra, (e["name"], extra)
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        cell = run.cell_spec(w["name"], SPEC)
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_per_layer_metrics_move_one_end_to_end_metric_and_have_a_reader():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(run.load_module(f"metrics/{m['name']}.py").read)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_layers_are_spelt_alike():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(len(x.split()) <= 4 for x in layers)
    lowered = {x.lower() for x in layers}
    assert len(lowered) == len(layers)


def test_cells_configs_traffic_and_limits_resolve():
    used = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        cell = run.cell_spec(w["name"], SPEC)
        cfg = cell["config"]
        used.add(w["config"])
        for part, key in (("data", "data"), ("systems", "family"),
                          ("reference", "reference")):
            assert os.path.exists(os.path.join(run.BENCH, part,
                                               cfg[key] + ".py"))
        assert os.path.exists(os.path.join(
            run.BENCH, "loops", cell["traffic"]["loop"] + ".py"))
        assert set(cell["limits"]) >= set(run.check.ORDER)
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert c["reduced"] == run.load_json(
            os.path.join(run.ROOT, c["file"]))["reduced"]


def test_command_stays_inside_paths():
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    for p in SPEC["paths"]:
        assert not p.startswith("/") and ".." not in p.split("/")
