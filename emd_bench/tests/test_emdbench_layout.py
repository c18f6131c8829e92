"""BENCHMARK.json and the files it names: every cell, configuration, mix, limit and metric
is found by name, as the harness finds them."""
import json
import re

import pytest

import emdbench_tiny as tiny

BENCH = tiny.bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_and_metrics(name):
    cell = tiny.load(name)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert (tiny.harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert tiny.harness.load_module(tiny.harness.BENCH / "traffic"
                                    / f"{cell.traffic['loop']}.py")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configs_state_what_was_cut(entry):
    cfg = json.loads((tiny.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in cfg["reduced"]:
        assert key in cfg["published"] and cfg[key] != cfg["published"][key]
    assert cfg["engine"]["precision"] == "f32"
