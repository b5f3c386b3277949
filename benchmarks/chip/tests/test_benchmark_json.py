"""BENCHMARK.json against the contract's limits, and every name it uses
against the files that the harness finds by that name."""

import json
import re

import pytest

import spec as SP
from conftest import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
LINE = re.compile(r"^[^\t\n]{1,200}$")


def names():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[sec]:
            yield e["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


def test_keys_and_sizes():
    assert set(BENCH) == TOP
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for w in BENCH["command"]:
        assert LINE.match(w) and not w.startswith("/") and ".." not in w


@pytest.mark.parametrize("name", sorted(set(names())))
def test_name_characters(name):
    assert SP.NAME.match(name), name


def test_units_and_fields():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert SP.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert LINE.match(m["layer"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_name_has_its_files():
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    for w in BENCH["workloads"]:
        mix = json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (CHIP / "drivers" / f"{mix['driver']}.py").is_file()
        assert (CHIP / "checks" / f"{w['name']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= cells


def test_each_cell_reports_what_its_metrics_move():
    spec = SP.Spec(ROOT, BENCH)
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in spec.metrics(w, "end_to_end")}
        layers = spec.metrics(w, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        for m in layers:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_configs_match_the_program():
    spec = SP.Spec(ROOT, BENCH)
    for w in BENCH["workloads"]:
        spec.program_config(w)  # raises where the program departs
