"""BENCHMARK.json keeps the benchmark's contract, and every name in it
finds its file: a configuration, a traffic mix, its loop kind, a metric's
reader."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks" / "chip"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_check_fits_the_day_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_and_units():
    items = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + \
        BENCH["per_layer"]
    for it in items:
        assert NAME.match(it["name"]), it["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[kind]]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    for text in (conf["source"], conf["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    path = ROOT / conf["file"]
    assert path.is_relative_to(CHIP / "configs")
    body = json.loads(path.read_text())
    for key in conf["reduced"]:
        assert key in body and key in body.get("changed", {}), key
    assert body["limits"]["prefill_max_logit_gap"] > 0
    assert any(w["config"] == conf["name"] for w in CELLS.values())


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_finds_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    mix = json.loads((CHIP / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    assert (CHIP / "loops" / f"{mix['loop']}.py").is_file()
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    per_layer = [m for m in BENCH["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in E2E and len(E2E) >= 2 and per_layer
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
    assert m["moves"] in E2E and m["moves"] != "setup_s"
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert all(w in CELLS for w in m.get("workloads", []))
    assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"
