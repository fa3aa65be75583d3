"""A configuration brings its own equations: a new architecture is new
files and entries, and set-up fails where a configuration names no
equations module or the program departs from the one it names."""
import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import shutil
import sys

import pytest

import run

DATA = pathlib.Path(__file__).resolve().parent / "data"

# A new architecture's equations module, as a test double: the dense
# equations, each call recorded.
PROBE = '''"""Test double: the dense equations, each call recorded."""
import importlib.util
import pathlib
import sys

_spec = importlib.util.spec_from_file_location(
    "probe_dense", pathlib.Path(__file__).with_name("dense.py"))
dense = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = dense
_spec.loader.exec_module(dense)
CALLS = []


def check(cfg, config):
    CALLS.append("check")
    return dense.check(cfg, config)


def Reference(config):
    CALLS.append("Reference")
    return dense.Reference(config)


def step(config, phase, batch, seq):
    CALLS.append("step")
    return dense.step(config, phase, batch, seq)


def param_count(config):
    CALLS.append("param_count")
    return dense.param_count(config)


def kernels(config, phase, batch, seq):
    CALLS.append("kernels")
    return dense.kernels(config, phase, batch, seq)
'''


def tiny_spec(config: dict) -> dict:
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": json.loads((DATA / "tiny-pd.json").read_text()),
            "end_to_end": bench["end_to_end"], "per_layer": []}


def digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_architecture_needs_only_new_files(tmp_path):
    """In a copy of the benchmark, add an equations module, a
    configuration naming it, a traffic mix and the entries for them; a
    cell of it, resolved by name, runs through the module and comes out
    correct, and no file that was there changed."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(run.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = digests(tmp_path)

    (chip / "equations" / "probe.py").write_text(PROBE)
    conf = json.loads((DATA / "tiny-phi3.json").read_text())
    conf["name"] = "tiny-probe"
    conf["program"]["equations"] = "probe"
    (chip / "configs" / "tiny-probe.json").write_text(json.dumps(conf))
    shutil.copy(DATA / "tiny-pd.json", chip / "traffic" / "tiny-pd.json")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-probe", "source": conf["source"],
        "file": "benchmarks/chip/configs/tiny-probe.json", "reduced": [],
        "why": "a CPU test size"})
    bench["workloads"].append({
        "name": "tiny-probe.pd", "config": "tiny-probe",
        "traffic": "tiny-pd", "chips": 1, "why": "a CPU test size"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = importlib.util.spec_from_file_location("run_in_copy",
                                                  chip / "run.py")
    copy = importlib.util.module_from_spec(spec)
    saved_path = list(sys.path)
    try:
        spec.loader.exec_module(copy)
        seen = []
        out = copy.run_cell(copy.resolve("tiny-probe.pd"), 2**31 + 7, 1.0,
                            False, on_chip=False, fault=seen.append)
    finally:
        sys.path[:] = saved_path
    assert out["correct"], out["checks"]
    eq = seen[0].eq
    assert pathlib.Path(eq.__file__) == chip / "equations" / "probe.py"
    assert {"check", "step", "kernels", "Reference"} <= set(eq.CALLS)
    after = digests(tmp_path)
    assert {k: after[k] for k in before} == before | {
        "BENCHMARK.json": after["BENCHMARK.json"]}
    kept = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][:-1] == kept["configs"]
    assert bench["workloads"][:-1] == kept["workloads"]
    assert {k: bench[k] for k in bench if k not in ("configs", "workloads")} \
        == {k: kept[k] for k in kept if k not in ("configs", "workloads")}


def test_configuration_naming_no_equations_fails_at_setup():
    conf = json.loads((DATA / "tiny-phi3.json").read_text())
    del conf["program"]["equations"]
    with pytest.raises(RuntimeError, match="names no equations module"):
        run.run_cell(tiny_spec(conf), 2**31 + 8, 1.0, False, on_chip=False)


def test_configuration_naming_a_missing_module_fails_at_setup():
    conf = json.loads((DATA / "tiny-phi3.json").read_text())
    conf["program"]["equations"] = "no-such-architecture"
    with pytest.raises(FileNotFoundError, match="no equations file"):
        run.run_cell(tiny_spec(conf), 2**31 + 8, 1.0, False, on_chip=False)


def test_program_departing_from_its_equations_fails_at_setup(monkeypatch):
    """A program config with sparse experts under the dense equations."""
    import repro.configs as configs
    real = configs.get_config

    def with_experts(arch):
        return dataclasses.replace(real(arch), moe=configs.MoEConfig(
            num_experts=8, top_k=2, d_ff_expert=64))
    monkeypatch.setattr(configs, "get_config", with_experts)
    conf = json.loads((DATA / "tiny-phi3.json").read_text())
    with pytest.raises(RuntimeError, match="departs from the file.*moe"):
        run.run_cell(tiny_spec(conf), 2**31 + 9, 1.0, False, on_chip=False)
