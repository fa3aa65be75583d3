"""The program's own records, as ``program_trace.py`` reads them: its
spans on a small chip trace recorded by ``record_program_trace.py``, the
readers of ``PROGRAM_METRICS`` on hand-made run records, and whole runs at
a CPU test size against the program and against one shaped like the
program before it kept records."""
import json
import pathlib
from types import SimpleNamespace

import pytest

import program_trace as pt
import record_program_trace
import record_trace
import run
import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
PROGRAM = DATA / "program.xplane.pb"
SMALL = DATA / "small.xplane.pb"
SLICES = sum(n for _t, n in record_program_trace.ADMITS)


@pytest.fixture(scope="module")
def raw():
    return pt.read(str(PROGRAM))


@pytest.fixture(scope="module")
def red(raw):
    return pt.reduce(raw)


def named(raw, name):
    return [s for s in raw["program"] if s[0] == f"kernelet.{name}"]


# --------------------------------------------------------------------- #
# the recorded trace
# --------------------------------------------------------------------- #
def test_spans_carry_their_ids(raw):
    drain, = named(raw, "drain")
    plan, = named(raw, "plan")
    assert drain[3] == {"seq": 2}               # after the warm-up drain
    assert plan[3] == {"planned": 2, "pending": SLICES}
    slices = named(raw, "slice")
    assert len(slices) == SLICES
    per_job = {}
    for s in slices:
        key = (s[3]["tenant"], s[3]["job_id"])
        per_job[key] = per_job.get(key, 0) + 1
    assert sorted(per_job.values()) == sorted(
        n for _t, n in record_program_trace.ADMITS)
    rounds = named(raw, "round")
    assert [r[3]["index"] for r in rounds] == list(range(len(rounds)))
    assert len(named(raw, "decide")) == len(rounds)


def test_step_programs_are_named_by_their_step(raw):
    runs = [n for n, _ in tr.module_runs(raw)]
    tenants = [s[3]["tenant"] for s in named(raw, "slice")]
    assert runs == [f"jit_{t}_step" for t in tenants]


def test_each_program_run_starts_in_its_slice_and_round(raw):
    """The shared clock holds once aligned: the k-th program run starts
    after the k-th slice span opens and before its round ends."""
    runs = sorted(raw["devices"]["/device:TPU:0"]["modules"],
                  key=lambda m: m[1])
    rounds = named(raw, "round")
    for (_n, start, _e), sl in zip(runs, named(raw, "slice")):
        rnd, = [r for r in rounds if r[1] <= sl[1] and sl[2] <= r[2]]
        assert sl[1] <= start < rnd[2]


def test_span_times(raw, red):
    spans = red["program_spans"]
    assert {n: s["count"] for n, s in spans.items()
            if n != "kernelet.round"} == {
        "kernelet.drain": 1, "kernelet.plan": 1,
        "kernelet.decide": spans["kernelet.round"]["count"],
        "kernelet.dispatch": len(named(raw, "dispatch")),
        "kernelet.block": len(named(raw, "block")),
        "kernelet.slice": SLICES}
    for s in spans.values():
        assert 0 <= s["self_s"] <= s["total_s"] + 1e-12
        assert s["max_s"] <= s["total_s"] + 1e-12
    # self times partition the drain span
    drain = spans["kernelet.drain"]["total_s"]
    assert sum(s["self_s"] for s in spans.values()) == pytest.approx(drain)


def test_idle_by_program_span(raw, red):
    whole = tr.reduce(raw)
    idle = sum(whole["idle_by_span"].values())
    by = red["idle_by_program_span"]
    assert set(by) <= set(red["program_spans"])
    assert 0 < sum(by.values()) <= idle
    assert sum(by.values()) <= red["program_spans"]["kernelet.drain"][
        "total_s"]
    # the device waits on the host while it plans
    assert by["kernelet.plan"] > 0


def test_idle_gaps_join_harness_and_program_labels(red):
    labels = [lab for lab, _ in red["idle_gaps"]]
    joined = [lab for lab in labels if ">" in lab]
    assert joined and all(lab.startswith("drain>kernelet.") for lab in joined)
    assert all(lab in ("admit", "drain", "none", "wait_arrival")
               for lab in labels if ">" not in lab)


def test_small_trace_reduces_as_before():
    raw = pt.read(str(SMALL))
    assert raw["program"] == []
    old = tr.reduce(raw)
    assert set(old) == {"window_s", "busy_s", "devices", "modules",
                        "kernels", "device_ops", "idle_gaps",
                        "idle_by_span"}
    assert old["idle_by_span"]["wait_arrival"] > 0.15
    assert old["modules"]["jit_mm"]["count"] == record_trace.MM_RUNS
    new = pt.reduce(raw)
    assert new["program_spans"] == {} and new["idle_by_program_span"] == {}
    assert new["idle_gaps"] == old["idle_gaps"]      # labels unchanged


def test_innermost_and_overlap():
    spans = [("a", 0, 10), ("b", 2, 5), ("c", 6, 8), ("d", 12, 14),
             ("e", 12, 13)]
    pieces = pt.innermost(spans)
    assert pieces == [(0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"),
                      (8, 10, "a"), (12, 13, "e"), (13, 14, "d")]
    assert pt.overlap([(1, 3), (7, 12.5)], pieces) == [
        (1, 2, "a"), (2, 3, "b"), (7, 8, "c"), (8, 10, "a"),
        (12, 12.5, "e")]


# --------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------- #
def job(adm, first, done):
    return SimpleNamespace(admitted_at=adm, first_dispatch=first, done=done)


DRAINS = [
    {"host_s": 1.0, "wall_s": 0.99, "rounds": [],
     "jobs": [job(0.0, 0.1, 0.5), job(0.2, 0.3, 0.9)], "returned_at": 1.0,
     "planned_slices": 2, "pending_slices": 30},
    {"host_s": 1.0, "wall_s": 0.99, "rounds": [],
     "jobs": [job(1.0, 1.5, 1.7)], "returned_at": 2.0,
     "planned_slices": 2, "pending_slices": 10}]
SPANS = {"kernelet.plan": {"count": 4, "total_s": 0.0032},
         "kernelet.decide": {"count": 40, "total_s": 0.0008},
         "kernelet.dispatch": {"count": 80, "total_s": 0.04},
         "kernelet.round": {"count": 40, "total_s": 4.0}}
TRACE = {"window_s": 10.0, "busy_s": 9.7, "devices": 1,
         "program_spans": SPANS,
         "idle_by_program_span": {"kernelet.plan": 0.01,
                                  "kernelet.block": 0.19}}


def record(drains=DRAINS, trace=TRACE):
    s = SimpleNamespace(tenants={}, work={}, kernels={})
    return run.Record(SimpleNamespace(drains=drains, s=s), trace, None)


def read(name, rec):
    return run.load_plugin("metrics", name).read(rec)


@pytest.mark.parametrize("name,value", [
    ("queue_wait_ms", 100.0),             # waits 100, 100, 500
    ("completion_hold_ms", 300.0),        # holds 500, 100, 300
    ("plan_coverage", 10.0),              # 4 of 40
    ("plan_span_ms", 0.8), ("decide_us", 20.0), ("enqueue_ms", 1.0),
    ("idle_program_share", 2.0)])
def test_reader(name, value):
    assert read(name, record()) == pytest.approx(value)


def test_decode_attention_roofline_reads_nothing_on_a_trace_without_it(
        raw):
    """The program trace's decode steps ran before the decode kernel
    existed: their step time reads, the kernel's roofline does not."""
    dense = run.load_plugin("equations", "dense")
    conf = json.loads((pathlib.Path(__file__).resolve().parent / "data"
                       / "tiny-phi3.json").read_text())
    tenants = {"prefill": {"phase": "prefill", "batch": 2, "seq": 64},
               "decode": {"phase": "decode", "batch": 4, "seq": 64}}
    phases = [n[len("jit_"):-len("_step")] for n, _ in tr.module_runs(raw)]
    trace = tr.reduce(raw, run_labels=phases)
    s = SimpleNamespace(
        tenants=tenants,
        work={n: dense.step(conf, t["phase"], t["batch"], t["seq"])
              for n, t in tenants.items()},
        kernels={n: dense.kernels(conf, t["phase"], t["batch"], t["seq"])
                 for n, t in tenants.items()})
    import peaks
    rec = run.Record(SimpleNamespace(drains=DRAINS, s=s), trace,
                     peaks.peaks_for("TPU v5 lite"))
    assert rec.kernel_least_s("decode", "decode_attention") > 0
    assert "decode_attention" not in trace["kernels"]["decode"]
    assert read("decode_step_ms", rec) > 0
    assert read("decode_attention_roofline", rec) is None


@pytest.mark.parametrize("m", pt.PROGRAM_METRICS, ids=lambda m: m["name"])
def test_reader_of_a_parent_shaped_record_reads_nothing(m):
    parent = [{k: d[k] for k in ("host_s", "wall_s", "rounds")}
              for d in DRAINS]
    trace = {k: TRACE[k] for k in ("window_s", "busy_s", "devices")}
    assert read(m["name"], record(parent, trace)) is None
    assert read(m["name"], record(parent, {})) is None


@pytest.mark.parametrize("m", pt.PROGRAM_METRICS, ids=lambda m: m["name"])
def test_program_metric_keeps_the_contract(m):
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
    assert m["moves"] in ("tokens_per_s", "job_p50_ms")
    assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    layers = {x["layer"] for x in bench["per_layer"]}
    assert m["layer"] in layers | {"admission"}


# --------------------------------------------------------------------- #
# whole runs at a CPU test size
# --------------------------------------------------------------------- #
def spec():
    data = pathlib.Path(__file__).resolve().parent / "data"
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"name": "tiny", "chips": 1},
            "config": json.loads((data / "tiny-phi3.json").read_text()),
            "traffic": json.loads((data / "tiny-pd.json").read_text()),
            "end_to_end": bench["end_to_end"], "per_layer": []}


class ParentShaped:
    """The server as it was before it kept records: no ``admit``, and a
    ``drain()`` that returns no job records or plan counts."""

    def __init__(self, srv):
        self._srv = srv

    def __getattr__(self, name):
        if name == "admit":
            raise AttributeError(name)
        return getattr(self._srv, name)

    def drain(self, **kw):
        res = self._srv.drain(**kw)
        return {k: res[k] for k in ("rounds", "wall_s", "predicted_gain",
                                    "plan")}


def parent_shaped(session):
    session.srv = ParentShaped(session.srv)


COUNTERS = ("queue_wait_ms", "completion_hold_ms", "plan_coverage")


def test_run_reads_the_program_records():
    out, win = pt.run_cell(spec(), 2**31 + 5, 1.0, False, on_chip=False)
    assert out["correct"], out["checks"]
    split = pt.job_split(win)
    assert split["jobs"] == len(win.jobs) == out["attempted"]
    assert abs(split["latency_gap_ms"]) < 1.0
    assert split["latency_mean_ms"] == pytest.approx(
        split["wait_mean_ms"] + split["service_mean_ms"]
        + split["hold_mean_ms"])
    rec = record(win.drains, {})
    for name in COUNTERS:
        assert read(name, rec) is not None, name
    assert read("plan_coverage", rec) < 100.0     # the plan's stale sizes


def test_run_of_a_parent_shaped_program_lacks_the_new_metrics():
    out, win = pt.run_cell(spec(), 2**31 + 6, 1.0, False, on_chip=False,
                           fault=parent_shaped)
    assert out["correct"], out["checks"]
    split = pt.job_split(win)
    assert set(out["metrics"]) == {"tokens_per_s", "job_p50_ms",
                                   "job_p95_ms", "setup_s"}
    assert "jobs" not in split and split["harness_p50_ms"] > 0
    rec = record(win.drains, {})
    for name in COUNTERS:
        assert read(name, rec) is None, name
