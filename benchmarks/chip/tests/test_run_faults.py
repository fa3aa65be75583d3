"""A whole run at a CPU test size, the chip check skipped: sound, it comes
out correct; with the timed path broken underneath, it does not."""
import json
import pathlib

import jax.numpy as jnp
import pytest

import run

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def spec(config="tiny-phi3"):
    return {"cell": {"name": "tiny", "chips": 1},
            "config": json.loads((DATA / f"{config}.json").read_text()),
            "traffic": json.loads((DATA / "tiny-pd.json").read_text()),
            "end_to_end": BENCH["end_to_end"], "per_layer": []}


def wrap_outputs(session, alter):
    """Break every tenant's step where its logits are produced."""
    execs = session.srv._exec
    for name, fn in list(execs.items()):
        execs[name] = (lambda f: lambda *a: alter(f(*a)))(fn)


def token_altered(s):
    # the first position of every output now ranks the worst token first
    def alter(o):
        return o.at[(0,) * (o.ndim - 1)].multiply(-1)
    wrap_outputs(s, alter)


def half_batch_left_out(s):
    # the second half of each slice's batch is never computed
    def alter(o):
        return o.at[o.shape[0] // 2:].set(0)
    wrap_outputs(s, alter)


def control_in_place(s):
    """The reference in fp8 put in every prefill tenant's place."""
    ref = s.eq.Reference(s.config)
    for name, t in s.tenants.items():
        if t["phase"] == "prefill":
            s.srv._exec[name] = lambda params, batch: ref.prefill_logits(
                params, batch["tokens"], quant="fp8")


def output_reused(s):
    # every second slice of a tenant returns the slice before's output
    def reuse(f):
        state = {"n": 0, "out": None}

        def call(*a):
            state["n"] += 1
            if state["n"] % 2 == 0 and state["out"] is not None:
                return state["out"]
            state["out"] = f(*a)
            return state["out"]
        return call
    execs = s.srv._exec
    for name, fn in list(execs.items()):
        execs[name] = reuse(fn)


def interleaved_slices_altered(s):
    # a slice dispatched right after another tenant's (as a paired round
    # interleaves them) ranks the worst token first at one position
    last = {"name": None}
    execs = s.srv._exec

    def wrap(name, f):
        def call(*a):
            out = f(*a)
            prev, last["name"] = last["name"], name
            if prev in (None, name):
                return out
            return out.at[(0,) * (out.ndim - 1)].multiply(-1)
        return call
    for name, fn in list(execs.items()):
        execs[name] = wrap(name, fn)


def slices_skipped(s):
    # every third slice of a tenant is not run: the drain is handed the
    # tenant's last output instead
    srv, run_one, seen = s.srv, s.srv._run, {}

    def skip(name):
        seen[name] = seen.get(name, 0) + 1
        if seen[name] % 3 == 0 and name in srv.outputs:
            return srv.outputs[name]
        return run_one(name)
    srv._run = skip


@pytest.mark.parametrize("config", ["tiny-phi3", "tiny-stablelm"])
def test_sound_run_is_correct(config):
    out = run.run_cell(spec(config), 2**31 + 3, 1.0, False, on_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "job_p50_ms",
                                   "job_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def prefill_closed():
    """The prefill-closed mix at a CPU test size."""
    mix = json.loads((run.HERE / "traffic" / "prefill-closed.json")
                     .read_text())
    for t in mix["tenants"]:
        t.update(seq=64, clients=2)
    return {**spec(), "traffic": mix}


def test_two_prefill_tenants_run_correct():
    """Two prefill tenants share the weights and both are checked."""
    out = run.run_cell(prefill_closed(), 2**31 + 10, 1.0, False,
                       on_chip=False)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {
        f"{n}.{c}" for n in ("prefill-a", "prefill-b")
        for c in ("max_logit_gap", "slices_not_run")}


@pytest.mark.parametrize("fault", [token_altered, half_batch_left_out,
                                   control_in_place, output_reused,
                                   interleaved_slices_altered,
                                   slices_skipped])
def test_broken_timed_path_is_not_correct(fault):
    out = run.run_cell(spec(), 2**31 + 4, 1.0, False, on_chip=False,
                       fault=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [token_altered, half_batch_left_out,
                                   control_in_place, output_reused,
                                   interleaved_slices_altered,
                                   slices_skipped])
def test_broken_prefill_closed_is_not_correct(fault):
    out = run.run_cell(prefill_closed(), 2**31 + 11, 1.0, False,
                       on_chip=False, fault=fault)
    assert not out["correct"], out["checks"]


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        run.run_cell(spec(), 1, 1.0, False)
    assert capsys.readouterr().out == ""


class FakeServer:
    def __init__(self, tenants):
        self._exec = {n: (lambda n: lambda *a: f"out-{n}")(n)
                      for n in tenants}
        self._args = {n: None for n in tenants}


def test_sample_sorts_outputs_by_round_kind():
    from types import SimpleNamespace
    tenants = {"p": {"phase": "prefill", "batch": 2, "seq": 64},
               "d": {"phase": "decode", "batch": 4, "seq": 64}}
    srv = FakeServer(tenants)
    session = SimpleNamespace(tenants=tenants, srv=srv,
                              pools={"p": ["p0", "p1"], "d": ["d0", "d1"]})
    win = run.Window(session, 1.0, 5, None)
    caps = win._caps()
    assert caps == {"p": 2, "d": 64}
    # a drain: p and d paired (2 and 3 slices), then d alone (4 slices)
    win._pick = {"p": {0, 1}, "d": {0, 2, 3, 6}}
    for name in ["p", "d", "p", "d", "d", "d", "d", "d", "d"]:
        srv._exec[name]()
    assert srv._args == {"p": "p0", "d": "d1"}      # 2 and 7 slices run
    win._keep([("p", "d", 2, 3, 0.1), ("d", None, 4, 0, 0.0)])
    kinds = {k: sorted(pool for _, pool, _ in v)
             for k, v in win.kept.items()}
    assert kinds == {("p", True): [0, 1], ("d", True): [0, 0],
                     ("d", False): [0, 1]}
    assert win.ran == {"p": 2, "d": 7}
