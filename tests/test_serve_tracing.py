"""SharedPodServer's own records: job records from ``admit`` to the wait
that covered a job's last slice, and the ``kernelet.*`` profiler spans of
a drain, read back from the profiler's trace (CPU, reduced config, a
prefill and a decode tenant)."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.profiles import V5E

ADMITS = [("p", 2), ("d", 3), ("p", 1), ("d", 1), ("p", 4)]
SUBMITTED = {"p": 1, "d": 2}       # each submit's slices: its first job


def spans_of(trace_dir: str) -> list:
    """(name, start_ns, end_ns, stats) of every ``kernelet.*`` span."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats))
                    for ev in line.events if ev.name.startswith("kernelet.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.launch.serve import Job, SharedPodServer
    srv = SharedPodServer(device_kind=V5E)
    srv.submit(Job("p", "phi3-mini-3.8b", "prefill", SUBMITTED["p"], 1, 32))
    srv.submit(Job("d", "phi3-mini-3.8b", "decode", SUBMITTED["d"], 2, 32))
    ids = [srv.admit(t, n) for t, n in ADMITS]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        res = srv.drain()
    finally:
        jax.profiler.stop_trace()
    return srv, res, ids, spans_of(trace_dir)


def named(spans, name):
    return [s for s in spans if s[0] == f"kernelet.{name}"]


def test_spans_nest(served):
    _srv, res, _ids, spans = served
    drain, = named(spans, "drain")
    assert drain[3]["seq"] == 1
    for s in named(spans, "plan") + named(spans, "round"):
        assert inside(s, drain), s
    rounds = named(spans, "round")
    assert [r[3]["index"] for r in rounds] == list(range(len(res["rounds"])))
    for stage in ("decide", "dispatch", "block", "slice"):
        for s in named(spans, stage):
            assert any(inside(s, r) for r in rounds), (stage, s)
    assert len(named(spans, "decide")) == len(rounds)
    assert not named(spans, "control")          # no daemon, no control
    for s in named(spans, "slice"):
        assert any(inside(s, d) for d in named(spans, "dispatch"))


def test_one_slice_span_per_slice_run(served):
    _srv, res, ids, spans = served
    ran = {"p": 0, "d": 0}
    for k1, k2, n1, n2, _cp in res["rounds"]:
        ran[k1] += n1
        if k2 is not None:
            ran[k2] += n2
    slices = named(spans, "slice")
    assert {t: sum(s[3]["tenant"] == t for s in slices) for t in ran} == ran
    assert ran == {t: SUBMITTED[t] + sum(n for u, n in ADMITS if u == t)
                   for t in ran}
    # every slice is one admitted job's, and a job's slices carry its id
    per_job = {}
    for s in slices:
        per_job[s[3]["job_id"]] = per_job.get(s[3]["job_id"], 0) + 1
    assert per_job == {j.job_id: j.slices for j in res["jobs"]}
    assert set(ids) < set(per_job)


def test_jobs_complete_in_admission_order(served):
    _srv, res, ids, _spans = served
    assert len(res["jobs"]) == len(ADMITS) + len(SUBMITTED)
    for tenant in SUBMITTED:
        mine = [j for j in res["jobs"] if j.tenant == tenant]
        assert [j.job_id for j in mine] == sorted(j.job_id for j in mine)
        assert [j.done for j in mine] == sorted(j.done for j in mine)
        assert [j.first_dispatch for j in mine] == sorted(
            j.first_dispatch for j in mine)
    by_id = {j.job_id: j for j in res["jobs"]}
    assert [(by_id[i].tenant, by_id[i].slices) for i in ids] == ADMITS


def test_job_times_are_ordered_and_slices_add_up(served):
    srv, res, _ids, _spans = served
    for j in res["jobs"]:
        assert j.admitted_at <= j.first_dispatch <= j.done <= \
            res["returned_at"]
        assert j.left == 0
    admitted = sum(SUBMITTED.values()) + sum(n for _t, n in ADMITS)
    assert sum(j.slices for j in res["jobs"]) == admitted
    assert all(not q for q in srv._queue.values())    # nothing held


def test_plan_counts(served):
    srv, res, _ids, spans = served
    admitted = sum(SUBMITTED.values()) + sum(n for _t, n in ADMITS)
    assert res["pending_slices"] == admitted
    # the plan replays each tenant's profile, sized at submit
    assert res["planned_slices"] == sum(SUBMITTED.values()) == sum(
        p.num_blocks for p in srv.profiles.values())
    plan, = named(spans, "plan")
    assert plan[3] == {"planned": res["planned_slices"],
                       "pending": res["pending_slices"]}


def test_step_programs_carry_their_names(served):
    srv, *_ = served
    for name, step in (("p", "prefill_step"), ("d", "decode_step")):
        assert srv._exec[name].as_text().startswith(f"HloModule jit_{step}")


def test_untraced_drain_of_anonymous_slices_runs_alike(served):
    """Slices written into num_slices directly, with no profiler: the same
    rounds and outputs as the traced drain of admitted jobs, and no job
    record."""
    srv, res, _ids, _spans = served
    first = {n: np.asarray(o) for n, o in srv.outputs.items()}
    for t, n in SUBMITTED.items():
        srv.jobs[t].num_slices += n
    for t, n in ADMITS:
        srv.jobs[t].num_slices += n
    again = srv.drain()
    assert again["rounds"] == res["rounds"]
    assert again["jobs"] == []
    for n, o in srv.outputs.items():
        np.testing.assert_array_equal(np.asarray(o), first[n])


def test_admit_checks_its_job(served):
    srv, *_ = served
    with pytest.raises(KeyError, match="no submitted tenant"):
        srv.admit("nobody", 1)
    with pytest.raises(ValueError, match="needs a slice"):
        srv.admit("p", 0)


def test_daemon_drain_has_a_control_span_per_round(served, tmp_path):
    from repro.runtime.daemon import ServingDaemon
    srv, *_ = served
    srv.admit("p", 2)
    srv.admit("d", 2)
    dmn = ServingDaemon(str(tmp_path / "serve.sqlite"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        res = srv.drain(daemon=dmn, plan_first=False)
    finally:
        jax.profiler.stop_trace()
        dmn.close()
    spans = spans_of(str(tmp_path / "trace"))
    assert res["state"] == "finished" and res["planned_slices"] == 0
    assert len(res["jobs"]) == 2 and res["returned_at"] >= max(
        j.done for j in res["jobs"])
    controls, rounds = named(spans, "control"), named(spans, "round")
    assert len(controls) == len(rounds) == len(res["rounds"])
    assert all(inside(c, r) for c, r in zip(controls, rounds))
