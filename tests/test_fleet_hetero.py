"""Heterogeneous-fleet tier: ``run_fleet`` over per-lane ``GPUSpec``s.

PR 7's contract has three legs, each pinned here:

* **Generality never buys different results** — a fleet of N *identical*
  specs through the heterogeneous path is bit-identical (totals, event
  log, completions) to the scalar-``gpu`` homogeneous path for all six
  policies, and mixed-spec lanes match the scalar
  ``run_policy_reference`` oracle on their own spec/table.
* **The bugfix satellites stay fixed** — empty lanes (``n_gpus >
  len(order)``) replay to zero without crashing or skewing the pooled
  latency; per-lane MC streams are ``SeedSequence.spawn``-derived (no
  ``seed + g`` collisions); the least-backlog service predictor is
  memoized module-wide (no Markov re-solves per ``assign``).
* **Isolation is structural** — per-spec decision stores never replay
  another spec's decisions, and the engine charges a mixed fleet in
  grouped vectorized batches (one table group per distinct spec).
"""
import dataclasses
import os

import numpy as np
import pytest

try:                                        # degrade gracefully without it:
    from hypothesis import given, settings, strategies as st
except ImportError:                         # the == pins below still run
    st = None

from repro.core import markov
from repro.core.engine import (_SERVICE_MEMO, DealPolicy, LeastBacklogDeal,
                               WorkloadEngine, aggregate_latency, run_fleet)
from repro.core.profiles import (V5E, C2050, GPUSpec, KernelProfile,
                                 content_digest)
from repro.core.queue import run_policy_reference
from repro.core.scheduler import _decision_store_at
from repro.core.simulator import IPCTable

GPU = C2050
VG = GPU.virtual()
ROUNDS = 400
ALL_POLICIES = ["BASE", "KERNELET", "OPT", "MC", "EDF-KERNELET", "PWAIT-CP"]
FAST = dataclasses.replace(C2050, name="C2050-2x", n_sm=C2050.n_sm * 2)
SLOW = dataclasses.replace(C2050, name="C2050-half", n_sm=C2050.n_sm // 2)


def prof(name, rm, coal=1.0, dep=0.0, blocks=512, ipb=200.0, occ=1.0,
         pur=0.5, mur=0.1):
    return KernelProfile(name, rm=rm, coal=coal, insns_per_block=ipb,
                         num_blocks=blocks, occupancy=occ, pur=pur,
                         mur=mur, dep_ratio=dep)


@pytest.fixture(scope="module")
def profiles():
    return {
        "CA": prof("CA", 0.05, pur=0.9, mur=0.02, blocks=60),
        "CB": prof("CB", 0.08, dep=0.15, pur=0.6, mur=0.05, blocks=40,
                   ipb=150.0),
        "MA": prof("MA", 0.4, coal=0.3, pur=0.1, mur=0.25, blocks=80,
                   ipb=300.0),
        "MB": prof("MB", 0.3, pur=0.2, mur=0.2, blocks=50, ipb=250.0),
    }


@pytest.fixture()
def no_persist(monkeypatch):
    monkeypatch.setenv("REPRO_IPC_CACHE", "0")


@pytest.fixture()
def truth():
    return IPCTable(VG, rounds=ROUNDS, persist=False)


ORDER = ["CA", "MA", "CB", "MB"] * 2
TIMED = [i * 5e4 for i in range(len(ORDER))]


def assert_lane_equal(a, b, ctx):
    assert a.total_cycles == b.total_cycles, ctx
    assert a.n_coschedules == b.n_coschedules, ctx
    assert a.n_slices == b.n_slices, ctx
    assert a.time_line == b.time_line, ctx
    assert a.completions == b.completions, ctx


# ------------------------------------------------------------------ #
# identical specs == homogeneous: the heterogeneous path may not move
# a single bit for fleets that are not actually heterogeneous
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_identical_specs_bit_identical_to_homogeneous(policy, profiles,
                                                      truth, no_persist):
    # equal-but-distinct spec objects: content equality, not identity,
    # must drive the table sharing
    copies = [dataclasses.replace(GPU) for _ in range(3)]
    for arrivals, slo in ((None, None), (TIMED, 4e5)):
        homo = run_fleet(policy, profiles, ORDER, GPU, truth, 3, seed=2,
                         arrivals=arrivals, slo_deadline=slo)
        het = run_fleet(policy, profiles, ORDER, copies, truth, seed=2,
                        arrivals=arrivals, slo_deadline=slo)
        for g, (a, b) in enumerate(zip(homo.lanes, het.lanes)):
            assert_lane_equal(a, b, (policy, g, arrivals is not None))
        assert homo.makespan == het.makespan, policy
        assert homo.total_cycles == het.total_cycles, policy
        assert homo.latency == het.latency, policy
        assert homo.deal == het.deal, policy
        assert [s.name for s in het.gpus] == [GPU.name] * 3


# ------------------------------------------------------------------ #
# mixed specs == per-lane scalar oracle on each lane's own spec/table
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("policy", ["BASE", "KERNELET", "OPT"])
def test_mixed_specs_match_scalar_reference(policy, profiles, truth,
                                            no_persist):
    specs = [FAST, GPU, SLOW]
    fleet = run_fleet(policy, profiles, ORDER, specs, truth,
                      deal="round_robin")
    assert [s.name for s in fleet.gpus] == [s.name for s in specs]
    for g, spec in enumerate(specs):
        lane_order = ORDER[g::len(specs)]
        ref = run_policy_reference(
            policy, profiles, lane_order, spec,
            IPCTable(spec.virtual(), rounds=ROUNDS, persist=False))
        got = fleet.lanes[g]
        assert got.total_cycles == ref.total_cycles, (policy, g)
        assert got.time_line == ref.time_line, (policy, g)
        assert got.n_coschedules == ref.n_coschedules, (policy, g)
    # the specs genuinely differ: a 4x SM spread must not produce three
    # equal lane totals on identical per-lane streams
    totals = {fleet.lanes[g].total_cycles for g in range(3)}
    assert len(totals) == 3, totals


# ------------------------------------------------------------------ #
# empty-lane regression: n_gpus > len(order) must not crash or skew
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_empty_lanes_replay_to_zero(policy, profiles, truth, no_persist):
    order = ["CA", "MA"]
    for arrivals, slo in ((None, None), ([0.0, 5e4], 4e5)):
        fleet = run_fleet(policy, profiles, order, GPU, truth, 4,
                          arrivals=arrivals, slo_deadline=slo,
                          deal="round_robin")
        assert len(fleet.lanes) == 4, policy
        for lane in fleet.lanes[2:]:         # the dealt-nothing lanes
            assert lane.total_cycles == 0.0, policy
            assert lane.completions == [], policy
            assert lane.n_coschedules == 0, policy
        assert fleet.makespan == max(r.total_cycles
                                     for r in fleet.lanes), policy
        assert fleet.makespan > 0.0, policy
        if arrivals is not None:
            lat = fleet.latency
            assert lat["wait_p95"] >= lat["wait_p50"] >= 0.0, policy
            assert 0.0 <= lat["slo_attainment"] <= 1.0, policy


def test_empty_hetero_fleet_and_zero_completion_pooling(profiles, truth,
                                                        no_persist):
    # heterogeneous flavor of the same regression
    fleet = run_fleet("KERNELET", profiles, ["MA"], [FAST, GPU, SLOW],
                      truth, arrivals=[0.0], slo_deadline=4e5)
    assert sum(1 for r in fleet.lanes if r.total_cycles == 0.0) == 2
    assert fleet.makespan > 0.0
    # pooling over lanes with zero completions is the empty distribution,
    # not a crash: zero waits, vacuously met SLO
    empty = [r for r in fleet.lanes if not r.completions]
    lat = aggregate_latency(empty, 123.0)
    assert lat["wait_p50"] == 0.0
    assert lat["wait_p95"] == 0.0
    assert lat["slo_attainment"] == 1.0


# ------------------------------------------------------------------ #
# MC lane streams: SeedSequence-spawned, collision-free
# ------------------------------------------------------------------ #
def test_mc_lane_streams_pin_and_disjointness(profiles, truth, no_persist):
    # duplicated stream: under round-robin over 2 GPUs both lanes replay
    # the identical order, so lane results isolate the rng derivation
    order = [x for n in ORDER for x in (n, n)]
    fleet0 = run_fleet("MC", profiles, order, GPU, truth, 2, seed=0,
                       deal="round_robin")
    # pin the derivation: lane g draws from SeedSequence(seed).spawn(n)[g]
    for g in range(2):
        ref = run_policy_reference(
            "MC", profiles, order[g::2], GPU, truth, seed=0,
            mc_rng=np.random.default_rng(
                np.random.SeedSequence(0).spawn(2)[g]))
        assert fleet0.lanes[g].total_cycles == ref.total_cycles, g
        assert fleet0.lanes[g].time_line == ref.time_line, g
    # lanes draw independent streams (the old seed+g scheme gave lane g
    # of seed s the same stream as lane g-1 of seed s+1)
    assert fleet0.lanes[0].time_line != fleet0.lanes[1].time_line
    fleet1 = run_fleet("MC", profiles, order, GPU, truth, 2, seed=1,
                       deal="round_robin")
    assert fleet0.lanes[1].time_line != fleet1.lanes[0].time_line
    # and the spawned entropy itself cannot collide across (seed, lane)
    a = np.random.SeedSequence(0).spawn(2)[1].generate_state(4)
    b = np.random.SeedSequence(1).spawn(2)[0].generate_state(4)
    assert not np.array_equal(a, b)


# ------------------------------------------------------------------ #
# least-backlog dealing: memoized per-GPU service predictors
# ------------------------------------------------------------------ #
def _spy_single_ipc(monkeypatch):
    calls = []
    orig = markov.MarkovModel.single_ipc

    def spy(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)

    monkeypatch.setattr(markov.MarkovModel, "single_ipc", spy)
    return calls


def test_service_predictor_memoized_across_assigns(profiles, monkeypatch,
                                                   no_persist):
    _SERVICE_MEMO.clear()
    calls = _spy_single_ipc(monkeypatch)
    kw = dict(profiles=profiles, gpu=GPU, gpus=(GPU, FAST))
    first = LeastBacklogDeal().assign(ORDER, TIMED, 2, **kw)
    n_first = len(calls)
    # one Markov solve per (distinct spec, kernel name), never per entry
    assert n_first == 2 * len(profiles)
    # a *new* dealer instance reuses the module-wide memo: zero solves
    second = LeastBacklogDeal().assign(ORDER, TIMED, 2, **kw)
    assert len(calls) == n_first
    assert second == first


def test_plan_fleet_second_call_does_no_extra_solves(profiles, monkeypatch,
                                                     no_persist):
    serve = pytest.importorskip("repro.launch.serve")
    srv = serve.SharedPodServer(gpu_spec=GPU, device_kind=V5E)
    for i, (name, p) in enumerate(sorted(profiles.items())):
        srv.jobs[name] = serve.Job(name, "arch", "prefill", 2 + i)
        srv.profiles[name] = p
    _SERVICE_MEMO.clear()
    calls = _spy_single_ipc(monkeypatch)
    pods = [GPU, FAST]
    plan1 = srv.plan_fleet(2, 1e-5, pod_specs=pods, rounds=300,
                           slo_deadline=4e5)
    n_first = len(calls)
    # the dealer predicted one service per (spec, name): 2 * 4 of the
    # first call's single_ipc traffic is its — and only its first call's
    assert n_first >= 2 * len(profiles)
    n_solves = len(markov._SOLVES)
    plan2 = srv.plan_fleet(2, 1e-5, pod_specs=pods, rounds=300,
                           slo_deadline=4e5)
    # memo warm: the dealer does zero single_ipc calls (any residual
    # traffic is the per-call scheduler build, bounded by the name count
    # and served from the Markov solve memo — no new solves at all)
    assert len(calls) - n_first <= len(profiles)
    assert len(calls) - n_first < 2 * len(profiles)
    assert len(markov._SOLVES) == n_solves
    assert plan1["pods"] == plan2["pods"] == [GPU.name, FAST.name]
    assert plan1["predicted_makespan_cycles"] == \
        plan2["predicted_makespan_cycles"]
    with pytest.raises(ValueError, match="pod_specs"):
        srv.plan_fleet(3, 1e-5, pod_specs=pods)


def test_fast_pod_absorbs_more_of_the_stream(profiles, no_persist):
    # near-simultaneous arrivals: the backlog ledgers dominate, and the
    # 4x-SM pod's predicted service is a fraction of the half-SM pod's
    order = ["MA"] * 40
    arrivals = [float(i) for i in range(40)]
    assign = LeastBacklogDeal().assign(order, arrivals, 2,
                                       profiles=profiles, gpu=GPU,
                                       gpus=(SLOW, FAST))
    n_slow, n_fast = assign.count(0), assign.count(1)
    assert n_fast > 2 * n_slow, (n_slow, n_fast)


def test_predictor_arity_dispatch(profiles, no_persist):
    seen = []

    def per_gpu(name, spec):
        seen.append(spec.name)
        return 1.0 if spec.n_sm > GPU.n_sm else 10.0

    # simultaneous arrivals: the ledgers pile up, so both pods' predicted
    # services are exercised (with sparse arrivals every lane idles and
    # the tie-break never leaves lane 0)
    burst = [0.0] * len(ORDER)
    assign = LeastBacklogDeal(predictor=per_gpu).assign(
        ORDER, burst, 2, profiles=profiles, gpu=GPU, gpus=(GPU, FAST))
    assert FAST.name in seen and GPU.name in seen
    assert assign.count(1) > assign.count(0)     # cheap pod wins
    # legacy one-arg predictors (pre-heterogeneity) keep working
    flat = LeastBacklogDeal(predictor=lambda name: 5.0).assign(
        ORDER, TIMED, 2, profiles=profiles, gpu=GPU, gpus=(GPU, FAST))
    assert len(flat) == len(ORDER)


class _LegacyDeal(DealPolicy):
    """A pre-heterogeneity subclass: no ``gpus`` parameter at all."""

    name = "legacy"

    def assign(self, order, arrivals, n_gpus, *, profiles, gpu):
        assert isinstance(gpu, GPUSpec)
        return [i % n_gpus for i in range(len(order))]


def test_legacy_deal_policy_still_works_on_hetero_fleet(profiles, truth,
                                                        no_persist):
    fleet = run_fleet("KERNELET", profiles, ORDER, [GPU, FAST], truth,
                      deal=_LegacyDeal())
    assert fleet.deal == "legacy"
    assert len(fleet.lanes) == 2
    assert all(r.total_cycles > 0 for r in fleet.lanes)


# ------------------------------------------------------------------ #
# isolation: decision stores are per-spec, lookups group per table
# ------------------------------------------------------------------ #
def test_decision_store_never_replays_across_specs(profiles, tmp_path,
                                                   monkeypatch):
    def fresh(dirname):
        monkeypatch.setenv("REPRO_IPC_CACHE", str(dirname))
        markov._store_at.cache_clear()
        _decision_store_at.cache_clear()

    warm, cold = tmp_path / "warm", tmp_path / "cold"
    warm.mkdir(), cold.mkdir()
    fresh(warm)
    run_fleet("KERNELET", profiles, ORDER, [GPU],
              IPCTable(VG, rounds=ROUNDS))
    fast_warm = run_fleet("KERNELET", profiles, ORDER, [FAST],
                          IPCTable(VG, rounds=ROUNDS))
    stored = [f for _, _, fs in os.walk(warm) for f in fs]
    assert any(content_digest(GPU) in f for f in stored), stored
    assert any(content_digest(FAST) in f for f in stored), stored
    # FAST against a store warm with GPU's decisions must equal FAST
    # against a cold store: a stale cross-spec replay would differ
    fresh(cold)
    fast_cold = run_fleet("KERNELET", profiles, ORDER, [FAST],
                          IPCTable(VG, rounds=ROUNDS))
    assert_lane_equal(fast_warm.lanes[0], fast_cold.lanes[0], "stale")
    fresh(tmp_path / "gone")                 # leave no env for others


def test_engine_groups_tables_and_charges_vectorized(profiles, truth,
                                                     no_persist):
    eng = WorkloadEngine()
    specs = [FAST, GPU, GPU, SLOW]
    fleet = run_fleet("KERNELET", profiles, ORDER * 2, specs, truth,
                      engine=eng, deal="round_robin")
    assert fleet.makespan > 0
    # lanes on equal specs share one table: 3 distinct contents, not 4
    assert eng.stats["table_groups"] == 3
    # the charge pass stays one co + one solo vectorized batch per step —
    # a per-lane scalar fallback would need ~one batch per charged action
    assert eng.stats["charge_batches"] <= 2 * eng.stats["steps"]
    assert eng.stats["charged"] > eng.stats["charge_batches"]


# ------------------------------------------------------------------ #
# API surface
# ------------------------------------------------------------------ #
def test_fleet_spec_validation(profiles, truth, no_persist):
    with pytest.raises(ValueError, match="non-empty"):
        run_fleet("KERNELET", profiles, ORDER, [], truth)
    with pytest.raises(ValueError, match="sequence of GPUSpec"):
        run_fleet("KERNELET", profiles, ORDER, [GPU, "GTX"], truth)
    with pytest.raises(ValueError, match="n_gpus=2 but 1"):
        run_fleet("KERNELET", profiles, ORDER, [GPU], truth, 2)
    with pytest.raises(ValueError, match="not both"):
        run_fleet("KERNELET", profiles, ORDER, [GPU], truth, gpus=[FAST])
    with pytest.raises(ValueError, match="n_gpus is required"):
        run_fleet("KERNELET", profiles, ORDER, GPU, truth)
    with pytest.raises(ValueError, match="one GPUSpec per fleet lane"):
        LeastBacklogDeal().assign(ORDER, TIMED, 2, profiles=profiles,
                                  gpu=GPU, gpus=(GPU,))
    from repro.data.synthetic import make_skewed_workload
    with pytest.raises(ValueError, match="names must be non-empty"):
        make_skewed_workload([], instances=1)
    assert make_skewed_workload([], instances=0) == ([], [])


def test_scalar_gpu_equals_explicit_gpus_kwarg(profiles, truth, no_persist):
    a = run_fleet("KERNELET", profiles, ORDER, GPU, truth, 2)
    b = run_fleet("KERNELET", profiles, ORDER, GPU, truth,
                  gpus=[GPU, GPU])
    for x, y in zip(a.lanes, b.lanes):
        assert_lane_equal(x, y, "gpus kwarg")


# ------------------------------------------------------------------ #
# monotonicity: speeding up one GPU never increases the fleet makespan
# under least-backlog dealing (single kernel type — with one service
# class the greedy deal cannot hit Graham-style packing anomalies)
# ------------------------------------------------------------------ #
def _speedup_case(rm, blocks, ipb, instances, gap, mult, lane):
    p = prof("K", rm, blocks=blocks, ipb=ipb)
    profs = {"K": p}
    order = ["K"] * instances
    arrivals = [i * gap for i in range(instances)]
    truth = IPCTable(VG, rounds=300, persist=False)
    base = run_fleet("KERNELET", profs, order, [GPU, GPU], truth,
                     arrivals=arrivals, deal="least_backlog").makespan
    sped_specs = [GPU, GPU]
    sped_specs[lane] = dataclasses.replace(
        GPU, name=f"C2050x{mult}", n_sm=GPU.n_sm * mult)
    sped = run_fleet("KERNELET", profs, order, sped_specs, truth,
                     arrivals=arrivals, deal="least_backlog").makespan
    return base, sped


@pytest.mark.parametrize("rm,blocks,gap", [
    (0.05, 40, 2.5e4), (0.05, 40, 4e5), (0.4, 80, 2.5e4), (0.4, 80, 4e5),
])
def test_one_gpu_speedup_never_hurts_makespan(rm, blocks, gap, no_persist):
    for lane in (0, 1):
        for mult in (2, 4):
            base, sped = _speedup_case(rm, blocks, 200.0, 6, gap, mult,
                                       lane)
            assert sped <= base + 1e-9, (rm, blocks, gap, lane, mult)


if st is not None:
    @given(rm=st.sampled_from([0.05, 0.2, 0.4]),
           blocks=st.integers(20, 100),
           ipb=st.integers(100, 400),
           instances=st.integers(2, 8),
           gap=st.sampled_from([1e3, 5e4, 4e5]),
           mult=st.integers(2, 4),
           lane=st.integers(0, 1))
    @settings(max_examples=10, deadline=None)
    def test_speedup_monotone_property(rm, blocks, ipb, instances, gap,
                                       mult, lane):
        os.environ["REPRO_IPC_CACHE"] = "0"
        try:
            base, sped = _speedup_case(rm, blocks, float(ipb), instances,
                                       gap, mult, lane)
        finally:
            os.environ.pop("REPRO_IPC_CACHE", None)
        assert sped <= base + 1e-9
