"""The traffic generator and the closed loop: the same job sizes in the
same order for every seed, in the ranges and with the means that the
traffic file states."""
import itertools
import json
import pathlib

import numpy as np
import pytest

import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


SEED = 2**31 + 77


@pytest.mark.parametrize("tenant,lo,hi,mean", [
    ("prefill", 1, 8, 2.0), ("decode", 1, 32, 7.0)])
def test_job_sizes_in_range_with_mean(tenant, lo, hi, mean):
    spec = {t["name"]: t for t in mix("pd-closed")["tenants"]}[tenant]
    s = list(itertools.islice(
        traffic.size_stream(spec["slices"], "sizes/" + tenant), 32 * 20))
    assert min(s) >= lo and max(s) <= hi
    assert np.mean(s) == pytest.approx(mean, rel=0.05)
    assert max(s) >= hi // 2          # a heavy tail, not a constant
    assert sorted(s[:32]) == sorted(s[32:64])   # every block the same sizes
    assert s[:32] != s[32:64]                   # in another order


def test_rng_streams_differ_by_seed_and_name():
    a = traffic.rng_for(SEED, "sample").random(4)
    assert np.array_equal(a, traffic.rng_for(SEED, "sample").random(4))
    assert not np.array_equal(a, traffic.rng_for(SEED + 1, "sample")
                              .random(4))
    assert not np.array_equal(a, traffic.rng_for(SEED, "other").random(4))


class FakeWindow:
    """Records what a loop asks of the window; a drain takes 0.1 s."""

    def __init__(self, seconds):
        self.t = 0.0
        self.start = 0.0
        self.seconds = seconds
        self.deadline = seconds
        self.pending, self.log = [], []

    def now(self):
        return self.t

    def admit(self, tenant, slices, due=None):
        job = type("J", (), {"tenant": tenant, "slices": slices})()
        self.pending.append(job)
        self.log.append(("admit", tenant, slices, self.t))
        return job

    def drain(self):
        self.t += 0.1
        done, self.pending = self.pending, []
        self.log.append(("drain", len(done), self.t))
        return done


def load_loop(kind):
    import run
    return run.load_plugin("loops", kind)


def test_closed_loop_keeps_clients_busy():
    win = FakeWindow(2.0)
    load_loop("closed").drive(win, mix("pd-closed"), SEED)
    drains = [e for e in win.log if e[0] == "drain"]
    assert all(n == 8 for _, n, _ in drains)        # 4 + 4 clients
    assert len(drains) == 20 and not win.pending


def test_closed_loop_is_the_same_work_for_every_seed():
    logs = []
    for seed in (1, SEED):
        win = FakeWindow(2.0)
        load_loop("closed").drive(win, mix("pd-closed"), seed)
        logs.append(win.log)
    assert logs[0] == logs[1]


def test_prefill_closed_is_two_of_pd_closed_prefill_tenants():
    """The control mix: two prefill tenants, each the pd-closed prefill
    tenant under its own name, so each draws its own order of the same
    sizes."""
    pd = {t["name"]: t for t in mix("pd-closed")["tenants"]}["prefill"]
    pc = mix("prefill-closed")
    assert [t["name"] for t in pc["tenants"]] == ["prefill-a", "prefill-b"]
    for t in pc["tenants"]:
        assert {k: v for k, v in t.items() if k != "name"} == \
            {k: v for k, v in pd.items() if k != "name"}
    a, b = (list(itertools.islice(
        traffic.size_stream(t["slices"], "sizes/" + t["name"]), 32))
        for t in pc["tenants"])
    assert sorted(a) == sorted(b) and a != b
    assert set(pc["stands_for"]) and pc["loop"] == "closed"
