"""Each per-layer metric's reader on a hand-made run record: the number it
reads, and nothing where it finds nothing to read."""
import json
import pathlib
from types import SimpleNamespace

import pytest

import peaks
import run
import work

CONF = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                   / "phi3-mini-3.8b.json").read_text())
PEAKS = peaks.peaks_for("TPU v5 lite")
TENANTS = {"prefill": {"phase": "prefill", "batch": 2, "seq": 512},
           "decode": {"phase": "decode", "batch": 16, "seq": 512}}
WORK = {n: work.step(CONF, t["phase"], t["batch"], t["seq"])
        for n, t in TENANTS.items()}
DRAINS = [{"host_s": 0.50, "wall_s": 0.497,
           "rounds": [("prefill", "decode", 2, 6, 0.1),
                      ("decode", None, 4, 0, 0.0)]},
          {"host_s": 0.30, "wall_s": 0.299,
           "rounds": [("prefill", None, 3, 0, 0.0)]}]
TRACE = {"window_s": 2.0, "busy_s": 1.5, "devices": 1,
         "modules": {"prefill": {"count": 10, "device_s": 0.45},
                     "decode": {"count": 20, "device_s": 0.56}}}


def record(trace=TRACE, tenants=TENANTS):
    s = SimpleNamespace(tenants=tenants,
                        work={n: WORK[n] for n in tenants})
    return run.Record(SimpleNamespace(drains=DRAINS, s=s), trace, PEAKS)


def read(name, rec):
    return run.load_plugin("metrics", name).read(rec)


def test_plan_ms_is_drain_host_time_less_dispatch():
    assert read("plan_ms", record()) == pytest.approx(2.0)


def test_coschedule_slice_share_counts_paired_slices():
    assert read("coschedule_slice_share", record()) == pytest.approx(
        100.0 * 8 / 15)


@pytest.mark.parametrize("phase,ms", [("prefill", 45.0), ("decode", 28.0)])
def test_step_ms_and_roofline(phase, ms):
    rec = record()
    assert read(f"{phase}_step_ms", rec) == pytest.approx(ms)
    least = work.least_time(WORK[phase], PEAKS)
    assert read(f"{phase}_roofline", rec) == pytest.approx(
        100.0 * least / (ms * 1e-3))


def test_device_idle_share_and_mfu():
    rec = record()
    assert read("device_idle_share", rec) == pytest.approx(25.0)
    flops = 10 * WORK["prefill"]["flops"] + 20 * WORK["decode"]["flops"]
    assert read("mfu", rec) == pytest.approx(
        100.0 * flops / (2.0 * PEAKS.bf16_flops))


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_roofline"])
def test_no_decode_tenant_reads_nothing(name):
    rec = record(trace={**TRACE, "modules": {"prefill":
                                             TRACE["modules"]["prefill"]}},
                 tenants={"prefill": TENANTS["prefill"]})
    assert read(name, rec) is None


@pytest.mark.parametrize("name", ["prefill_step_ms", "prefill_roofline",
                                  "device_idle_share", "mfu"])
def test_no_trace_reads_nothing(name):
    assert read(name, record(trace={})) is None
