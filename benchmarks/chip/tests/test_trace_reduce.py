"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py``: inside a ``window`` span, 3 runs of ``jit_mm`` each
under a ``drain`` span and followed by a 50 ms sleep under
``wait_arrival``, then 5 runs of ``jit_scale`` under ``admit``."""
import pathlib

import pytest

import record_trace
import trace_reduce as tr

TRACE = pathlib.Path(__file__).resolve().parents[1] / "testdata" / \
    "small.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    return tr.read(str(TRACE))


@pytest.fixture(scope="module")
def red(raw):
    return tr.reduce(raw)


def test_reads_one_device_and_the_harness_spans(raw):
    assert list(raw["devices"]) == ["/device:TPU:0"]
    names = [e[0] for e in raw["host"]]
    assert names.count("window") == 1
    assert names.count("drain") == record_trace.MM_RUNS
    assert names.count("wait_arrival") == record_trace.MM_RUNS
    assert names.count("admit") == 1


def test_device_clock_is_put_after_each_dispatch(raw):
    runs = sorted(raw["devices"]["/device:TPU:0"]["modules"],
                  key=lambda m: m[1])
    assert len(raw["dispatch"]) == len(runs) == 8
    assert 0 < raw["shift_ns"] < 5e6            # about a millisecond
    assert all(s >= d for d, (_, s, _e) in zip(raw["dispatch"], runs))


def test_program_runs_in_order(raw):
    names = [n for n, _ in tr.module_runs(raw)]
    assert names == ["jit_mm"] * record_trace.MM_RUNS + \
        ["jit_scale"] * record_trace.SCALE_RUNS


def test_per_program_device_time(red):
    mm, sc = red["modules"]["jit_mm"], red["modules"]["jit_scale"]
    assert mm["count"] == record_trace.MM_RUNS
    assert sc["count"] == record_trace.SCALE_RUNS
    # a 4096^3 bf16 matmul: 137 GFLOP, at least 0.70 ms at 197 TFLOP/s
    assert 0.70e-3 < mm["device_s"] / mm["count"] < 1.0e-3
    # 8192^2 f32 read and written: 537 MB, at least 0.66 ms at 819 GB/s
    assert 0.66e-3 < sc["device_s"] / sc["count"] < 1.0e-3


def test_recorded_matmul_share_of_roofline_at_most_one(red):
    import peaks
    import work
    n = 4096
    w = {"flops": 2.0 * n ** 3, "bytes": 3.0 * n * n * 2}
    mm = red["modules"]["jit_mm"]
    share = work.least_time(w, peaks.peaks_for("TPU v5 lite")) / (
        mm["device_s"] / mm["count"])
    assert 0.5 < share <= 1.0


def test_busy_and_idle_fill_the_window(raw, red):
    win = [e for e in raw["host"] if e[0] == "window"][0]
    assert red["window_s"] == pytest.approx((win[2] - win[1]) * 1e-9)
    assert red["busy_s"] == pytest.approx(
        sum(m["device_s"] for m in red["modules"].values()), rel=0.01)
    idle = sum(red["idle_by_span"].values())
    assert red["busy_s"] + idle == pytest.approx(red["window_s"])
    assert 1 - red["busy_s"] / red["window_s"] > 0.9


def test_longest_gaps_are_the_sleeps(red):
    top = red["idle_gaps"][:record_trace.MM_RUNS]
    assert [lab for lab, _ in top] == ["wait_arrival"] * 3
    for _, s in top:
        assert record_trace.SLEEP_S * 0.95 < s < record_trace.SLEEP_S * 1.2
    assert red["idle_by_span"]["wait_arrival"] > 0.15


def test_costliest_ops_are_named_by_program(red):
    first, second = red["device_ops"][:2]
    assert first[0].startswith("jit_scale/") and "f32[8192,8192]" in first[0]
    assert second[0].startswith("jit_mm/") and "bf16[4096,4096]" in second[0]


def test_run_labels_name_the_programs(raw):
    red = tr.reduce(raw, run_labels=["a"] * 3 + ["b"] * 5)
    assert red["modules"]["a"]["count"] == 3
    assert red["modules"]["b"]["count"] == 5


@pytest.mark.parametrize("n", [7, 9])
def test_runs_that_do_not_match_dispatches_fail_the_run(n):
    import run
    with pytest.raises(RuntimeError, match="8 program runs"):
        run.read_trace(str(TRACE.parent), ["a"] * n)
    assert run.read_trace(str(TRACE.parent), ["a"] * 8)["modules"]["a"][
        "count"] == 8


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 8)]) == [[0, 3], [5, 8]]
    assert tr.gaps([[0, 3], [5, 8]], -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]
    ops = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 11, 12)]
    assert [o[0] for o in tr.leaves(ops)] == ["a", "b", "c"]
    assert tr.module_name("jit_prefill_logits(123)") == "jit_prefill_logits"
    assert tr.op_label("%fusion.1 = bf16[2,3]{1,0:T(8,128)} fusion(%x)") == \
        "%fusion.1 bf16[2,3]"
