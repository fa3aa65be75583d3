"""The program's own records of a run: the ``kernelet.*`` spans that
``SharedPodServer`` opens on the profiler's clock, and the job records and
plan counts its ``drain()`` returns.

  python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1> [--trace-seconds <s>] [--keep-trace DIR]

Runs a cell as ``run.py`` does, with what ``run.py`` does not yet do: the
window admits each job through ``SharedPodServer.admit`` and keeps each
drain's job records and plan counts, and the trace's reduction adds the
program's spans (``reduce``): per span name its count, total and self
time; the device's idle time by the innermost program span open; and the
idle gaps labelled ``<harness span>><program span>``. Prints ``run.py``'s
result line, whose per-layer metrics gain ``PROGRAM_METRICS``; on stderr,
the split of the jobs' latency into wait, service and hold, and how far
the program's job latency lies from the harness's. Against a program
without ``admit``, job records or spans, the window admits as ``run.py``
does and the new metrics are absent.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import functools
import json

import numpy as np

import run
import trace_reduce

PREFIX = "kernelet."
KEEP = ("jobs", "returned_at", "planned_slices", "pending_slices")
PROGRAM_METRICS = [
    {"name": "queue_wait_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "admission",
     "moves": "job_p50_ms"},
    {"name": "completion_hold_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "scheduling and dispatch",
     "moves": "job_p50_ms"},
    {"name": "plan_span_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "planning", "moves": "job_p50_ms"},
    {"name": "plan_coverage", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "planning",
     "moves": "job_p50_ms"},
    {"name": "decide_us", "unit": "us", "better": "lower",
     "source": "program_span", "layer": "scheduling and dispatch",
     "moves": "job_p50_ms"},
    {"name": "enqueue_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "scheduling and dispatch",
     "moves": "tokens_per_s"},
    {"name": "idle_program_share", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "tokens_per_s"},
]


# --------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------- #
def read(path: str) -> dict:
    """``trace_reduce.read``'s events, and under ``program`` every
    ``kernelet.*`` host span as (name, start, end, stats), host clock."""
    from jax.profiler import ProfileData
    raw = trace_reduce.read(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           dict(ev.stats)) for ev in line.events
                          if ev.name.startswith(PREFIX)]
    raw["program"] = sorted(spans, key=lambda e: (e[1], -e[2]))
    return raw


def innermost(spans) -> list:
    """(start, end, name) pieces of the time the spans cover, each named
    by the innermost span open over it. Spans nest, as the server's one
    thread opens them."""
    out, stack, t = [], [], None        # stack: (name, end) of open spans

    def close(upto):
        nonlocal t
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    close(float("inf"))
    return out


def overlap(a, b) -> list:
    """(start, end, name) pieces of ``b`` inside the (start, end)
    intervals of ``a``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi, b[k][2]))
            k += 1
    return out


def _at(mid: float, pieces, starts) -> str | None:
    i = bisect.bisect_right(starts, mid) - 1
    return pieces[i][2] if i >= 0 and mid < pieces[i][1] else None


def reduce(raw: dict, *, top: int = 10) -> dict:
    """The program's spans within the harness's ``window`` span (or the
    extent of the device ops): ``program_spans``, per name, ``count``,
    ``total_s``, ``self_s`` (time in which it is the innermost span) and
    ``max_s``; ``idle_by_program_span``, the device's idle seconds by
    the innermost program span open, summed over devices; ``idle_gaps``,
    the longest idle gaps labelled ``<harness span>><program span>`` by
    what was open at their midpoint, or by the harness span alone."""
    win = [e for e in raw["host"] if e[0] == trace_reduce.WINDOW_SPAN]
    devs = raw["devices"]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        lo = min(e[1] for d in devs.values() for e in d["ops"])
        hi = max(e[2] for d in devs.values() for e in d["ops"])
    spans = [e for e in raw.get("program", []) if lo <= e[1] < hi]
    pieces = innermost(spans)
    stats = {}
    for name, s, e, _ in spans:
        st = stats.setdefault(name, {"count": 0, "total_s": 0.0,
                                     "self_s": 0.0, "max_s": 0.0})
        st["count"] += 1
        st["total_s"] += (e - s) * 1e-9
        st["max_s"] = max(st["max_s"], (e - s) * 1e-9)
    for s, e, name in pieces:
        stats[name]["self_s"] += (e - s) * 1e-9
    harness = sorted((e for e in raw["host"]
                      if e[0] in trace_reduce.LABEL_SPANS),
                     key=lambda e: e[1])
    hstarts = [e[1] for e in harness]
    pstarts = [p[0] for p in pieces]
    idle_by, labelled = {}, []
    for dev in devs.values():
        busy = trace_reduce.union(trace_reduce.clip(
            [(s, e) for _, s, e in dev["ops"]], lo, hi))
        idle = trace_reduce.gaps(busy, lo, hi)
        for s, e, name in overlap(idle, pieces):
            idle_by[name] = idle_by.get(name, 0.0) + (e - s) * 1e-9
        for s, e in idle:
            mid = (s + e) / 2
            lab = trace_reduce._label(mid, harness, hstarts)
            inner = _at(mid, pieces, pstarts)
            labelled.append((lab if inner is None else f"{lab}>{inner}",
                             (e - s) * 1e-9))
    labelled.sort(key=lambda x: -x[1])
    return {"program_spans": stats, "idle_by_program_span": idle_by,
            "idle_gaps": [[k, v] for k, v in labelled[:top]]}


_RUN_READ_TRACE = run.read_trace


def read_trace(trace_dir: str, dispatched: list) -> dict:
    """``run.read_trace``, with the program's spans reduced in."""
    red = _RUN_READ_TRACE(trace_dir, dispatched)
    red.update(reduce(read(trace_reduce.find_xplane(trace_dir))))
    run.log("program spans " + json.dumps(red["program_spans"])
            + "; idle by program span "
            + json.dumps(red["idle_by_program_span"]))
    return red


# --------------------------------------------------------------------- #
# the window
# --------------------------------------------------------------------- #
class ProgramWindow(run.Window):
    """``run.Window``, admitting each job through the server's ``admit``
    where the program has it, and keeping the job records and plan counts
    each drain returns in that drain's record. Appends itself to
    ``made``."""

    def __init__(self, made: list, *args, **kwargs):
        super().__init__(*args, **kwargs)
        made.append(self)
        srv = self.s.srv
        self._admits = callable(getattr(srv, "admit", None))
        self._res, drain = None, srv.drain

        def keep(**kw):
            self._res = drain(**kw)
            return self._res
        srv.drain = keep

    def admit(self, tenant: str, slices: int, due=None):
        if not self._admits:
            return super().admit(tenant, slices, due)
        with self._span("admit"):
            t = self.s.tenants[tenant]
            per = t["batch"] * (t["seq"] if t["phase"] == "prefill" else 1)
            job = run.Job(tenant, slices, slices * per,
                          self.now() if due is None else due)
            self.s.srv.admit(tenant, slices)
            self.admitted[tenant] += slices
            self.jobs.append(job)
            self.pending.append(job)
            return job

    def drain(self) -> list:
        done = super().drain()
        self.drains[-1].update({k: self._res[k] for k in KEEP
                                if k in self._res})
        return done


def job_split(win) -> dict:
    """The window's jobs, in ms: from the program's records, each job's
    wait (admission to its first slice's enqueue), service (to the wait
    that covered its last slice), hold (to its drain's return) and
    latency (admission to its drain's return), as medians and means; the
    harness's median job latency; and their difference."""
    harness = [j.done - j.due for j in win.jobs if j.done is not None]
    out = {"harness_p50_ms": 1e3 * float(np.median(harness))
           if harness else None}
    rows = [(r.admitted_at, r.first_dispatch, r.done, d["returned_at"])
            for d in win.drains for r in d.get("jobs", ())]
    if not rows:
        return out
    adm, first, done, ret = np.array(rows).T
    out["jobs"] = len(rows)
    for part, v in (("wait", first - adm), ("service", done - first),
                    ("hold", ret - done), ("latency", ret - adm)):
        out[f"{part}_p50_ms"] = 1e3 * float(np.median(v))
        out[f"{part}_mean_ms"] = 1e3 * float(np.mean(v))
    if harness:
        out["latency_gap_ms"] = out["latency_p50_ms"] - out["harness_p50_ms"]
    return out


# --------------------------------------------------------------------- #
# a run
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def program_records(trace_seconds: float, made: list):
    """``run.py``'s window and trace reduction, with the program's
    records added; the trace covers the window's first
    ``trace_seconds``; each window made is appended to ``made``."""
    saved = run.Window, run.read_trace, run.TRACE_MAX_S
    run.Window = functools.partial(ProgramWindow, made)
    run.read_trace, run.TRACE_MAX_S = read_trace, trace_seconds
    try:
        yield
    finally:
        run.Window, run.read_trace, run.TRACE_MAX_S = saved


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             trace_seconds: float = run.TRACE_MAX_S, **kwargs):
    """``run.run_cell`` with the program's records: its result line, with
    ``PROGRAM_METRICS`` among the per-layer metrics, and the window."""
    spec = {**spec, "per_layer": spec["per_layer"] + PROGRAM_METRICS}
    made = []
    with program_records(trace_seconds, made):
        out = run.run_cell(spec, seed, seconds, trace, **kwargs)
    return out, made[-1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=run.TRACE_MAX_S)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    out, win = run_cell(run.resolve(args.workload), args.seed,
                        args.seconds, bool(args.trace),
                        trace_seconds=args.trace_seconds,
                        keep_trace=args.keep_trace)
    split = job_split(win)
    run.log("jobs " + json.dumps(split))
    if "latency_gap_ms" in split:
        run.log(f"program job latency p50 less the harness's: "
                f"{split['latency_gap_ms']:.4f} ms")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
