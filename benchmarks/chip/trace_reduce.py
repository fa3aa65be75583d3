"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle
time, per-program device time, the costliest device ops, and the longest
idle gaps labelled by the host span the harness had open at the time.

Reads the trace with ``jax.profiler.ProfileData`` alone. Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per op run and
their ``XLA Modules`` line one event per program run. Host spans are the
harness's ``jax.profiler.TraceAnnotation`` events on the host plane.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "window"
LABEL_SPANS = ("admit", "drain", "wait_arrival")
DISPATCH = "PJRT_LoadedExecutable_Execute"   # host: one per program run
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """``jit_prefill_logits(12)`` -> ``jit_prefill_logits``."""
    return _SUFFIX.sub("", event_name)


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def read(path: str) -> dict:
    """The raw events the reduction needs, per device and for the host."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, dispatch = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                "ops": _events(lines["XLA Ops"]) if "XLA Ops" in lines
                else [],
                "modules": _events(lines["XLA Modules"])
                if "XLA Modules" in lines else []}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                host += [e for e in evs if e[0] in (WINDOW_SPAN,) + LABEL_SPANS]
                dispatch += [e[1] for e in evs if e[0] == DISPATCH]
    return align({"devices": devices, "host": host,
                  "dispatch": sorted(dispatch)})


def align(raw: dict) -> dict:
    """Put the device events on the host's clock. The two run about a
    millisecond apart; no program can start before the host dispatched
    it, so shift the device events by the least amount that makes every
    program run start after its dispatch (the k-th run after the k-th
    dispatch). Without a dispatch per run, nothing is shifted."""
    shift = 0.0
    for dev in raw["devices"].values():
        starts = sorted(s for _, s, _ in dev["modules"])
        if starts and len(starts) == len(raw["dispatch"]):
            shift = max(0.0, max(d - s for d, s in
                                 zip(raw["dispatch"], starts)))
        break
    raw["shift_ns"] = shift
    for dev in raw["devices"].values():
        for k in ("ops", "modules"):
            dev[k] = [(n, s + shift, e + shift) for n, s, e in dev[k]]
    return raw


def _label(mid: float, spans, starts) -> str:
    """The label span open at ``mid`` (label spans never overlap)."""
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and mid < spans[i][2]:
        return spans[i][0]
    return "none"


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[2,512]{...} fusion(...)`` -> ``%fusion.12
    bf16[2,512]``: the op and its result's type, without the layout."""
    lhs, _, rhs = name.partition(" = ")
    rtype = rhs.split("{")[0].split(" ")[0]
    return f"{lhs} {'tuple' if rtype.startswith('(') else rtype}".strip()


def leaves(ops) -> list:
    """The ops that contain no other op: a while or call op spans the ops
    of its body on the same line."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ops)
    stack = []
    for i, (_, s, _e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def reduce(raw: dict, *, run_labels=None, top: int = 10) -> dict:
    """Busy, idle and per-program device time within the harness's
    ``window`` span (or, without one, the extent of the device events).
    ``run_labels``, one per program run of the first device in the order
    they ran, names the programs in place of their module names. Times in
    seconds; ``busy_s`` is averaged over the devices."""
    win = [e for e in raw["host"] if e[0] == WINDOW_SPAN]
    devs = raw["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        raise ValueError("the trace holds no device op")
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        lo = min(e[1] for d in devs.values() for e in d["ops"])
        hi = max(e[2] for d in devs.values() for e in d["ops"])
    spans = sorted((e for e in raw["host"] if e[0] in LABEL_SPANS),
                   key=lambda e: e[1])
    starts = [e[1] for e in spans]
    busy_total, modules, op_time, idle = 0.0, {}, {}, []
    for k, dev in enumerate(sorted(devs)):
        dev = devs[dev]
        busy = union(clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        idle += gaps(busy, lo, hi)
        runs = sorted(dev["modules"], key=lambda m: m[1])
        names = ([module_name(n) for n, _, _ in runs]
                 if k or run_labels is None or len(run_labels) != len(runs)
                 else list(run_labels))
        mods = [(s, e, nm) for (_, s, e), nm in zip(runs, names)
                if e > lo and s < hi]
        for s, e, name in mods:
            m = modules.setdefault(name, {"count": 0, "device_s": 0.0})
            m["count"] += 1
            m["device_s"] += (e - s) * 1e-9
        mstarts = [m[0] for m in mods]
        for name, s, e in leaves(dev["ops"]):
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(mstarts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            key = f"{owner}/{op_label(name)}"
            op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
    n_dev = len(devs)
    idle_by_span = {}
    labelled = []
    for s, e in idle:
        lab = _label((s + e) / 2, spans, starts)
        idle_by_span[lab] = idle_by_span.get(lab, 0.0) + (e - s) * 1e-9
        labelled.append((lab, (e - s) * 1e-9))
    labelled.sort(key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "devices": n_dev,
        "modules": modules,
        "device_ops": [[k, v] for k, v in
                       sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[k, v] for k, v in labelled[:top]],
        "idle_by_span": idle_by_span,
    }


def module_runs(raw: dict) -> list:
    """(name, device seconds) of every program run on the first device,
    in the order they ran. The harness starts the profiler just before
    its window and stops it just after, with nothing dispatched in
    between, so these are exactly the window's program runs (the host
    span cannot select them: the device clock runs about a millisecond
    off the host's)."""
    dev = raw["devices"][sorted(raw["devices"])[0]]
    return [(module_name(n), (e - s) * 1e-9)
            for n, s, e in sorted(dev["modules"], key=lambda m: m[1])]
