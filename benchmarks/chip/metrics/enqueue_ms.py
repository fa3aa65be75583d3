"""Scheduling and dispatch: time in the program's ``kernelet.dispatch``
spans (the enqueue of a round's slices) per ``kernelet.round`` in the
traced window."""


def read(rec):
    spans = (rec.trace or {}).get("program_spans", {})
    d, r = spans.get("kernelet.dispatch"), spans.get("kernelet.round")
    return 1e3 * d["total_s"] / r["count"] if d and r else None
