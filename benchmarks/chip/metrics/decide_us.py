"""Scheduling and dispatch: mean duration of the program's
``kernelet.decide`` span (``find_coschedule``, once a round) in the
traced window."""


def read(rec):
    s = (rec.trace or {}).get("program_spans", {}).get("kernelet.decide")
    return 1e6 * s["total_s"] / s["count"] if s else None
