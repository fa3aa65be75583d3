"""Planning: mean duration of the program's ``kernelet.plan`` span (the
workload engine, the scheduler and the plan) in the traced window."""


def read(rec):
    s = (rec.trace or {}).get("program_spans", {}).get("kernelet.plan")
    return 1e3 * s["total_s"] / s["count"] if s else None
