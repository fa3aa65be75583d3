"""Device: share of the traced window in which the chip is idle and the
innermost host span open is one of the program's ``kernelet.*`` spans."""


def read(rec):
    t = rec.trace or {}
    if not t.get("program_spans") or t.get("window_s", 0) <= 0:
        return None
    idle = sum(t["idle_by_program_span"].values())
    return 100.0 * idle / (t["window_s"] * t["devices"])
