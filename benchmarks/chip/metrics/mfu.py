"""Device: FLOPs that the step programs run in the traced window require
(the configuration's ``equations.step``), over the window times the
chip's bf16 peak. The whole step's share of peak, which still bounds a
gain after a kernel leaves the path."""


def read(rec):
    if not rec.trace or not rec.per_phase:
        return None
    flops = 0.0
    for phase, p in rec.per_phase.items():
        w = rec.step_work(phase)
        if w is None:
            return None
        flops += p["count"] * w["flops"]
    chips = rec.trace["devices"]
    return 100.0 * flops / (rec.trace["window_s"] * chips
                            * rec.peaks.bf16_flops)
