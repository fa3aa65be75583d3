"""Device: share of the traced window in which no op ran on the chip
(one less the union of device op intervals over the window)."""


def read(rec):
    if not rec.trace or rec.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
