"""Step programs against the roofline: the least time the prefill step's
required work allows at the chip's peaks (``work.py``, ``peaks.py``) over
its measured mean device time per call."""


def read(rec):
    t, least = rec.per_call_s("prefill"), rec.least_s("prefill")
    if t is None or least is None:
        return None
    return 100.0 * least / t
