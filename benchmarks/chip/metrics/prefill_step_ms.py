"""Tenant steps: mean device time of one prefill step program in the
traced window."""


def read(rec):
    t = rec.per_call_s("prefill")
    return None if t is None else 1e3 * t
