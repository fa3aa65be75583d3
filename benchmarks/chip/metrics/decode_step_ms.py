"""Tenant steps: mean device time of one decode step program in the
traced window."""


def read(rec):
    t = rec.per_call_s("decode")
    return None if t is None else 1e3 * t
