"""Scheduling and dispatch: share of the window's slices that ran in
co-scheduled rounds (two tenants interleaved), from ``drain()``'s
``rounds`` (k1, k2, n1, n2, cp)."""


def read(rec):
    paired = total = 0
    for d in rec.drains:
        for _k1, k2, n1, n2, _cp in d["rounds"]:
            total += n1 + n2
            if k2 is not None:
                paired += n1 + n2
    return 100.0 * paired / total if total else None
