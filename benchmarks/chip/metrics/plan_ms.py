"""Planning layer: host time of each ``drain()`` call less the dispatch
time (``wall_s``) it reports, averaged over the window's drains: the
engine plan and the set-up before the first launch."""


def read(rec):
    d = rec.drains
    if not d:
        return None
    return 1e3 * sum(x["host_s"] - x["wall_s"] for x in d) / len(d)
