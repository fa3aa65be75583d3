"""Planning: the slices each drain's plan simulated (the pending tenants'
profile ``num_blocks``) over the slices pending at its start, summed over
the window's drains, from the counts each ``drain()`` returns."""


def read(rec):
    d = [x for x in rec.drains if "planned_slices" in x]
    pending = sum(x["pending_slices"] for x in d)
    if not pending:
        return None
    return 100.0 * sum(x["planned_slices"] for x in d) / pending
