"""The comparison that decides ``correct`` for a served model.

For each position a tenant served, the token the program serves is the
argmax of its logits (greedy). Its gap is by how much the reference's
logit of that token lies below the reference's best logit at that
position. A tenant's number is the widest gap over every position of
every sampled output; a non-finite program logit makes it infinite.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _gap(served, ref):
    served = served.reshape(-1, served.shape[-1])
    ref = ref.reshape(-1, ref.shape[-1]).astype(jnp.float32)
    choice = jnp.argmax(served, axis=-1)
    got = jnp.take_along_axis(ref, choice[:, None], axis=-1)[:, 0]
    gap = jnp.max(jnp.max(ref, axis=-1) - got)
    finite = jnp.all(jnp.isfinite(served.astype(jnp.float32)))
    return jnp.where(finite, gap, jnp.inf)


def widest_gap(served, ref) -> float:
    """Widest gap of ``served`` logits (..., V) against the reference's
    float32 logits of the same positions."""
    if served.shape != ref.shape:
        return float("inf")
    return float(_gap(served, ref))
