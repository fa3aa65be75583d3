"""RG-LRU linear recurrence (Griffin) as a Pallas TPU kernel.

Grid (B, W_blocks, n_chunks); chunks sequential with the hidden state
carried in VMEM scratch. Within a chunk the first-order recurrence
h_t = a_t h_{t-1} + b_t is a log-step (Hillis-Steele) scan over the time
axis: each step combines every row with the row ``d`` above it, fetched by
a sublane roll, so the kernel needs no scatter and no associative_scan,
neither of which Mosaic lowers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(x_ref, a_ref, o_ref, h_ref, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                 # (C, bw)
    a_log = a_ref[0].astype(jnp.float32)             # (C, bw), <= 0
    a = jnp.exp(a_log)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * a_log), 1e-12)) * x
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    b = b + jnp.where(row == 0, a * h_ref[...], 0.0)   # carry-in at t = 0

    d = 1
    while d < chunk:
        keep = row >= d
        a_up = jnp.where(keep, pltpu.roll(a, d, 0), 1.0)
        b_up = jnp.where(keep, pltpu.roll(b, d, 0), 0.0)
        b = b + a * b_up
        a = a * a_up
        d *= 2
    o_ref[0] = b.astype(o_ref.dtype)
    h_ref[...] = b[chunk - 1:chunk]


def rg_lru(x, a_log, *, chunk: int = 128, bw: int = 512,
           interpret: bool = False):
    """x, a_log: (B, S, W) -> h: (B, S, W) f32. Zero initial state."""
    b, s, w = x.shape
    chunk = min(chunk, s)
    bw = min(bw, w)
    assert s % chunk == 0 and w % bw == 0
    nc, nw = s // chunk, w // bw
    out = pl.pallas_call(
        functools.partial(_lru_kernel, chunk=chunk),
        grid=(b, nw, nc),
        in_specs=[pl.BlockSpec((1, chunk, bw), lambda i, j, c: (i, c, j))] * 2,
        out_specs=pl.BlockSpec((1, chunk, bw), lambda i, j, c: (i, c, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(x, a_log)
    return out
