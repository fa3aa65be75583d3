"""RWKV6 (WKV) chunked-parallel recurrence as a Pallas TPU kernel.

Grid (B, H, n_chunks); chunks iterate sequentially (innermost) carrying the
(N, N) state in VMEM scratch. Within a chunk the decay-weighted attention
matrix uses only exponents <= 0 (numerically safe, see
repro.models.recurrent). One grid step's VMEM footprint is
O(C*N + C*C + N*N) — hardware-aligned for N = 64 heads.

Mosaic has no cumsum, and sublane-level reshapes of a (1, N) row are
unaligned, so the in-chunk prefix sum is a lower-triangular matmul and the
per-row state decay a diagonal matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                chunk: int, n: int):
    h, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)               # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)               # log-decay <= 0
    u = u_ref[pl.ds(h, 1), :].astype(jnp.float32)     # (1, N)
    state = s_ref[...]                                # (N, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (ii >= jj).astype(jnp.float32)
    la = jax.lax.dot(tril, w, precision=_HI,
                     preferred_element_type=jnp.float32)   # cumsum, (C, N)
    la_prev = la - w
    la_end = la[chunk - 1:chunk]                      # (1, N)

    # inter-chunk
    r_dec = r * jnp.exp(la_prev)
    out = jax.lax.dot(r_dec, state, preferred_element_type=jnp.float32)
    # intra-chunk: att[i,j] = sum_n r_i k_j exp(la_prev_i - la_j), j < i
    dmat = jnp.exp(la_prev[:, None, :] - la[None, :, :])      # (C, C, N)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * dmat, axis=-1)
    att = jnp.where(ii > jj, att, 0.0)
    out = out + jax.lax.dot(att, v, preferred_element_type=jnp.float32)
    # bonus diagonal
    diag = jnp.sum(r * (u * k), axis=-1, keepdims=True)
    out = out + diag * v
    o_ref[0, 0] = out.astype(o_ref.dtype)
    # state update: row n of the state decays by exp(la_end[n])
    k_dec = k * jnp.exp(la_end - la)
    ni = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    nj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    decay = jnp.where(ni == nj, jnp.exp(la_end), 0.0)          # diag, (N, N)
    s_ref[...] = jax.lax.dot(decay, state, precision=_HI,
                             preferred_element_type=jnp.float32) + jax.lax.dot(
        k_dec.T, v, preferred_element_type=jnp.float32)


def rwkv6_scan(r, k, v, w_log, u, *, chunk: int = 32,
               interpret: bool = False):
    """r/k/v/w_log: (B, S, H, N); u: (H, N). Returns out (B, S, H, N) f32.

    Chunked-parallel WKV6; state starts at zero (training mode).
    """
    b, s, h, n = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    # layout (B, H, S, N) so chunks are contiguous per (b, h)
    def to_bhsn(x):
        return x.transpose(0, 2, 1, 3).astype(x.dtype)
    rr, kk, vv, ww = map(to_bhsn, (r, k, v, w_log))
    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n=n),
        grid=(b, h, nc),
        in_specs=[pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, j, c, 0))] * 4
        + [pl.BlockSpec((h, n), lambda i, j, c: (0, 0))],
        out_specs=pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, j, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, u)
    return out.transpose(0, 2, 1, 3)
