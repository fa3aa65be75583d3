"""Fused co-scheduled execution — the TPU-native analogue of Kernelet's
concurrent kernel execution.

TPU cores run one program at a time: co-residency of two kernels on an SM
has no direct equivalent. What the hardware *does* give us is the Pallas
software pipeline: while grid step t computes, step t+1's blocks are being
DMA'd from HBM. A single fused kernel whose grid interleaves slices of an
MXU-bound op (matmul tiles) with slices of an HBM-bound op (streaming scale
blocks) therefore overlaps the streaming op's DMA with the matmul's MXU
time — the same complementary-resource insight as the paper, realized
through the DMA/compute pipeline instead of warp co-residency.

The interleave schedule (which op runs at grid step t, and which of its
blocks) is a scalar-prefetch operand — the Kernelet scheduler's slice plan
(s1 : s2 balanced ratio, Eq. 8) is literally the input to this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DEFAULT_SCOPED_VMEM = 16 << 20   # the TPU compiler's default scoped limit
_VMEM_HEADROOM = 4 << 20          # Mosaic's internal scratch


def make_schedule(n_a: int, n_b: int, run_a: int = 1, run_b: int = 1):
    """Interleave n_a matmul tiles and n_b stream blocks in runs of
    (run_a, run_b) — the co-schedule's balanced slice ratio.

    Returns (op, a_idx, b_idx) int32 arrays of length n_a + n_b. For steps
    executing the *other* op, an op's index repeats its previous value so
    the out-block copy-out rewrites identical data.
    """
    op, ai, bi = [], [], []
    a_done = b_done = 0
    cur_a = cur_b = 0
    while a_done < n_a or b_done < n_b:
        for _ in range(run_a):
            if a_done < n_a:
                cur_a = a_done
                op.append(0)
                a_done += 1
                ai.append(cur_a)
                bi.append(cur_b)
        for _ in range(run_b):
            if b_done < n_b:
                cur_b = b_done
                op.append(1)
                b_done += 1
                ai.append(cur_a)
                bi.append(cur_b)
    return (np.asarray(op, np.int32), np.asarray(ai, np.int32),
            np.asarray(bi, np.int32))


def _kernel(op_ref, ai_ref, bi_ref, a_ref, b_ref, x_ref,
            mm_ref, st_ref, *, scale: float):
    t = pl.program_id(0)

    @pl.when(op_ref[t] == 0)
    def _mm():
        mm_ref[0] = jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32
                            ).astype(mm_ref.dtype)

    @pl.when(op_ref[t] == 1)
    def _stream():
        st_ref[...] = (x_ref[...] * scale).astype(st_ref.dtype)


def coschedule(a, b, x, *, scale: float = 2.0, run_a: int = 1,
               run_b: int = 1, bm: int = 128, bn: int = 128,
               bx: int = 256, interpret: bool = False):
    """Fused interleaved execution of ``matmul(a, b)`` and ``x * scale``.

    a: (M, K), b: (K, N) — K is kept unblocked (the MXU-bound op).
    x: (P, Q) streamed in (bx, Q) row-blocks (the HBM-bound op).
    Returns (a @ b, x * scale).

    The scoped-VMEM limit is reckoned from the block footprint: every
    in/out block is double-buffered, so at the bench shapes (K = Q = 8192,
    bf16) the kernel holds about 24 MiB, over the chip compiler's 16 MiB
    default.
    """
    m, k = a.shape
    n = b.shape[1]
    p, q = x.shape
    assert m % bm == 0 and n % bn == 0 and p % bx == 0
    n_i, n_j = m // bm, n // bn
    n_a, n_b = n_i * n_j, p // bx
    op, ai, bi = make_schedule(n_a, n_b, run_a, run_b)
    grid = (len(op),)
    blocks = (bm * k * a.dtype.itemsize + k * bn * b.dtype.itemsize
              + bm * bn * a.dtype.itemsize + 2 * bx * q * x.dtype.itemsize)
    vmem_limit = max(_DEFAULT_SCOPED_VMEM, 2 * blocks + _VMEM_HEADROOM)

    def a_map(t, op_r, ai_r, bi_r):
        return (ai_r[t] // n_j, 0)

    def b_map(t, op_r, ai_r, bi_r):
        return (0, ai_r[t] % n_j)

    def x_map(t, op_r, ai_r, bi_r):
        return (bi_r[t], 0)

    def mm_map(t, op_r, ai_r, bi_r):
        return (ai_r[t], 0, 0)

    def st_map(t, op_r, ai_r, bi_r):
        return (bi_r[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), a_map),
                  pl.BlockSpec((k, bn), b_map),
                  pl.BlockSpec((bx, q), x_map)],
        out_specs=[pl.BlockSpec((1, bm, bn), mm_map),
                   pl.BlockSpec((bx, q), st_map)],
    )
    mm_tiles, st_out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_a, bm, bn), a.dtype),
                   jax.ShapeDtypeStruct((p, q), x.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(jnp.asarray(op), jnp.asarray(ai), jnp.asarray(bi), a, b, x)
    mm = mm_tiles.reshape(n_i, n_j, bm, bn).transpose(0, 2, 1, 3).reshape(m, n)
    return mm, st_out
