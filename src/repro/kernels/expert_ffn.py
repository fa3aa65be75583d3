"""Grouped SwiGLU expert FFN over a layer's held experts (Pallas TPU).

Rows arrive grouped by expert in tiles of ``tm`` rows, each tile all of
one expert (an expert's last tile padded with zero rows), and the layer's
expert weights arrive whole: the stage's stacked ``(L, E, D, F)`` ``wi``
and ``wg`` and ``(L, E, F, D)`` ``wo``, with the layer, each tile's
expert and the number of tiles in use as scalar-prefetch operands. So
each grid step DMAs one ``bf``-wide block of one expert's three matrices
straight from the stacked weights, and no layer's weights are sliced or
copied first.

The grid is (tile, F block). A tile accumulates ``silu(x wg) * (x wi) wo``
over the F blocks in an f32 scratch and writes its rows once. Tiles past
those in use are neither fetched (their index maps repeat the last live
step's blocks, and a repeated block index issues no DMA) nor computed
(``pl.when``), so an expert with no rows reads no weights, and the row
bound can be static: at most every (token, expert) pair. Their output
rows are left unwritten; callers read only the rows of their pairs.
Matmul operands stay in the weights' dtype with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the F block: three (D, bf) weight blocks a step, double-buffered, large
# enough that a step's DMA dwarfs its fixed cost
MAX_BF = 512


def pick_tile(expected_rows: float) -> int:
    """Rows per tile: the power of two from 16 to 256 at or above twice
    the rows an expert is expected to get, so that most experts fill one
    tile and read their weights once."""
    tm = 16
    while tm < 256 and tm < 2 * expected_rows:
        tm *= 2
    return tm


def pick_bf(f: int) -> int:
    """The F block: the largest multiple of 128 up to ``MAX_BF`` dividing
    ``f`` (``f`` itself when it is not a multiple of 128)."""
    if f % 128:
        return f
    return max(b for b in range(128, min(f, MAX_BF) + 1, 128) if f % b == 0)


def _kernel(te_ref, n_ref, ly_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref,
            acc_ref, *, n_f: int):
    del te_ref, ly_ref                  # used by the index maps only
    i = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _live():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                   # (tm, D)
        up = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(wo_ref.dtype)   # (tm, bf)
        acc_ref[...] += jnp.dot(h, wo_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(f == n_f - 1)
        def _write():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_ffn(xs, wi, wg, wo, tile_expert, n_tiles, layer, *, tm: int,
               interpret: bool = False):
    """xs: (M, D), M a multiple of ``tm``, rows grouped by expert in
    tiles; wi, wg: (L, E, D, F); wo: (L, E, F, D); tile_expert: (M // tm,)
    int32, the expert of each tile; n_tiles, layer: int32 scalars, the
    tiles in use (the first ``n_tiles``) and the layer of the weights.

    Returns (M, D) in xs's dtype: row r of a tile in use is
    ``silu(x_r wg_e) * (x_r wi_e) @ wo_e`` of its tile's expert e; the
    rows of tiles not in use are unwritten.
    """
    m, d = xs.shape
    f = wi.shape[-1]
    bf = pick_bf(f)
    assert m % tm == 0 and f % bf == 0, (m, tm, f, bf)
    n_f = f // bf
    scalars = (tile_expert.astype(jnp.int32),
               jnp.reshape(n_tiles, (1,)).astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32))

    def live(i, f_blk, n_ref):
        """The step whose blocks step (i, f_blk) reads: itself, or for a
        tile not in use the last live step (no DMA)."""
        last = jnp.maximum(n_ref[0] - 1, 0)
        return (jnp.minimum(i, last),
                jnp.where(i <= last, f_blk, n_f - 1))

    def row_map(i, f_blk, te_ref, n_ref, ly_ref):
        return (live(i, f_blk, n_ref)[0], 0)

    def up_map(i, f_blk, te_ref, n_ref, ly_ref):       # wi, wg
        ii, ff = live(i, f_blk, n_ref)
        return (ly_ref[0], te_ref[ii], 0, ff)

    def down_map(i, f_blk, te_ref, n_ref, ly_ref):     # wo
        ii, ff = live(i, f_blk, n_ref)
        return (ly_ref[0], te_ref[ii], ff, 0)

    sq = pl.Squeezed()
    w_up = pl.BlockSpec((sq, sq, d, bf), up_map)
    item = jnp.dtype(wi.dtype).itemsize
    vmem = (2 * (3 * d * bf * item + 2 * tm * d * jnp.dtype(xs.dtype).itemsize)
            + tm * d * 4 + 2 * tm * bf * 4)
    return pl.pallas_call(
        functools.partial(_kernel, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(m // tm, n_f),
            in_specs=[pl.BlockSpec((tm, d), row_map), w_up, w_up,
                      pl.BlockSpec((sq, sq, bf, d), down_map)],
            out_specs=pl.BlockSpec((tm, d), row_map),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="expert_ffn",
    )(*scalars, xs, wi, wg, wo)
