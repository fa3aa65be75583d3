"""Single-token decode attention over a stage's stacked KV cache (Pallas TPU).

The cache is read where it lies: the whole ``(L, B, S, KV, hd)`` stage
cache goes in, and the layer and the position ``t`` arrive as
scalar-prefetch operands, so each grid step DMAs one block of one layer's
k and v straight from HBM. Positions ``< t`` come from the cache; the new
token's own k and v (not yet written) are merged into the online softmax
at the end. Blocks past ``t`` are neither fetched (the index map clamps
them to the last live block, and a repeated block index issues no DMA)
nor computed (``pl.when``).

Blocks are read in the order the cache is stored in, so that XLA puts no
relayout of the whole cache in front of the kernel. A TPU stores a cache
whose head size is not a multiple of 128 lanes (phi3's 96) with its
positions minor, ``(KV, hd, S)`` per (layer, batch row), which leaves no
padding: there ``positions_minor`` reads ``(KV, hd, block)`` blocks, and
the wrapper's transpose to that order is a bitcast. Otherwise the cache is
row-major and the kernel reads ``(block, KV, hd)`` blocks. Both products
run on the MXU, batched over kv heads, with operands in the cache's dtype
and f32 accumulation: q . k is exact, and the softmax weights are rounded
to the cache's dtype for p . v, as in the chunked prefill path. Softmax
statistics are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# k and v blocks together (one buffer each): large enough that the DMA
# runs at bandwidth, small enough to double-buffer in scoped VMEM
BLOCK_BYTES = 2 << 20


def pick_block(s: int, kv: int, hd: int, itemsize: int) -> int:
    """Positions per block: the largest multiple of 128 dividing ``s``
    whose k and v blocks fit ``BLOCK_BYTES`` (``s`` itself when it is
    not a multiple of 128)."""
    if s % 128:
        return s
    per_pos = 2 * kv * hd * itemsize
    best = 128
    for bs in range(128, s + 1, 128):
        if s % bs == 0 and bs * per_pos <= BLOCK_BYTES:
            best = bs
    return best


def _kernel(layer_ref, t_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, n_blk: int, scale: float,
            positions_minor: bool):
    del layer_ref                       # used by the index maps only
    j = pl.program_id(1)
    t = t_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < t)
    def _attend():
        q = q_ref[0]                                   # (KV, g, hd)
        k = k_ref[...].astype(q.dtype)                 # (KV, hd, bs)
        v = v_ref[...]
        if not positions_minor:
            k = jnp.swapaxes(k, 0, 1)
            v = jnp.swapaxes(v, 0, 1)
        kd, vd = (1, 2) if positions_minor else (2, 1)
        s = jax.lax.dot_general(
            q, k, (((2,), (kd,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (KV, g, bs)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < t, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (vd,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # (KV, g, hd)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == n_blk - 1)
    def _finish():
        q = q_ref[0].astype(jnp.float32)               # (KV, g, hd)
        kn = kn_ref[0].astype(jnp.float32)             # (KV, 1, hd)
        vn = vn_ref[0].astype(jnp.float32)
        s = jnp.sum(q * kn, axis=-1, keepdims=True) * scale   # (KV, g, 1)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc = acc_ref[...] * alpha + p * vn
        o_ref[0] = (acc / (l_ref[...] * alpha + p)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, layer, t, k_new, v_new, *,
                     block: int | None = None, interpret: bool = False,
                     positions_minor: bool = True):
    """q: (B, H, hd); k/v_cache: (L, B, S, KV, hd); layer, t: int32
    scalars; k/v_new: (B, KV, hd), the token at position ``t``.

    Returns (B, H, hd) in q's dtype: softmax attention of each q head
    over positions ``0..t`` of its kv head in cache ``layer``, with
    position ``t`` taken from ``k_new``/``v_new``.
    """
    b, h, hd = q.shape
    _, _, s, kvh, _ = k_cache.shape
    g = h // kvh
    dt = jnp.promote_types(q.dtype, k_cache.dtype)
    bs = block or pick_block(s, kvh, hd, jnp.dtype(k_cache.dtype).itemsize)
    assert s % bs == 0, (s, bs)
    n_blk = s // bs
    # positions minor, (L, B, KV, hd, S): a bitcast of a cache stored so,
    # a copy of any other
    perm = (0, 1, 3, 4, 2) if positions_minor else (0, 1, 2, 3, 4)
    kt = jnp.transpose(k_cache, perm)
    vt = jnp.transpose(v_cache, perm)
    qg = q.reshape(b, kvh, g, hd).astype(dt)
    kn = k_new.reshape(b, kvh, 1, hd)
    vn = v_new.reshape(b, kvh, 1, hd)
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32),
               jnp.reshape(t, (1,)).astype(jnp.int32))

    def cache_map(bi, j, layer_ref, t_ref):
        last = jnp.maximum(t_ref[0] - 1, 0) // bs      # last live block
        jj = jnp.minimum(j, last)
        if positions_minor:
            return (layer_ref[0], bi, 0, 0, jj)
        return (layer_ref[0], bi, jj, 0, 0)

    def row_map(bi, j, layer_ref, t_ref):
        return (bi, 0, 0, 0)

    sq = pl.Squeezed()
    cache_spec = pl.BlockSpec((sq, sq, kvh, hd, bs) if positions_minor
                              else (sq, sq, bs, kvh, hd), cache_map)
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_blk=n_blk,
                          scale=1.0 / np.sqrt(hd),
                          positions_minor=positions_minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blk),
            in_specs=[pl.BlockSpec((1, kvh, g, hd), row_map),
                      cache_spec, cache_spec,
                      pl.BlockSpec((1, kvh, 1, hd), row_map),
                      pl.BlockSpec((1, kvh, 1, hd), row_map)],
            out_specs=pl.BlockSpec((1, kvh, g, hd), row_map),
            scratch_shapes=[pltpu.VMEM((kvh, g, 1), jnp.float32),
                            pltpu.VMEM((kvh, g, 1), jnp.float32),
                            pltpu.VMEM((kvh, g, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(*scalars, qg, kt, vt, kn, vn)
    return out.reshape(b, h, hd)
