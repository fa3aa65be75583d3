"""Jit'd public wrappers for the Pallas kernels.

Kernels run in Pallas interpret mode on the CPU backend (tests) and are
compiled everywhere else; nothing can force interpret mode on a chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from repro.kernels import coschedule as _cs
from repro.kernels import decode_attention as _da
from repro.kernels import expert_ffn as _ef
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import rg_lru as _lru
from repro.kernels import rwkv6_scan as _wkv
from repro.kernels import sliced_matmul as _sm


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("slice_size", "bm", "bn", "bk"))
def sliced_matmul(a, b, *, slice_size: int = 4, bm: int = 128,
                  bn: int = 128, bk: int = 128):
    return _sm.sliced_matmul(a, b, slice_size=slice_size, bm=bm, bn=bn,
                             bk=bk, interpret=_default_interpret())


@functools.partial(jax.jit,
                   static_argnames=("scale", "run_a", "run_b", "bm", "bn", "bx"))
def coschedule(a, b, x, *, scale: float = 2.0, run_a: int = 1,
               run_b: int = 1, bm: int = 128, bn: int = 128, bx: int = 256):
    return _cs.coschedule(a, b, x, scale=scale, run_a=run_a, run_b=run_b,
                          bm=bm, bn=bn, bx=bx,
                          interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=_default_interpret())


@functools.lru_cache(maxsize=None)
def cache_positions_minor(shape: tuple, dtype, device=None) -> bool:
    """Whether ``device`` (the default one if None) stores a ``(L, B, S,
    KV, hd)`` cache with its positions (axis 2) minor-most, as a TPU does
    where the head size is not a multiple of 128 lanes."""
    device = device or jax.devices()[0]
    layout = Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(dtype), shape, device))
    return layout.major_to_minor[-1] == 2


def decode_attention(q, k_cache, v_cache, layer, t, k_new, v_new):
    """One token's attention over layer ``layer`` of a stacked cache, read
    in the orientation the device stores it in (see
    ``kernels/decode_attention.py``)."""
    return _da.decode_attention(
        q, k_cache, v_cache, layer, t, k_new, v_new,
        positions_minor=cache_positions_minor(k_cache.shape, k_cache.dtype),
        interpret=_default_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def expert_ffn(xs, wi, wg, wo, tile_expert, n_tiles, layer, tm):
    """The grouped expert FFN (``kernels/expert_ffn.py``) over ``tm``-row
    tiles. Its gradient is that of the plain ``ref.expert_ffn``."""
    return _ef.expert_ffn(xs, wi, wg, wo, tile_expert, n_tiles, layer,
                          tm=tm, interpret=_default_interpret())


def _expert_ffn_fwd(xs, wi, wg, wo, tile_expert, n_tiles, layer, tm):
    out = expert_ffn(xs, wi, wg, wo, tile_expert, n_tiles, layer, tm)
    return out, (xs, wi, wg, wo, tile_expert, layer)


def _expert_ffn_bwd(tm, res, g):
    xs, wi, wg, wo, tile_expert, layer = res
    _, vjp = jax.vjp(lambda *w: _ref.expert_ffn(*w, tile_expert, layer,
                                                tm=tm), xs, wi, wg, wo)
    return (*vjp(g), None, None, None)


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w_log, u, *, chunk: int = 32):
    return _wkv.rwkv6_scan(r, k, v, w_log, u, chunk=chunk,
                           interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "bw"))
def rg_lru(x, a_log, *, chunk: int = 128, bw: int = 512):
    return _lru.rg_lru(x, a_log, chunk=chunk, bw=bw,
                       interpret=_default_interpret())
