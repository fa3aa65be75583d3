"""What every plain reference in ``equations/`` shares: float32 at
``Precision.HIGHEST``, and the lower-precision control.

``quant`` rounds every matmul operand to a lower precision (``"int8"``:
symmetric, one scale per row of activations and per output column of
weights; ``"fp8"``: e4m3 with the same scales). A reference run with it is
the control the comparison must reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _round(x, axes, quant):
    """Round ``x`` to ``quant`` with one scale per slice over ``axes``."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if quant == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown quant {quant!r}")


def _mm(spec, x, w, quant, x_axes, w_axes):
    return jnp.einsum(spec, _round(x, x_axes, quant),
                      _round(w, w_axes, quant), precision=HI)
