"""Common layer primitives: norms, activations, rotary embeddings, inits.

All functions are pure (params passed explicitly) so that layers compose
under ``jax.lax.scan`` over stacked per-layer parameter pytrees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def dense_init(key, shape, scale: float = 1.0, dtype=jnp.bfloat16):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------- #
# norms (computed in f32, cast back)
# --------------------------------------------------------------------- #
def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params.get("bias"))


def init_norm(kind: str, d: int):
    p = {"scale": jnp.zeros((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


# --------------------------------------------------------------------- #
# activations
# --------------------------------------------------------------------- #
def act_fn(name: str):
    if name in ("swiglu", "silu"):
        return jax.nn.silu
    if name in ("geglu", "gelu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":                         # RWKV channel-mix
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


# --------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(scale) + 1."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * float(np.log(scale)) + 1.0


def yarn_frequencies(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's rotary frequencies: interpolated by ``yarn.factor`` below
    the correction range of ``beta_fast``..``beta_slow`` rotations over
    the original context, extrapolated above it, ramped between."""
    def dim_of(rotations):
        return head_dim * np.log(yarn.original_max_position_embeddings
                                 / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(int(np.floor(dim_of(yarn.beta_fast))), 0)
    high = min(int(np.ceil(dim_of(yarn.beta_slow))), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = rope_frequencies(head_dim, theta)
    inter = extra / np.float32(yarn.factor)
    keep = 1.0 - ramp                           # 1: extrapolate
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x, positions, theta: float = 10000.0, yarn=None):
    """x: (..., S, H, D); positions: broadcastable to (..., S). ``yarn``
    (a ``YarnConfig``) scales the frequencies, and cos and sin by
    mscale / mscale_all_dim."""
    d = x.shape[-1]
    if yarn is None:
        freqs = jnp.asarray(rope_frequencies(d, theta))       # (d/2,)
    else:
        freqs = jnp.asarray(yarn_frequencies(d, theta, yarn))
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple:
    """3-way split of the d/2 frequency bands (temporal, height, width)."""
    h2 = head_dim // 2
    a = h2 // 4
    b = (h2 - a) // 2
    return (a, b, h2 - a - b)


def apply_mrope(x, positions3, theta: float = 10000.0):
    """Multimodal RoPE (Qwen2-VL). positions3: (..., 3, S) t/h/w position ids.

    For pure-text tokens t==h==w, in which case this equals standard RoPE.
    """
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta))           # (d/2,)
    secs = mrope_sections(d)
    # angle per frequency band: temporal / height / width position ids each
    # drive their own contiguous band of frequencies
    p = positions3.astype(jnp.float32)                        # (...,3,S)
    ang_parts = []
    start = 0
    for axis_i, n in enumerate(secs):
        ang_parts.append(p[..., axis_i, :, None] * freqs[start:start + n])
        start += n
    ang = jnp.concatenate(ang_parts, axis=-1)[..., :, None, :]  # (...,S,1,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positional(x, q_pos, pos_kind: str, theta: float):
    if pos_kind == "rope":
        return apply_rope(x, q_pos, theta)
    if pos_kind == "mrope":
        p3 = jnp.broadcast_to(q_pos[..., None, :],
                              q_pos.shape[:-1] + (3, q_pos.shape[-1]))
        return apply_mrope(x, p3, theta)
    return x                                                   # learned/none


# --------------------------------------------------------------------- #
# MLP / FFN
# --------------------------------------------------------------------- #
def init_mlp(key, d: int, f: int, act: str, n_layers: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wi": dense_init(k1, (d, f), dtype=dtype),
         "wo": dense_init(k2, (f, d), scale=1.0 / np.sqrt(2 * n_layers), dtype=dtype)}
    if is_gated(act):
        p["wg"] = dense_init(k3, (d, f), dtype=dtype)
    return p


def mlp(x, p, act: str):
    h = x @ p["wi"]
    if is_gated(act):
        h = act_fn(act)(x @ p["wg"]) * h
    else:
        h = act_fn(act)(h)
    return h @ p["wo"]
