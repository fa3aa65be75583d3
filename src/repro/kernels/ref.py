"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def sliced_matmul(a, b, slice_offsets_sizes=None):
    """Slicing never changes the result — the oracle is the full matmul."""
    return matmul(a, b)


def streaming_scale(x, scale):
    """The memory-bound co-scheduled op: y = x * scale (pure HBM traffic)."""
    return (x * scale).astype(x.dtype)


def coschedule(a, b, x, scale):
    """Fused interleave of matmul(a,b) and streaming_scale(x): results must
    equal running the two ops separately."""
    return matmul(a, b), streaming_scale(x, scale)


def flash_attention(q, k, v, *, causal=True):
    """q,k,v: (B, H, S, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def rwkv6(r, k, v, w_log, u, state=None):
    """Sequential WKV6 recurrence. r/k/v/w_log: (B, S, H, N); u: (H, N);
    state: (B, H, N, N) f32. Returns (out f32, final_state)."""
    bsz, s, h, n = r.shape
    if state is None:
        state = jnp.zeros((bsz, h, n, n), jnp.float32)
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    wf = w_log.astype(jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp                      # (B,H,N)
        kv = jnp.einsum("bhn,bhm->bhnm", kt, vt)
        out = jnp.einsum("bhn,bhnm->bhm", rt, S + u[None, ..., None] * kv)
        S = jnp.exp(wt)[..., None] * S + kv
        return S, out

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (rf, kf, vf, wf))
    state_f, outs = jax.lax.scan(step, state, xs)
    return outs.transpose(1, 0, 2, 3), state_f


def rg_lru(x, a_log, h0=None):
    """h_t = a_t h_{t-1} + sqrt(1-a_t^2) x_t. x, a_log: (B, S, W) f32."""
    b, s, w = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, w), jnp.float32)

    def step(h, inp):
        xt, at = inp
        a = jnp.exp(at)
        h = a * h + jnp.sqrt(jnp.maximum(1 - a * a, 1e-12)) * xt
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (x.astype(jnp.float32).transpose(1, 0, 2),
                          a_log.astype(jnp.float32).transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)


def expert_ffn(xs, wi, wg, wo, tile_expert, layer, *, tm):
    """Every ``tm``-row tile of xs (M, D) through its expert's SwiGLU FFN,
    from layer ``layer`` of the stacked wi, wg (L, E, D, F), wo (L, E, F,
    D): the grouped kernel's rows of the tiles it computes."""
    m, d = xs.shape
    x = xs.reshape(m // tm, tm, d)
    w_i, w_g, w_o = (w[layer][tile_expert] for w in (wi, wg, wo))
    up = jnp.einsum("tmd,tdf->tmf", x, w_i, preferred_element_type=jnp.float32)
    gate = jnp.einsum("tmd,tdf->tmf", x, w_g,
                      preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(wo.dtype)
    y = jnp.einsum("tmf,tfd->tmd", h, w_o, preferred_element_type=jnp.float32)
    return y.reshape(m, d).astype(xs.dtype)
