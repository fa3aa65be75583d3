"""Plain float32 reference of the dense decoders the benchmark serves.

Straight ``jax.numpy`` from the configuration file's equations (Hugging
Face ``config.json`` keys), with every matmul at ``Precision.HIGHEST``,
computed one layer at a time so that it fits beside the served weights.
It imports nothing of the program under test. It reads the benchmark's
own weights (``weights.py``) in the layout the program is served from:

  embed (V, d); lm_head (d, V); final_norm {scale[, bias]};
  stage0/sub0: layer-stacked norm1, norm2 {scale[, bias]},
    attn {wq (d, h, hd), wk (d, kv, hd), wv (d, kv, hd), wo (h, hd, d)},
    mlp {wg (d, f) gate, wi (d, f) up, wo (f, d) down}.

A norm's weight is stored as an offset from one: weight = 1 + scale.

``quant`` runs the same equations with every matmul operand rounded to a
lower precision (``"int8"``: symmetric, one scale per row of activations
and per output column of weights; ``"fp8"``: e4m3 with the same scales).
That is the control the comparison must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


class Dense:
    """The equations of one dense decoder configuration."""

    def __init__(self, cfg: dict):
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg.get("num_key_value_heads", self.heads)
        self.head_dim = (cfg.get("head_dim")
                         or cfg["hidden_size"] // self.heads)
        self.layers = cfg["num_hidden_layers"]
        self.layernorm = "layer_norm_eps" in cfg
        self.eps = cfg["layer_norm_eps" if self.layernorm else "rms_norm_eps"]
        rot = int(self.head_dim * cfg.get("partial_rotary_factor", 1.0))
        self.rot = rot - rot % 2
        self.theta = float(cfg.get("rope_theta", 10000.0))
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError("reference covers gated SiLU MLPs only")
        self._programs: dict = {}

    # -- pieces ---------------------------------------------------------
    def norm(self, x, p):
        w = 1.0 + p["scale"].astype(F32)
        if self.layernorm:
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + self.eps) * w \
                + p["bias"].astype(F32)
        ms = jnp.mean(jnp.square(x), -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) * w

    def rope(self, x, pos):
        """Rotate the first ``rot`` dims of each head (rotate-half form).
        x: (B, S, H, hd); pos: (S,) or (B, S)."""
        r = self.rot
        inv = 1.0 / (self.theta ** (np.arange(0, r, 2, dtype=np.float64)
                                    / r))
        ang = jnp.asarray(pos, F32)[..., None] * jnp.asarray(inv, F32)
        if ang.ndim == 2:
            ang = ang[None]
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
        x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                                rest], -1)

    def mlp(self, x, p, quant):
        gate = _mm("bsd,df->bsf", x, p["wg"].astype(F32), quant, -1, 0)
        up = _mm("bsd,df->bsf", x, p["wi"].astype(F32), quant, -1, 0)
        return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   p["wo"].astype(F32), quant, -1, 0)

    def qkv(self, x, p, pos, quant):
        q = _mm("bsd,dhk->bshk", x, p["wq"].astype(F32), quant, -1, 0)
        k = _mm("bsd,dhk->bshk", x, p["wk"].astype(F32), quant, -1, 0)
        v = _mm("bsd,dhk->bshk", x, p["wv"].astype(F32), quant, -1, 0)
        return self.rope(q, pos), self.rope(k, pos), v

    def attend(self, q, k, v, mask, quant):
        """q (B,Sq,H,hd), k/v (B,Sk,KV,hd), mask (Sq,Sk) or (B,Sq,Sk)."""
        g = self.heads // self.kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = _mm("bqhk,bshk->bhqs", q, k, quant, -1, -1) / np.sqrt(
            self.head_dim)
        s = jnp.where(mask[None, None] if mask.ndim == 2
                      else mask[:, None], s, -jnp.inf)
        return _mm("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, quant,
                   -1, 1)

    def out(self, o, p, quant):
        return _mm("bshk,hkd->bsd", o, p["wo"].astype(F32), quant,
                   (-2, -1), (0, 1))

    # -- layers, one compiled program each ------------------------------
    def prefill_layer(self, x, stack, i, quant):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        s = x.shape[1]
        pos = jnp.arange(s)
        h = self.norm(x, p["norm1"])
        q, k, v = self.qkv(h, p["attn"], pos, quant)
        causal = pos[:, None] >= pos[None, :]
        x = x + self.out(self.attend(q, k, v, causal, quant), p["attn"],
                         quant)
        return x + self.mlp(self.norm(x, p["norm2"]), p["mlp"], quant)

    def decode_layer(self, x, stack, kc, vc, i, t, quant):
        """x (B, 1, d); kc/vc (L, B, S, KV, hd): the cache before the
        step; the new token sits at position t and sees 0..t."""
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        kc = kc[i].astype(F32)
        vc = vc[i].astype(F32)
        h = self.norm(x, p["norm1"])
        q, k, v = self.qkv(h, p["attn"], jnp.full((1,), t), quant)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, t, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, t, 1)
        mask = (jnp.arange(kc.shape[1]) <= t)[None, :]
        x = x + self.out(self.attend(q, kc, vc, mask, quant), p["attn"],
                         quant)
        return x + self.mlp(self.norm(x, p["norm2"]), p["mlp"], quant)

    def head(self, x, params, quant):
        h = self.norm(x, params["final_norm"])
        return _mm("bsd,dv->bsv", h, params["lm_head"].astype(F32), quant,
                   -1, 0)


    # -- whole passes ----------------------------------------------------
    def _jit(self, quant):
        if quant not in self._programs:
            self._programs[quant] = (
                jax.jit(lambda e, tok: jnp.take(e, tok, axis=0).astype(F32)),
                jax.jit(functools.partial(self.prefill_layer, quant=quant)),
                jax.jit(functools.partial(self.decode_layer, quant=quant)),
                jax.jit(functools.partial(self.head, quant=quant)))
        return self._programs[quant]

    def prefill_logits(self, params, tokens, quant=None):
        """Logits (B, S, V) in float32 at every position of ``tokens``."""
        embed, layer, _, head = self._jit(quant)
        stack = params["stage0"]["sub0"]
        x = embed(params["embed"], tokens)
        for i in range(self.layers):
            x = layer(x, stack, np.int32(i))
        return head(x, params)

    def decode_logits(self, params, caches, tokens, t, quant=None):
        """Next-token logits (B, V) of ``tokens`` (B,) at position ``t``
        over ``caches`` (stage0/sub0 k, v of shape (L, B, S, KV, hd))."""
        embed, _, layer, head = self._jit(quant)
        stack = params["stage0"]["sub0"]
        kv = caches["stage0"]["sub0"]
        x = embed(params["embed"], tokens[:, None])
        for i in range(self.layers):
            x = layer(x, stack, kv["k"], kv["v"], np.int32(i), np.int32(t))
        return head(x, params)[:, 0]
