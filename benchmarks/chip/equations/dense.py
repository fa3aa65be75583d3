"""Equations of a dense decoder: a stack of pre-norm layers, each full
causal self-attention with rotary positions (grouped-query where
``num_key_value_heads`` < ``num_attention_heads``) and a gated SiLU MLP.

A configuration file names this module as ``"program": {"equations":
"dense"}``. It gives the harness:

- ``check(cfg, config)``: the program's ``ModelConfig`` runs what the file
  states, or set-up fails;
- ``Reference(config)``: the plain float32 reference of the served logits;
- ``step`` and ``param_count``: the work a step requires, counted from the
  file's sizes whatever program implements it;
- ``kernels``: the work each kernel of the step requires, by the stem of
  its name in the device trace.

Required work: causal attention counts each (query, key) pair with key <=
query once; every weight is read once per step; a decode step reads the
cache only up to its position; activations between layers are not counted
(a fused program need not spill them). Matmul FLOPs are 2 per multiply-add.

The reference is straight ``jax.numpy`` from the file's equations (Hugging
Face ``config.json`` keys), every matmul at ``Precision.HIGHEST``, one
layer at a time so that it fits beside the served weights. It imports
nothing of the program under test. It reads the benchmark's own weights
(``weights.py``) in the layout the program is served from:

  embed (V, d); lm_head (d, V); final_norm {scale[, bias]};
  stage0/sub0: layer-stacked norm1, norm2 {scale[, bias]},
    attn {wq (d, h, hd), wk (d, kv, hd), wv (d, kv, hd), wo (h, hd, d)},
    mlp {wg (d, f) gate, wi (d, f) up, wo (f, d) down}.

A norm's weight is stored as an offset from one: weight = 1 + scale.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, _mm
from work import DTYPE_BYTES


# --------------------------------------------------------------------- #
# the program runs what the file states
# --------------------------------------------------------------------- #
def check(cfg, config: dict):
    """Fail unless the program's ``ModelConfig`` ``cfg`` is the dense
    decoder the file ``config`` states."""
    want = {"num_layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "rope_theta": float(config["rope_theta"]),
            "norm": "layernorm" if "layer_norm_eps" in config else "rmsnorm",
            "act": "swiglu", "dtype": config["torch_dtype"],
            "tie_embeddings": config["tie_word_embeddings"],
            "pos_kind": "rope", "block_pattern": ("attn",),
            "attention_kind": "full", "moe": None, "mla": None}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise RuntimeError(f"the program's config departs from the file: "
                           f"{diff} (program, file)")


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #
class Reference:
    """The equations of one dense decoder configuration. ``quant`` runs
    them with every matmul operand rounded to a lower precision
    (``reference._round``): the control the comparison must reject."""

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


# --------------------------------------------------------------------- #
# required work
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    norm: str              # "rmsnorm" | "layernorm"
    tied: bool
    wbytes: int            # bytes per weight and cache element


def dims(cfg: dict) -> Dims:
    """Sizes of a dense decoder from its configuration file (Hugging Face
    ``config.json`` keys)."""
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return Dims(
        layers=cfg["num_hidden_layers"], d=d, heads=heads,
        kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or d // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        gated=cfg.get("hidden_act", "silu") in ("silu", "swiglu"),
        norm="layernorm" if "layer_norm_eps" in cfg else "rmsnorm",
        tied=bool(cfg.get("tie_word_embeddings", False)),
        wbytes=DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")])


def layer_matmul_params(m: Dims) -> int:
    attn = m.d * m.heads * m.head_dim * 2 + 2 * m.d * m.kv_heads * m.head_dim
    mlp = (3 if m.gated else 2) * m.d * m.d_ff
    return attn + mlp


def norm_params(m: Dims) -> int:
    return m.d * (2 if m.norm == "layernorm" else 1)


def param_count(cfg: dict) -> int:
    """Every parameter: embedding, layers (projections and two norms each),
    final norm and head."""
    m = dims(cfg)
    embed = m.vocab * m.d * (1 if m.tied else 2)
    per_layer = layer_matmul_params(m) + 2 * norm_params(m)
    return embed + m.layers * per_layer + norm_params(m)


def streamed_weight_bytes(m: Dims, tokens: int) -> int:
    """Weights a step reads once: every layer, final norm and head, plus
    the embedding rows of its tokens (a gather, not the whole table)."""
    n = (m.layers * (layer_matmul_params(m) + 2 * norm_params(m))
         + norm_params(m) + m.vocab * m.d)
    return (n + tokens * m.d) * m.wbytes


def prefill(cfg: dict, batch: int, seq: int) -> dict:
    """One prefill slice: ``batch`` prompts of ``seq`` tokens, logits at
    every position."""
    m = dims(cfg)
    tokens = batch * seq
    matmul = 2 * tokens * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    pairs = seq * (seq + 1) // 2                 # causal, diagonal included
    attn = m.layers * batch * 4 * m.heads * m.head_dim * pairs
    nbytes = (streamed_weight_bytes(m, tokens) + tokens * 4       # ids in
              + tokens * m.vocab * m.wbytes)                      # logits out
    return {"flops": float(matmul + attn), "bytes": float(nbytes),
            "tokens": tokens}


def decode(cfg: dict, batch: int, pos: int) -> dict:
    """One decode step for ``batch`` sequences whose new token sits at
    position ``pos``: attention over the ``pos`` cached positions and the
    new one; the cache is read only up to ``pos``."""
    m = dims(cfg)
    matmul = 2 * batch * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    attn = m.layers * batch * 4 * m.heads * m.head_dim * (pos + 1)
    cache = m.layers * batch * pos * 2 * m.kv_heads * m.head_dim * m.wbytes
    nbytes = (streamed_weight_bytes(m, batch) + cache + batch * 4
              + batch * m.vocab * m.wbytes)
    return {"flops": float(matmul + attn), "bytes": float(nbytes),
            "tokens": batch}


def step(cfg: dict, phase: str, batch: int, seq: int) -> dict:
    """Required work of one slice of a tenant, as the traffic file states
    it: a prefill over ``seq`` tokens, or a decode step at ``seq // 2`` of a
    ``seq``-long cache."""
    if phase == "prefill":
        return prefill(cfg, batch, seq)
    if phase == "decode":
        return decode(cfg, batch, seq // 2)
    raise ValueError(f"unknown phase {phase!r}")


def kernels(cfg: dict, phase: str, batch: int, seq: int) -> dict:
    """Required work, per step, of each kernel this module defines, by the
    stem of its name in the device trace. ``decode_attention``: one
    token's attention at position ``seq // 2`` over every layer, reading
    the query, k and v of positions 0..pos (the cached ones and the new
    token's) and writing the output."""
    if phase != "decode":
        return {}
    m = dims(cfg)
    pos = seq // 2
    q_out = 2 * batch * m.heads * m.head_dim
    kv = 2 * batch * (pos + 1) * m.kv_heads * m.head_dim
    return {"decode_attention": {
        "flops": float(m.layers * batch * 4 * m.heads * m.head_dim
                       * (pos + 1)),
        "bytes": float(m.layers * (q_out + kv) * m.wbytes)}}
