"""Equations of DeepSeek-V2 (arXiv:2405.04434) as one chip's share of an
expert-parallel deployment: a stack of pre-norm layers, each multi-head
latent attention (MLA) with YaRN rotary dims, then a SwiGLU MLP (the
first ``first_k_dense_replace`` layers) or a mixture of experts.

A configuration file names this module as ``"program": {"equations":
"deepseek_v2"}``. It gives the harness ``check``, ``Reference``, ``step``,
``param_count`` and ``kernels``, as ``dense.py`` does.

The share. ``deployment.published_experts`` experts are split into
``n_group`` routing groups, one a chip; this chip holds the
``n_routed_experts`` experts from ``deployment.held_experts[0]``. Every
token is routed over all of them as published: softmax scores in
float32; a group's score is its best expert's; the top ``num_experts_per_tok``
come from the ``topk_group`` best groups; their weights are scaled by
``routed_scaling_factor`` (or renormalised, with ``norm_topk_prob``). The
layer's output is what the held experts add, weighted, plus the shared
experts (``n_shared_experts`` of ``moe_intermediate_size``, one SwiGLU),
and that partial result goes on to the next layer, in the program and
here alike.

MLA: q = rmsnorm(x Wq_a) Wq_b, per head ``qk_nope_head_dim`` +
``qk_rope_head_dim``; [c, k_pe] = x Wkv_a, c = rmsnorm(c); [k_nope, v] =
c Wkv_b per head; k_pe is shared by the heads; rope on q_pe and k_pe;
softmax scale ``(nope + rope)**-0.5`` times YaRN's
``mscale(factor, mscale_all_dim)**2``. The reference expands k and v
(it never absorbs Wkv_b into q); a decode cache holds c and the roped
k_pe of each position.

Required work: as ``dense.py``; decode attention is counted in the
absorbed form, the least work (q_nope through Wkv_b's key half, scores
and weighted sum over the latent cache, then Wkv_b's value half); expert
work is the pairs a held group gets in expectation, ``T * top_k *
n_held / published_experts`` (every group alike by symmetry), while
every held expert's weights are read once a step.

The reference is straight ``jax.numpy``, float32 at ``Precision.HIGHEST``,
one layer at a time, the held experts one at a time, and decode
attention in blocks of ``DECODE_BLOCK`` sequences, so that it fits beside
the served weights. It imports nothing of the program. Weights
(``weights.py``), in the program's layout:

  embed (V, d); lm_head (d, V); final_norm {scale};
  stage0/sub0: the dense layers, stage1/sub0: the expert layers, each
    layer-stacked: norm1, norm2 {scale}; attn {wq_a (d, q_lora), q_norm,
    wq_b (q_lora, H, nope+rope), wkv_a (d, kv_lora+rope), kv_norm,
    wkv_b (kv_lora, H, nope+v), wo (H, v, d)}; mlp {wg, wi (d, f),
    wo (f, d)} or moe {router (d, E) float32, wg, wi (E_h, d, f),
    wo (E_h, f, d), shared {wg, wi (d, f_s), wo (f_s, d)}}.
  Decode caches: stageN/sub0 {ckv (L, B, S, kv_lora), krope (L, B, S,
  rope)}.

A norm's weight is stored as an offset from one: weight = 1 + scale.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, _mm
from work import DTYPE_BYTES

DECODE_BLOCK = 8         # sequences a decode attention block expands


# --------------------------------------------------------------------- #
# the configuration
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    d_ff: int
    f_expert: int
    shared: int            # shared experts, one SwiGLU of shared * f_expert
    experts: int           # routed experts, all chips
    held_start: int
    held: int              # routed experts on this chip
    top_k: int
    n_group: int
    topk_group: int
    vocab: int
    wbytes: int
    eps: float


def dims(cfg: dict) -> Dims:
    dep = cfg["deployment"]
    start, stop = dep["held_experts"]
    if stop - start != cfg["n_routed_experts"]:
        raise ValueError(f"held_experts {start}-{stop} is not "
                         f"n_routed_experts {cfg['n_routed_experts']}")
    return Dims(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], q_lora=cfg["q_lora_rank"],
        kv_lora=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], f_expert=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"], experts=dep["published_experts"],
        held_start=start, held=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], vocab=cfg["vocab_size"],
        wbytes=DTYPE_BYTES[cfg.get("torch_dtype", "bfloat16")],
        eps=cfg["rms_norm_eps"])


def check(cfg, config: dict):
    """Fail unless the program's ``ModelConfig`` ``cfg`` is the model and
    share the file ``config`` states."""
    m = dims(config)
    rs = config["rope_scaling"]
    want = {"num_layers": m.layers, "d_model": m.d, "num_heads": m.heads,
            "d_ff": m.d_ff, "vocab_size": m.vocab,
            "rope_theta": float(config["rope_theta"]), "norm": "rmsnorm",
            "act": "swiglu", "dtype": config["torch_dtype"],
            "tie_embeddings": config["tie_word_embeddings"],
            "pos_kind": "rope", "block_pattern": ("attn",),
            "attention_kind": "full",
            "mla": (m.kv_lora, m.q_lora, m.nope, m.rope, m.v),
            "yarn": (float(rs["factor"]),
                     rs["original_max_position_embeddings"],
                     float(rs["beta_fast"]), float(rs["beta_slow"]),
                     float(rs["mscale"]), float(rs["mscale_all_dim"])),
            "moe": (m.experts, m.top_k, m.f_expert, m.shared,
                    m.dense_layers, config["scoring_func"], m.n_group,
                    m.topk_group, config["norm_topk_prob"],
                    float(config["routed_scaling_factor"]),
                    (m.held_start, m.held_start + m.held))}
    got = {k: getattr(cfg, k) for k in want if k not in ("mla", "yarn",
                                                         "moe")}
    ml, y, mo = cfg.mla, cfg.yarn, cfg.moe
    got["mla"] = ml and (ml.kv_lora_rank, ml.q_lora_rank, ml.qk_nope_dim,
                         ml.qk_rope_dim, ml.v_head_dim)
    got["yarn"] = y and (y.factor, y.original_max_position_embeddings,
                         y.beta_fast, y.beta_slow, y.mscale,
                         y.mscale_all_dim)
    got["moe"] = mo and (mo.num_experts, mo.top_k, mo.d_ff_expert,
                         mo.num_shared_experts, mo.first_dense_layers,
                         mo.router_act, mo.n_group, mo.topk_group,
                         mo.norm_topk_prob, mo.routed_scaling_factor,
                         mo.held)
    # the program's RMSNorm has a fixed epsilon and its scaled rope is YaRN
    want["rms_norm_eps"], got["rms_norm_eps"] = m.eps, 1e-6
    want["rope_scaling.type"], got["rope_scaling.type"] = rs["type"], "yarn"
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise RuntimeError(f"the program's config departs from the file: "
                           f"{diff} (program, file)")


# --------------------------------------------------------------------- #
# YaRN (Hugging Face's DeepseekV2YarnRotaryEmbedding)
# --------------------------------------------------------------------- #
def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict) -> np.ndarray:
    """The rope dims' frequencies under ``rope_scaling`` ``rs``."""
    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = 1.0 / (rs["factor"]
                   * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #
class Reference:
    """The equations of one DeepSeek-V2 share. ``quant`` runs them with
    every matmul operand rounded to a lower precision
    (``reference._round``): the control the comparison must reject."""

    def __init__(self, cfg: dict):
        self.m = dims(cfg)
        rs = cfg["rope_scaling"]
        self.inv_freq = yarn_inv_freq(self.m.rope, float(cfg["rope_theta"]),
                                      rs)
        self.rope_scale = (yarn_mscale(rs["factor"], rs["mscale"])
                           / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
        self.softmax_scale = ((self.m.nope + self.m.rope) ** -0.5
                              * yarn_mscale(rs["factor"],
                                            rs["mscale_all_dim"]) ** 2)
        self.norm_topk = cfg["norm_topk_prob"]
        self.route_scale = float(cfg["routed_scaling_factor"])
        if cfg["scoring_func"] != "softmax" or \
                cfg["topk_method"] != "group_limited_greedy":
            raise ValueError("reference covers softmax group-limited "
                             "greedy routing only")
        self._programs: dict = {}

    # -- pieces ---------------------------------------------------------
    def norm(self, x, p):
        ms = jnp.mean(jnp.square(x), -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.m.eps) * (1.0 + p["scale"]
                                                     .astype(F32))

    def rope(self, x, pos):
        """Rotate-half rope of x (B, S, H, rope) at positions pos (S,)."""
        ang = jnp.asarray(pos, F32)[:, None] * jnp.asarray(self.inv_freq)
        cos = (jnp.cos(ang) * self.rope_scale)[None, :, None, :]
        sin = (jnp.sin(ang) * self.rope_scale)[None, :, None, :]
        h = x.shape[-1] // 2
        x1, x2 = x[..., :h], x[..., h:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               -1)

    def swiglu(self, x, p, quant):
        gate = _mm("td,df->tf", x, p["wg"].astype(F32), quant, -1, 0)
        up = _mm("td,df->tf", x, p["wi"].astype(F32), quant, -1, 0)
        return _mm("tf,fd->td", jax.nn.silu(gate) * up,
                   p["wo"].astype(F32), quant, -1, 0)

    def route(self, x, router, quant):
        """Each token's weight on each of all the experts (T, E), zero
        where not among its top k."""
        m = self.m
        scores = jax.nn.softmax(_mm("td,de->te", x, router.astype(F32),
                                    quant, -1, 0), -1)
        t = scores.shape[0]
        group = scores.reshape(t, m.n_group, -1).max(-1)       # (T, G)
        _, top_g = jax.lax.top_k(group, m.topk_group)
        in_top = jnp.zeros((t, m.n_group), bool).at[
            jnp.arange(t)[:, None], top_g].set(True)
        masked = jnp.where(jnp.repeat(in_top, m.experts // m.n_group, -1),
                           scores, 0.0)
        w, idx = jax.lax.top_k(masked, m.top_k)
        if self.norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        else:
            w = w * self.route_scale
        return jnp.zeros_like(scores).at[jnp.arange(t)[:, None], idx].set(w)

    def moe(self, x, p, quant):
        """x (T, d): the held experts' weighted outputs plus the shared
        experts'."""
        m = self.m
        w = self.route(x, p["router"], quant)[:, m.held_start:
                                              m.held_start + m.held]

        def expert(acc, e):
            pe = {k: p[k][e] for k in ("wi", "wg", "wo")}
            return acc + w[:, e, None] * self.swiglu(x, pe, quant), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m.held))
        if m.shared:
            out = out + self.swiglu(x, p["shared"], quant)
        return out

    def ffn(self, x, p, is_moe, quant):
        b, s, d = x.shape
        x2 = x.reshape(b * s, d)
        y = self.moe(x2, p["moe"], quant) if is_moe else \
            self.swiglu(x2, p["mlp"], quant)
        return y.reshape(b, s, d)

    def latents(self, x, p, pos, quant):
        """q (B, S, H, nope + rope) with roped q_pe; the latent c (B, S,
        kv_lora) and roped k_pe (B, S, rope) of the same positions."""
        m = self.m
        q_lat = self.norm(_mm("bsd,dr->bsr", x, p["wq_a"].astype(F32),
                              quant, -1, 0), p["q_norm"])
        q = _mm("bsr,rhk->bshk", q_lat, p["wq_b"].astype(F32), quant, -1, 0)
        q = jnp.concatenate([q[..., :m.nope], self.rope(q[..., m.nope:],
                                                        pos)], -1)
        kv_a = _mm("bsd,dr->bsr", x, p["wkv_a"].astype(F32), quant, -1, 0)
        c = self.norm(kv_a[..., :m.kv_lora], p["kv_norm"])
        k_pe = self.rope(kv_a[..., None, m.kv_lora:], pos)[:, :, 0]
        return q, c, k_pe

    def attend(self, q, c, k_pe, p, mask, quant):
        """Expanded MLA attention of q (B, Sq, H, nope+rope) over the
        keys' latents c (B, Sk, kv_lora) and k_pe (B, Sk, rope); mask
        (Sq, Sk). Returns the heads' outputs (B, Sq, H, v)."""
        m = self.m
        kv = _mm("bsr,rhk->bshk", c, p["wkv_b"].astype(F32), quant, -1, 0)
        k_nope, v = kv[..., :m.nope], kv[..., m.nope:]
        s = (_mm("bqhk,bshk->bhqs", q[..., :m.nope], k_nope, quant, -1, -1)
             + _mm("bqhk,bsk->bhqs", q[..., m.nope:], k_pe, quant, -1, -1))
        s = jnp.where(mask[None, None], s * self.softmax_scale, -jnp.inf)
        return _mm("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, quant, -1, 1)

    def out(self, o, p, quant):
        return _mm("bshk,hkd->bsd", o, p["wo"].astype(F32), quant,
                   (-2, -1), (0, 1))

    # -- layers, one compiled program each ------------------------------
    def prefill_layer(self, x, stack, i, quant, is_moe):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        pos = jnp.arange(x.shape[1])
        h = self.norm(x, p["norm1"])
        q, c, k_pe = self.latents(h, p["attn"], pos, quant)
        o = self.attend(q, c, k_pe, p["attn"], pos[:, None] >= pos[None, :],
                        quant)
        x = x + self.out(o, p["attn"], quant)
        return x + self.ffn(self.norm(x, p["norm2"]), p, is_moe, quant)

    def decode_layer(self, x, stack, cache, i, t, quant, is_moe):
        """x (B, 1, d); cache {ckv (L, B, S, kv_lora), krope (L, B, S,
        rope)}: before the step; the new token sits at position t and
        sees 0..t. Attention is expanded ``DECODE_BLOCK`` sequences at a
        time."""
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        ckv = cache["ckv"][i].astype(F32)
        krope = cache["krope"][i].astype(F32)
        h = self.norm(x, p["norm1"])
        q, c, k_pe = self.latents(h, p["attn"], jnp.full((1,), t), quant)
        ckv = jax.lax.dynamic_update_slice_in_dim(ckv, c, t, 1)
        krope = jax.lax.dynamic_update_slice_in_dim(krope, k_pe, t, 1)
        mask = (jnp.arange(ckv.shape[1]) <= t)[None, :]
        b = x.shape[0]
        blk = math.gcd(b, DECODE_BLOCK)

        def block(args):
            qb, cb, kb = args
            return self.attend(qb, cb, kb, p["attn"], mask, quant)

        o = jax.lax.map(block, tuple(a.reshape(b // blk, blk, *a.shape[1:])
                                     for a in (q, ckv, krope)))
        x = x + self.out(o.reshape(b, *o.shape[2:]), p["attn"], quant)
        return x + self.ffn(self.norm(x, p["norm2"]), p, is_moe, quant)

    def head(self, x, params, quant):
        h = self.norm(x, params["final_norm"])
        return _mm("bsd,dv->bsv", h, params["lm_head"].astype(F32), quant,
                   -1, 0)

    # -- whole passes ----------------------------------------------------
    def _jit(self, quant):
        if quant not in self._programs:
            self._programs[quant] = (
                jax.jit(lambda e, tok: jnp.take(e, tok, axis=0).astype(F32)),
                jax.jit(functools.partial(self.prefill_layer, quant=quant),
                        static_argnames="is_moe"),
                jax.jit(functools.partial(self.decode_layer, quant=quant),
                        static_argnames="is_moe"),
                jax.jit(functools.partial(self.head, quant=quant)))
        return self._programs[quant]

    def _layers(self):
        """(stage, index in its stack, is_moe) of every layer in order."""
        m = self.m
        out = [("stage0", i, False) for i in range(m.dense_layers)]
        stage = "stage1" if m.dense_layers else "stage0"
        return out + [(stage, i, True)
                      for i in range(m.layers - m.dense_layers)]

    def prefill_logits(self, params, tokens, quant=None):
        """Logits (B, S, V) in float32 at every position of ``tokens``."""
        embed, layer, _, head = self._jit(quant)
        x = embed(params["embed"], tokens)
        for stage, i, is_moe in self._layers():
            x = layer(x, params[stage]["sub0"], np.int32(i), is_moe=is_moe)
        return head(x, params)

    def decode_logits(self, params, caches, tokens, t, quant=None):
        """Next-token logits (B, V) of ``tokens`` (B,) at position ``t``
        over ``caches``."""
        embed, _, layer, head = self._jit(quant)
        x = embed(params["embed"], tokens[:, None])
        for stage, i, is_moe in self._layers():
            x = layer(x, params[stage]["sub0"], caches[stage]["sub0"],
                      np.int32(i), np.int32(t), is_moe=is_moe)
        return head(x, params)[:, 0]


# --------------------------------------------------------------------- #
# required work
# --------------------------------------------------------------------- #
def attn_params(m: Dims) -> int:
    return (m.d * m.q_lora + m.q_lora * m.heads * (m.nope + m.rope)
            + m.d * (m.kv_lora + m.rope)
            + m.kv_lora * m.heads * (m.nope + m.v) + m.heads * m.v * m.d)


def expert_params(m: Dims) -> int:
    return 3 * m.d * m.f_expert


def held_params(m: Dims) -> int:
    """Every parameter this chip holds: embedding and head, each layer's
    attention (with its two latent norms) and two norms, the dense MLPs,
    each expert layer's router, held and shared experts, final norm."""
    moe_layers = m.layers - m.dense_layers
    per_layer = attn_params(m) + m.q_lora + m.kv_lora + 2 * m.d
    moe = (m.d * m.experts + m.held * expert_params(m)
           + m.shared * expert_params(m))
    return (2 * m.vocab * m.d + m.layers * per_layer
            + m.dense_layers * 3 * m.d * m.d_ff + moe_layers * moe + m.d)


def param_count(cfg: dict) -> int:
    return held_params(dims(cfg))


def weight_bytes(m: Dims) -> int:
    """Every weight once: the router in float32, the rest in the
    configuration's dtype; the embedding is gathered, not read."""
    router = (m.layers - m.dense_layers) * m.d * m.experts
    return (held_params(m) - router - m.vocab * m.d) * m.wbytes + router * 4


def held_pairs(m: Dims, tokens: int) -> float:
    """(token, expert) pairs a held group gets in expectation."""
    return tokens * m.top_k * m.held / m.experts


def ffn_flops(m: Dims, tokens: int) -> float:
    """Dense MLPs, routers, held and shared experts, for ``tokens``."""
    moe_layers = m.layers - m.dense_layers
    dense = 2 * tokens * 3 * m.d * m.d_ff * m.dense_layers
    per_moe = (2 * tokens * m.d * m.experts
               + 2 * held_pairs(m, tokens) * expert_params(m)
               + 2 * tokens * m.shared * expert_params(m))
    return dense + moe_layers * per_moe


def prefill(cfg: dict, batch: int, seq: int) -> dict:
    """One prefill slice: ``batch`` prompts of ``seq`` tokens, logits at
    every position; attention with k and v expanded from the latents."""
    m = dims(cfg)
    tokens = batch * seq
    pairs = batch * seq * (seq + 1) // 2          # causal, diagonal included
    attn = m.layers * (2 * tokens * attn_params(m)
                       + 2 * pairs * m.heads * (m.nope + m.rope + m.v))
    flops = attn + ffn_flops(m, tokens) + 2 * tokens * m.d * m.vocab
    nbytes = (weight_bytes(m) + tokens * m.d * m.wbytes + tokens * 4
              + tokens * m.vocab * m.wbytes)
    return {"flops": float(flops), "bytes": float(nbytes), "tokens": tokens}


def decode(cfg: dict, batch: int, pos: int) -> dict:
    """One decode step for ``batch`` sequences whose new token sits at
    position ``pos``: absorbed attention over the ``pos`` cached latents
    and the new one; the cache is read only up to ``pos``."""
    m = dims(cfg)
    proj = (m.d * m.q_lora + m.q_lora * m.heads * (m.nope + m.rope)
            + m.d * (m.kv_lora + m.rope)
            + m.heads * m.nope * m.kv_lora + m.heads * m.kv_lora * m.v
            + m.heads * m.v * m.d)
    attn = m.layers * batch * (2 * proj + 2 * (pos + 1) * m.heads
                               * (2 * m.kv_lora + m.rope))
    flops = attn + ffn_flops(m, batch) + 2 * batch * m.d * m.vocab
    cache = m.layers * batch * pos * (m.kv_lora + m.rope) * m.wbytes
    nbytes = (weight_bytes(m) + batch * m.d * m.wbytes + cache + batch * 4
              + batch * m.vocab * m.wbytes)
    return {"flops": float(flops), "bytes": float(nbytes), "tokens": batch}


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
    """Required work, per step, of each kernel the step runs, by the stem
    of its name in the device trace. ``expert_ffn``: in each expert
    layer, the held group's expected pairs through their SwiGLU experts,
    reading every held expert's three matrices once and each pair's row
    in and out."""
    m = dims(cfg)
    tokens = batch * (seq if phase == "prefill" else 1)
    pairs = held_pairs(m, tokens)
    moe_layers = m.layers - m.dense_layers
    return {"expert_ffn": {
        "flops": float(moe_layers * 2 * pairs * expert_params(m)),
        "bytes": float(moe_layers * (m.held * expert_params(m)
                                     + 2 * pairs * m.d) * m.wbytes)}}
