"""Attention: GQA/MHA (full, local, chunked-flash) and DeepSeek MLA.

Two execution paths:
  * ``chunked`` — pure-XLA online-softmax over KV blocks (lax.scan). This is
    dry-run safe (lowers on any backend) and memory-bounded for 32k prefill.
  * ``pallas`` — TPU flash kernel from ``repro.kernels`` (validated in
    interpret mode on CPU); selected via ``ModelConfig.attention_impl``.

Decode uses a single-token einsum over the cache; the cache is laid out
(B, S, kv, hd) so GSPMD can shard B over 'data' and S over 'model'
(context-parallel decode — partial softmax stats are combined by XLA's
all-reduce on the contraction). On one device, a full-attention GQA
cache is instead read in place by the ``decode_attention`` kernel, from
the stage's stacked cache (``reads_cache_in_place``).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import layers as L
from repro.models.sharding import constrain, current_mesh

NEG_INF = -1e30


def _constrain_qkv(*ts):
    """Pin (B, S, H, hd) tensors to (dp, None, model, None): without this,
    GSPMD can leave scan-invariant attention operands ambiguously sharded
    and fall back to full replication inside the KV-block loop (observed as
    100GB-class all-gathers on the 256-chip mesh)."""
    return tuple(constrain(t, "dp", None, "model", None) for t in ts)


# --------------------------------------------------------------------- #
# parameter init
# --------------------------------------------------------------------- #
def init_attention(key, cfg, n_layers: int, dtype=jnp.bfloat16):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "wq_a": L.dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
            "q_norm": L.init_norm("rmsnorm", m.q_lora_rank),
            "wq_b": L.dense_init(ks[1], (m.q_lora_rank, h, m.qk_nope_dim + m.qk_rope_dim), dtype=dtype),
            "wkv_a": L.dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), dtype=dtype),
            "kv_norm": L.init_norm("rmsnorm", m.kv_lora_rank),
            "wkv_b": L.dense_init(ks[3], (m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim), dtype=dtype),
            "wo": L.dense_init(ks[4], (h, m.v_head_dim, d),
                               scale=1.0 / np.sqrt(2 * n_layers), dtype=dtype),
        }
    return {
        "wq": L.dense_init(ks[0], (d, h, hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (d, kv, hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (d, kv, hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (h, hd, d),
                           scale=1.0 / np.sqrt(2 * n_layers), dtype=dtype),
    }


# --------------------------------------------------------------------- #
# core softmax-attention over blocks (online softmax, pure XLA)
# --------------------------------------------------------------------- #
def _attend_block(q, k, v, mask, scale):
    """q:(B,qb,H,hd) k/v:(B,kb,kv,hd) mask:(qb,kb) or None -> partial stats."""
    b, qb, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, qb, kvh, g, hd)
    # operands stay in model dtype (bf16-native MXU, f32 accumulation): an
    # explicit operand cast is loop-invariant and gets hoisted by XLA,
    # which doubles the bytes of any K/V gather feeding the KV-block scan
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale  # (B,kv,g,qb,kb)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,kv,g,qb)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                   # (B,kv,g,qb)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def chunked_attention_causal_skip(q, k, v, *, q_block: int = 1024,
                                  kv_block: int = 1024, groups: int = 4):
    """Causal attention that skips fully-masked KV regions at a coarse
    grain: q is split into ``groups`` contiguous chunks and chunk g only
    scans KV up to its own end. Cuts attention FLOPs by ~(g+1)/(2g)
    (0.625x at g=4) at the cost of a ~4x larger attention HLO body."""
    b, sq, h, hd = q.shape
    groups = min(groups, max(sq // q_block, 1))
    gsz = sq // groups
    outs = []
    for g in range(groups):
        qg = q[:, g * gsz:(g + 1) * gsz]
        kv_len = (g + 1) * gsz
        outs.append(chunked_attention(
            qg, k[:, :kv_len], v[:, :kv_len], causal=True,
            q_block=min(q_block, gsz), kv_block=min(kv_block, kv_len),
            q_offset=g * gsz))
    return jnp.concatenate(outs, axis=1)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_block: int = 1024, kv_block: int = 1024,
                      q_offset=0):
    """Memory-bounded attention. q:(B,Sq,H,hd), k/v:(B,Sk,kv,hd).

    ``q_offset``: global position of q[0] relative to k[0] (prefill: 0).
    ``window`` > 0 limits attention to the last ``window`` keys (local).
    Returns (B,Sq,H,hd) in q.dtype.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    scale = 1.0 / np.sqrt(hd)
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    nq, nk = sq // q_block, sk // kv_block
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, q_block, sk, kv_block)

    qb_ids = jnp.arange(q_block)
    kb_ids = jnp.arange(kv_block)

    def q_step(_, qi):
        qblk = jax.lax.dynamic_slice_in_dim(q, qi * q_block, q_block, axis=1)

        def kv_step(carry, ki):
            m_run, l_run, o_run = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, ki * kv_block, kv_block, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, ki * kv_block, kv_block, 1)
            qpos = q_offset + qi * q_block + qb_ids                # (qb,)
            kpos = ki * kv_block + kb_ids                          # (kb,)
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window > 0:
                mask &= qpos[:, None] - kpos[None, :] < window
            m, l, o = _attend_block(qblk, kblk, vblk, mask, scale)
            m_new = jnp.maximum(m_run, m)
            a1 = jnp.exp(m_run - m_new)
            a2 = jnp.exp(m - m_new)
            l_new = l_run * a1 + l * a2
            o_new = o_run * a1[..., None] + o * a2[..., None]
            return (m_new, l_new, o_new), None

        init = (jnp.full((b, kvh, h // kvh, q_block), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, h // kvh, q_block), jnp.float32),
                jnp.zeros((b, kvh, h // kvh, q_block, hd), jnp.float32))
        (m_f, l_f, o_f), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = o_f / jnp.maximum(l_f[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_block, h, hd)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))       # (nq,B,qb,H,hd)
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def full_attention(q, k, v, *, causal: bool, window: int = 0, q_offset=0):
    """Unblocked reference attention (small shapes / oracles)."""
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, t, *, window: int = 0):
    """Single-token attention over a (B,S,kv,hd) cache, valid length t.

    t: scalar int32 — number of valid cache positions (new token already
    written at position t-1).
    """
    b, s, kvh, hd = k_cache.shape
    h = q.shape[2]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, kvh, g, hd)                               # (B,kv,g,hd)
    logits = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)
    valid = kpos[None, None, None, :] < t
    if window > 0:
        valid &= kpos[None, None, None, :] >= t - window
    logits = jnp.where(valid, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def reads_cache_in_place(cfg, kind: str, seq_len: int) -> bool:
    """Whether a ``kind`` block decoding ``seq_len`` tokens against its
    cache attends with the ``decode_attention`` kernel: one token, a
    full-attention GQA self-attention cache, one device (no mesh, whose
    sharded lowerings keep the einsum path)."""
    return (kind == "attn" and seq_len == 1 and cfg.mla is None
            and cfg.attention_kind == "full" and current_mesh() is None)


# --------------------------------------------------------------------- #
# GQA block (projection + attention + output)
# --------------------------------------------------------------------- #
def gqa_forward(x, p, cfg, positions, *, causal=True, cache=None, t=None,
                kv_source=None, layer=None):
    """x:(B,S,D). cache: dict(k,v) (B,Smax,kv,hd) or None.

    kv_source: if given (B,Skv,D), cross-attention (whisper decoder);
    positions apply to q only then.
    layer: given for a one-token decode whose cache is the stage's whole
    (L,B,Smax,kv,hd) cache (``reads_cache_in_place``); the kernel reads
    layer ``layer`` of it, and the new cache is the token's own (B,1,kv,hd)
    k and v, for the caller to write at ``t``.
    Returns (out, new_cache).
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.local_window if cfg.attention_kind == "local" else 0

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    src = x if kv_source is None else kv_source
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    q, k, v = _constrain_qkv(q, k, v)

    if kv_source is None and cfg.pos_kind in ("rope", "mrope"):
        q = L.positional(q, positions, cfg.pos_kind, cfg.rope_theta)
        k = L.positional(k, positions if cache is None else positions,
                         cfg.pos_kind, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        if t is None:
            raise ValueError("cache update requires t")
        if layer is not None:  # decode, reading the stacked cache in place
            k_t = k.astype(cache["k"].dtype)
            v_t = v.astype(cache["v"].dtype)
            new_cache = {"k": k_t, "v": v_t}
            o = ops.decode_attention(q[:, 0], cache["k"], cache["v"], layer,
                                     t, k_t[:, 0], v_t[:, 0])[:, None]
        elif s == 1:  # decode: write one token at position t
            k_c = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), t, 1)
            v_c = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), t, 1)
            new_cache = {"k": k_c, "v": v_c}
            o = decode_attention(q, k_c, v_c, t + 1, window=window)
        else:       # prefill into cache
            k_c = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), t, 1)
            v_c = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), t, 1)
            new_cache = {"k": k_c, "v": v_c}
            o = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        blk = _pick_block(s, k.shape[1])
        if s <= 2 * blk and kv_source is None:
            o = full_attention(q, k, v, causal=causal, window=window)
        elif kv_source is not None:
            o = full_attention(q, k, v, causal=False)
        elif cfg.causal_skip and causal and window == 0:
            o = chunked_attention_causal_skip(q, k, v, q_block=blk,
                                              kv_block=blk)
        else:
            o = chunked_attention(q, k, v, causal=causal, window=window,
                                  q_block=blk, kv_block=blk)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


def _pick_block(sq: int, sk: int, target: int = 1024) -> int:
    """Largest divisor of gcd(sq, sk) that is <= target."""
    g = int(np.gcd(sq, sk))
    for d in range(min(target, g), 0, -1):
        if g % d == 0:
            return d
    return 1


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    if cfg.mla is not None:
        m = cfg.mla
        return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype)}
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((batch, max_len, kvh, hd), dtype)}


# --------------------------------------------------------------------- #
# MLA (DeepSeek Multi-head Latent Attention)
# --------------------------------------------------------------------- #
def mla_forward(x, p, cfg, positions, *, causal=True, cache=None, t=None):
    """MLA with compressed KV cache (c_kv + shared k_rope).

    Training/prefill: expand K/V from latents and run standard attention.
    Decode: absorbed, attention runs over the cached latents (the cache
    stores only kv_lora_rank + qk_rope_dim per token — the paper's 93%
    cache saving), or expanded from them (``mla_decode="expand"``).
    With YaRN (``cfg.yarn``) the rope dims take its frequencies, and the
    softmax scale its temperature squared, folded into the queries.
    """
    m = cfg.mla
    b, s, d = x.shape
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    # --- queries ---
    q_lat = L.rmsnorm(x @ p["wq_a"], p["q_norm"]["scale"])
    if cfg.yarn is None:
        q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])      # (B,S,H,dn+dr)
    else:
        y = cfg.yarn
        temp = L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        q = (jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"],
                        preferred_element_type=jnp.float32)
             * temp).astype(x.dtype)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)

    # --- latent kv ---
    kv_a = x @ p["wkv_a"]                                      # (B,S,r+dr)
    c_kv = L.rmsnorm(kv_a[..., :m.kv_lora_rank], p["kv_norm"]["scale"])
    k_rope = kv_a[..., m.kv_lora_rank:]                        # (B,S,dr) shared
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions,
                          cfg.rope_theta, cfg.yarn)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        if t is None:
            raise ValueError("cache update requires t")
        ckv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), t, 1)
        kr_c = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), t, 1)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
        c_kv_full, k_rope_full = ckv_c, kr_c
        kv_len = t + s
    else:
        c_kv_full, k_rope_full = c_kv, k_rope
        kv_len = None

    def expand():
        """Per-head q, k and v, k and v expanded from the latents."""
        kv = jnp.einsum("bsr,rhk->bshk", c_kv_full.astype(x.dtype),
                        p["wkv_b"])
        k_nope, vv = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope_full.astype(x.dtype)[:, :, None, :],
                k_nope.shape[:-1] + (dr,))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        return _constrain_qkv(qq, k, vv)

    if cache is not None and s == 1 and cfg.mla_decode == "absorbed":
        # absorbed decode: attention runs in the latent space — never
        # expand K/V to per-head tensors over the cache length.
        #   score = q_nope·(c_kv W_b^K) + q_rope·k_rope
        #         = (q_nope W_b^K{T})·c_kv + q_rope·k_rope
        w_k = p["wkv_b"][..., :dn]                     # (r, H, dn)
        w_v = p["wkv_b"][..., dn:]                     # (r, H, dv)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_k)   # (B,1,H,r)
        scale = 1.0 / np.sqrt(dn + dr)
        s_lat = jnp.einsum("bshr,btr->bhst", q_abs.astype(jnp.float32),
                           ckv_c.astype(jnp.float32))
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                            kr_c.astype(jnp.float32))
        logits = (s_lat + s_rope) * scale              # (B,H,1,T)
        valid = jnp.arange(ckv_c.shape[1]) < kv_len
        logits = jnp.where(valid[None, None, None], logits, NEG_INF)
        pr = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", pr, ckv_c.astype(jnp.float32))
        o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(x.dtype), w_v)
    elif cache is not None and s == 1:
        qq, k, vv = expand()
        o = decode_attention(qq, k, _pad_v(vv, dn + dr), kv_len)[..., :dv]
    else:
        qq, k, vv = expand()
        blk = _pick_block(s, k.shape[1])
        if s <= 2 * blk:
            o = full_attention(qq, k, _pad_v(vv, dn + dr), causal=causal)[..., :dv]
        elif cfg.causal_skip and causal:
            o = chunked_attention_causal_skip(qq, k, _pad_v(vv, dn + dr),
                                              q_block=blk,
                                              kv_block=blk)[..., :dv]
        else:
            o = chunked_attention(qq, k, _pad_v(vv, dn + dr), causal=causal,
                                  q_block=blk, kv_block=blk)[..., :dv]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


def _pad_v(v, qk_dim):
    """Pad v head_dim up to qk head_dim so shared attention code applies."""
    dv = v.shape[-1]
    if dv == qk_dim:
        return v
    return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, qk_dim - dv)])
