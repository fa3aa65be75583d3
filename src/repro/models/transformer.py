"""Model assembly: stage-planned, scan-over-layers transformer for all
assigned architectures (dense / GQA / MLA / MoE / RWKV6 / RG-LRU hybrid /
encoder-decoder / stub-frontend VLM).

Layers are grouped into *stages* — maximal runs whose per-layer parameter
structure repeats with the block-pattern period — and each stage's params
are stacked and executed under ``lax.scan`` (one compiled body per stage,
which is what keeps 61-layer × 512-way-GSPMD compiles tractable).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models.sharding import constrain


# ===================================================================== #
# stage planning
# ===================================================================== #
@dataclasses.dataclass(frozen=True)
class Stage:
    cycle: tuple          # per-sublayer signatures: (kind, is_moe)
    repeats: int
    start_layer: int


def _layer_sig(cfg, i: int):
    kind = cfg.layer_kinds()[i]
    is_moe = (cfg.moe is not None and kind in ("attn", "local")
              and i >= cfg.moe.first_dense_layers)
    return (kind, is_moe)


def stage_plan(cfg) -> list:
    sigs = [_layer_sig(cfg, i) for i in range(cfg.num_layers)]
    p = len(cfg.block_pattern)
    stages, i = [], 0
    while i < len(sigs):
        if i + p <= len(sigs):
            cyc = tuple(sigs[i:i + p])
            reps = 1
            while i + (reps + 1) * p <= len(sigs) and \
                    tuple(sigs[i + reps * p:i + (reps + 1) * p]) == cyc:
                reps += 1
            # merge uniform cycles (p==1) across differing neighbours handled
            # by the while; emit stage
            stages.append(Stage(cyc, reps, i))
            i += reps * p
        else:
            stages.append(Stage((sigs[i],), 1, i))
            i += 1
    return stages


# ===================================================================== #
# per-block init / apply
# ===================================================================== #
def _init_block(key, cfg, sig, n_layers, dtype, cross: bool):
    kind, is_moe = sig
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": L.init_norm(cfg.norm, cfg.d_model),
               "norm2": L.init_norm(cfg.norm, cfg.d_model)}
    if kind in ("attn", "local"):
        p["attn"] = A.init_attention(ks[0], cfg, n_layers, dtype)
    elif kind == "rwkv6":
        p["tmix"] = R.init_rwkv6(ks[0], cfg, n_layers, dtype)
    elif kind == "rglru":
        p["rec"] = R.init_rglru(ks[0], cfg, n_layers, dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["norm_x"] = L.init_norm(cfg.norm, cfg.d_model)
        p["xattn"] = A.init_attention(ks[1], cfg, n_layers, dtype)
    if kind == "rwkv6":
        p["cmix"] = R.init_rwkv6_cmix(ks[2], cfg, n_layers, dtype)
    elif is_moe:
        p["moe"] = M.init_moe(ks[2], cfg, n_layers, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act,
                              n_layers, dtype)
    return p


def _init_block_cache(cfg, sig, batch, max_len, cross_len, dtype):
    kind, _ = sig
    c: dict = {}
    if kind == "attn":
        c.update(A.init_cache(cfg, batch, max_len, dtype))
    elif kind == "local":
        w = min(cfg.local_window, max_len)
        c["k"] = jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["v"] = jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["pos"] = jnp.full((w,), -1, jnp.int32)
    elif kind == "rwkv6":
        h = cfg.d_model // cfg.rwkv_head_dim
        c["state"] = jnp.zeros((batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                               jnp.float32)
        c["x_last_t"] = jnp.zeros((batch, cfg.d_model), jnp.float32)
        c["x_last_c"] = jnp.zeros((batch, cfg.d_model), jnp.float32)
    elif kind == "rglru":
        c["h"] = jnp.zeros((batch, cfg.lru_width), jnp.float32)
        c["conv"] = jnp.zeros((batch, R.CONV_WIDTH - 1, cfg.lru_width),
                              jnp.float32)
    if cross_len:
        c["xk"] = jnp.zeros((batch, cross_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["xv"] = jnp.zeros((batch, cross_len, cfg.num_kv_heads, cfg.head_dim), dtype)
    return c


def _local_ring_update(cache, k_new, v_new, positions):
    """Write (B,S,kv,hd) tokens at ring slots pos % W; returns new cache."""
    w = cache["k"].shape[1]
    s = k_new.shape[1]
    if s >= w:
        k_new, v_new = k_new[:, -w:], v_new[:, -w:]
        positions = positions[-w:]
    slots = positions % w
    kc = cache["k"].at[:, slots].set(k_new.astype(cache["k"].dtype))
    vc = cache["v"].at[:, slots].set(v_new.astype(cache["v"].dtype))
    pc = cache["pos"].at[slots].set(positions)
    return {"k": kc, "v": vc, "pos": pc}


def _local_ring_attend(q, cache, t, window):
    """Decode attention over a ring cache with stored absolute positions."""
    b, _, h, hd = q.shape
    kvh = cache["k"].shape[2]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, kvh, g, hd)
    logits = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        cache["k"].astype(jnp.float32)) * scale
    pos = cache["pos"]
    valid = (pos >= 0) & (pos <= t) & (pos > t - window)
    logits = jnp.where(valid[None, None, None], logits, A.NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, cache["v"].astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def _local_attention_block(x, p, cfg, positions, cache, t):
    """Local (sliding-window) attention with ring-buffer cache."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.pos_kind in ("rope", "mrope"):
        q = L.positional(q, positions, cfg.pos_kind, cfg.rope_theta)
        k = L.positional(k, positions, cfg.pos_kind, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        pos_vec = positions[0] if positions.ndim == 2 else positions
        new_cache = _local_ring_update(cache, k, v, pos_vec)
        if s == 1:
            o = _local_ring_attend(q, new_cache, pos_vec[-1], cfg.local_window)
        else:
            o = A.chunked_attention(q, k, v, causal=True,
                                    window=cfg.local_window,
                                    q_block=A._pick_block(s, s),
                                    kv_block=A._pick_block(s, s))
    else:
        blk = A._pick_block(s, s)
        if s <= 2 * blk:
            o = A.full_attention(q, k, v, causal=True, window=cfg.local_window)
        else:
            o = A.chunked_attention(q, k, v, causal=True,
                                    window=cfg.local_window,
                                    q_block=blk, kv_block=blk)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


def apply_block(x, bp, cfg, sig, positions, *, enc_out=None, cache=None,
                t=None, moe_group: int = 0, layer=None, expert_layer=None):
    """One transformer block. Returns (x, new_cache, aux_loss).

    ``layer``: the block's index in its stage, given when ``cache`` holds
    the stage's whole k and v (``A.reads_cache_in_place``);
    ``expert_layer`` likewise, given when ``bp``'s held experts are the
    stage's whole stacked weights (``_HELD``)."""
    kind, is_moe = sig
    aux = jnp.zeros((), jnp.float32)
    h = L.norm(x, bp["norm1"], cfg.norm)
    new_cache = dict(cache) if cache is not None else None

    if kind == "attn":
        sub_cache = None
        if cache is not None:
            sub_cache = {k: cache[k] for k in cache if k in ("k", "v", "ckv", "krope")}
        if cfg.mla is not None:
            a, nc = A.mla_forward(h, bp["attn"], cfg, positions,
                                  cache=sub_cache or None, t=t)
        else:
            a, nc = A.gqa_forward(h, bp["attn"], cfg, positions,
                                  cache=sub_cache or None, t=t, layer=layer)
        if nc is not None:
            new_cache.update(nc)
    elif kind == "local":
        sub_cache = None
        if cache is not None:
            sub_cache = {k: cache[k] for k in ("k", "v", "pos")}
        a, nc = _local_attention_block(h, bp["attn"], cfg, positions,
                                       sub_cache, t)
        if nc is not None:
            new_cache.update(nc)
    elif kind == "rwkv6":
        st = (cache["state"], cache["x_last_t"]) if cache is not None else (None, None)
        a, (state, x_last) = R.rwkv6_forward(h, bp["tmix"], cfg,
                                             state=st[0], x_last=st[1])
        if cache is not None:
            new_cache.update({"state": state, "x_last_t": x_last})
    elif kind == "rglru":
        st = ({"h": cache["h"], "conv": cache["conv"]}
              if cache is not None else None)
        a, ns = R.rglru_forward(h, bp["rec"], cfg, state=st)
        if cache is not None:
            new_cache.update(ns)
    else:
        raise ValueError(kind)
    x = x + a
    x = constrain(x, "dp", "model", None)

    if "xattn" in bp:                                          # cross-attention
        hx = L.norm(x, bp["norm_x"], cfg.norm)
        if cache is not None and enc_out is None:
            # decode: attend over precomputed cross K/V in the cache
            q = jnp.einsum("bsd,dhk->bshk", hx, bp["xattn"]["wq"])
            o = A.decode_attention(q, cache["xk"], cache["xv"],
                                   cache["xk"].shape[1])
            o = jnp.einsum("bshk,hkd->bsd", o, bp["xattn"]["wo"])
        else:
            o, _ = A.gqa_forward(hx, bp["xattn"], cfg, positions,
                                 causal=False, kv_source=enc_out)
            if cache is not None:                              # store cross K/V
                xk = jnp.einsum("bsd,dhk->bshk", enc_out, bp["xattn"]["wk"])
                xv = jnp.einsum("bsd,dhk->bshk", enc_out, bp["xattn"]["wv"])
                new_cache["xk"] = xk.astype(cache["xk"].dtype)
                new_cache["xv"] = xv.astype(cache["xv"].dtype)
        x = x + o

    h2 = L.norm(x, bp["norm2"], cfg.norm)
    if kind == "rwkv6":
        f, x_last_c = R.rwkv6_cmix(
            h2, bp["cmix"],
            x_last=cache["x_last_c"] if cache is not None else None)
        if cache is not None:
            new_cache["x_last_c"] = x_last_c
    elif is_moe:
        from repro.models.sharding import current_layout, current_mesh
        mesh = current_mesh()
        use_ep = (cfg.moe_impl == "ep" and mesh is not None
                  and current_layout() == "2d"
                  and "model" in mesh.shape and mesh.shape["model"] > 1
                  and h2.shape[1] % mesh.shape["model"] == 0)
        if cfg.moe.held is not None:
            f, aux = M.held_moe_ffn(h2, bp["moe"], cfg, layer=expert_layer)
        elif use_ep:
            f, aux = M.moe_ffn_ep_sharded(h2, bp["moe"], cfg, mesh)
        else:
            f, aux = M.moe_ffn(h2, bp["moe"], cfg, group_size=moe_group)
    else:
        f = L.mlp(h2, bp["mlp"], cfg.act)
    x = x + f
    x = constrain(x, "dp", "model", None)
    return x, new_cache, aux


# ===================================================================== #
# model init
# ===================================================================== #
def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg, key, dtype=None):
    dtype = jnp.dtype(dtype or cfg.dtype)
    stages = stage_plan(cfg)
    n_keys = 8 + 2 * len(stages)
    ks = list(jax.random.split(key, n_keys))
    d, v = cfg.d_model, cfg.vocab_size
    params: dict = {"embed": L.embed_init(ks[0], (v, d), dtype)}
    if cfg.pos_kind == "learned":
        params["pos_embed"] = L.embed_init(ks[1], (max(32768, cfg.encoder_seq), d), dtype)
    cross = cfg.is_encoder_decoder
    for si, st in enumerate(stages):
        sub = {}
        kk = jax.random.split(ks[2 + si], st.repeats * len(st.cycle))
        for ci, sig in enumerate(st.cycle):
            # one vmapped body per sublayer, not one per layer: the same
            # values as stacking per-layer inits, at a fraction of the
            # program size when the init is jitted
            sub[f"sub{ci}"] = jax.vmap(functools.partial(
                _init_block, cfg=cfg, sig=sig, n_layers=cfg.num_layers,
                dtype=dtype, cross=cross))(kk[ci::len(st.cycle)])
        params[f"stage{si}"] = sub
    params["final_norm"] = L.init_norm(cfg.norm, d)
    params["lm_head"] = L.dense_init(ks[-1], (d, v), dtype=dtype)
    if cross:
        kk = jax.random.split(ks[-2], cfg.encoder_layers)
        enc_blocks = [_init_block(kk[r], cfg, ("attn", False),
                                  cfg.encoder_layers, dtype, cross=False)
                      for r in range(cfg.encoder_layers)]
        params["enc"] = {"stage0": {"sub0": _stack(enc_blocks)},
                         "final_norm": L.init_norm(cfg.norm, d),
                         "pos_embed": L.embed_init(ks[-3], (cfg.encoder_seq, d), dtype)}
    if cfg.mtp:
        km = jax.random.split(ks[-4], 4)
        params["mtp"] = {
            "norm_h": L.init_norm(cfg.norm, d),
            "norm_e": L.init_norm(cfg.norm, d),
            "proj": L.dense_init(km[0], (2 * d, d), dtype=dtype),
            "block": {"sub0": _stack([_init_block(km[1], cfg, ("attn", False),
                                                  cfg.num_layers, dtype, False)])},
        }
    return params


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))


# ===================================================================== #
# forward
# ===================================================================== #
_HELD = ("wi", "wg", "wo")     # a held-expert layer's kernel-read weights


def _run_stages(params, cfg, x, positions, stages, *, prefix="stage",
                enc_out=None, caches=None, t=None, decode=False,
                causal=True, moe_group=0, root=None):
    root = params if root is None else root
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {} if caches is not None else None
    for si, st in enumerate(stages):
        sp = root[f"{prefix}{si}"] if prefix == "stage" else root[prefix][f"stage{si}"]
        cache_s = caches.get(f"{prefix}{si}") if caches is not None else None
        # sublayers whose attention reads the stage's whole k and v in
        # place: those stay out of the scan's per-layer slices, and the
        # scan returns only each layer's new token, written after it
        whole = set()
        if decode and cache_s is not None:
            whole = {f"sub{ci}" for ci, (kind, _) in enumerate(st.cycle)
                     if A.reads_cache_in_place(cfg, kind, x.shape[1])}
        xs_cache = cache_s
        if whole:
            xs_cache = {n: {k: a for k, a in c.items()
                            if n not in whole or k not in ("k", "v")}
                        for n, c in cache_s.items()}
        # sublayers holding experts: their expert weights stay out of the
        # scan's per-layer slices too, read in place by the kernel
        held = {f"sub{ci}" for ci, (_, is_moe) in enumerate(st.cycle)
                if is_moe and cfg.moe.held is not None}
        xs_params = {n: dict(c, moe={k: a for k, a in c["moe"].items()
                                     if k not in _HELD})
                     if n in held else c for n, c in sp.items()}

        def body(carry, xs, _st=st, _whole=whole, _cache_s=cache_s,
                 _held=held, _sp=sp):
            xx = carry
            layer_ps, layer_cs, i = xs
            aux_acc = jnp.zeros((), jnp.float32)
            ncs = {}
            for ci, sig in enumerate(_st.cycle):
                name = f"sub{ci}"
                cc = layer_cs.get(name) if layer_cs is not None else None
                bp = layer_ps[name]
                layer = None
                if name in _whole:
                    cc = dict(cc, k=_cache_s[name]["k"], v=_cache_s[name]["v"])
                    layer = i
                expert_layer = None
                if name in _held:
                    bp = dict(bp, moe=dict(bp["moe"], **{
                        k: _sp[name]["moe"][k] for k in _HELD}))
                    expert_layer = i
                xx, nc, aux = apply_block(
                    xx, bp, cfg, sig, positions,
                    enc_out=enc_out, cache=cc, t=t, moe_group=moe_group,
                    layer=layer, expert_layer=expert_layer)
                if new_caches is not None:
                    ncs[name] = nc
                aux_acc = aux_acc + aux
            return xx, (ncs if new_caches is not None else 0, aux_acc)

        if cfg.remat and not decode:
            body = jax.checkpoint(body)
        layers = jnp.arange(st.repeats) if whole or held else None
        x, (ncs, auxs) = jax.lax.scan(body, x, (xs_params, xs_cache, layers))
        for name in whole:                 # (L, B, 1, kv, hd) at position t
            ncs[name] = dict(ncs[name], **{
                k: jax.lax.dynamic_update_slice_in_dim(
                    cache_s[name][k], ncs[name][k], t, 2)
                for k in ("k", "v")})
        if new_caches is not None:
            new_caches[f"{prefix}{si}"] = ncs
        aux_total = aux_total + jnp.sum(auxs)
    return x, new_caches, aux_total


def _embed(params, cfg, tokens, positions, patches=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.pos_kind == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(x.dtype)
    if patches is not None:                                    # VLM stub prefix
        npatch = patches.shape[1]
        x = jnp.concatenate([patches.astype(x.dtype), x[:, npatch:]], axis=1)
    return x


def encode(params, cfg, audio):
    """Whisper encoder over precomputed frame embeddings (conv stub)."""
    enc = params["enc"]
    x = audio.astype(jnp.dtype(cfg.dtype)) + enc["pos_embed"][None]
    x = constrain(x, "dp", None, None)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

    def body(carry, layer_ps):
        xx = carry
        h = L.norm(xx, layer_ps["norm1"], cfg.norm)
        a, _ = A.gqa_forward(h, layer_ps["attn"], cfg, pos, causal=False)
        xx = xx + a
        h2 = L.norm(xx, layer_ps["norm2"], cfg.norm)
        xx = xx + L.mlp(h2, layer_ps["mlp"], cfg.act)
        return constrain(xx, "dp", None, None), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, enc["stage0"]["sub0"])
    return L.norm(x, enc["final_norm"], cfg.norm)


def forward(params, cfg, batch, *, caches=None, t=None, decode=False,
            moe_group: int = 0, return_hidden: bool = False):
    """batch: tokens (B,S) [+ patches (B,P,D) | audio (B,Se,D) | positions].

    Returns (logits, new_caches, aux_loss[, hidden]).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    if "positions" in batch:
        positions = batch["positions"]
    elif t is not None:
        positions = jnp.broadcast_to(t + jnp.arange(s)[None], (b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    enc_out = None
    if cfg.is_encoder_decoder and "audio" in batch:
        enc_out = encode(params, cfg, batch["audio"])
    x = _embed(params, cfg, tokens, positions, batch.get("patches"))
    x = constrain(x, "dp", "model", None)
    stages = stage_plan(cfg)
    x, new_caches, aux = _run_stages(params, cfg, x, positions, stages,
                                     enc_out=enc_out, caches=caches, t=t,
                                     decode=decode, moe_group=moe_group)
    h_final = L.norm(x, params["final_norm"], cfg.norm)
    logits = h_final @ params["lm_head"]
    logits = constrain(logits, "dp", None, "model")
    if return_hidden:
        return logits, new_caches, aux, h_final
    return logits, new_caches, aux


# ===================================================================== #
# losses
# ===================================================================== #
def softmax_xent(logits, labels, mask, impl: str = "gather"):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    if impl == "onehot":
        # select+reduce instead of gather: with V sharded over 'model' this
        # is a local masked sum + tiny all-reduce, not a logits all-gather
        v_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                          lf.ndim - 1)
        ll = jnp.sum(jnp.where(v_iota == labels.clip(0)[..., None], lf, 0.0),
                     axis=-1)
    else:
        ll = jnp.take_along_axis(lf, labels.clip(0)[..., None],
                                 axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def _mtp_loss(params, cfg, h_final, tokens, labels, mask):
    """DeepSeek-V3 multi-token prediction: predict t+2 from [h_t; emb_{t+1}]."""
    mp = params["mtp"]
    # shift by one and re-pad to S so attention block sizes stay aligned;
    # the padded tail position is masked out of the loss
    h = L.norm(jnp.pad(h_final[:, :-1], ((0, 0), (0, 1), (0, 0))),
               mp["norm_h"], cfg.norm)
    shifted = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    e = L.norm(jnp.take(params["embed"], shifted, axis=0),
               mp["norm_e"], cfg.norm)
    x = jnp.concatenate([h, e], axis=-1) @ mp["proj"]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    bp = jax.tree_util.tree_map(lambda a: a[0], mp["block"]["sub0"])
    x, _, _ = apply_block(x, bp, cfg, ("attn", False), pos)
    logits = x @ params["lm_head"]
    lab2 = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    m2 = jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
    return softmax_xent(logits, lab2, m2, cfg.xent_impl)


def train_loss(params, cfg, batch, *, moe_group: int = 0):
    """batch: tokens (B,S), labels (B,S) (-1 = masked), + frontend stubs."""
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logits, _, aux, h = forward(params, cfg, batch, moe_group=moe_group,
                                return_hidden=True)
    loss = softmax_xent(logits, labels, mask, cfg.xent_impl)
    metrics = {"ce": loss, "aux": aux}
    if cfg.mtp:
        mtp = _mtp_loss(params, cfg, h, batch["tokens"], labels, mask)
        metrics["mtp"] = mtp
        loss = loss + 0.1 * mtp
    return loss + aux, metrics


# ===================================================================== #
# decode
# ===================================================================== #
def init_decode_caches(cfg, batch: int, max_len: int, dtype=None):
    dtype = jnp.dtype(dtype or cfg.dtype)
    stages = stage_plan(cfg)
    caches = {}
    cross_len = cfg.encoder_seq if cfg.is_encoder_decoder else 0
    for si, st in enumerate(stages):
        sub = {}
        for ci, sig in enumerate(st.cycle):
            one = _init_block_cache(cfg, sig, batch, max_len, cross_len, dtype)
            sub[f"sub{ci}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (st.repeats,) + a.shape).copy()
                if st.repeats > 1 else a[None], one)
        caches[f"stage{si}"] = sub
    return caches


def prefill(params, cfg, batch, caches):
    """Run the full prompt through the model, filling caches. t=0 start."""
    logits, new_caches, _ = forward(params, cfg, batch, caches=caches,
                                    t=jnp.int32(0), decode=True)
    return logits, new_caches


def decode_step(params, cfg, caches, token, t):
    """token: (B,) int32; t: scalar int32 current length. -> (logits_B_V, caches)."""
    batch = {"tokens": token[:, None]}
    logits, new_caches, _ = forward(params, cfg, batch, caches=caches,
                                    t=t, decode=True)
    return logits[:, 0], new_caches
