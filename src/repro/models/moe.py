"""Mixture-of-Experts FFN (DeepSeek-style: shared + routed, top-k).

Dispatch is sort-based (argsort by expert id -> capacity-bucketed scatter ->
dense per-expert einsum -> unpermute). This avoids the GShard (tokens, E, C)
one-hot, whose memory is quadratic-ish at 256 experts; compute scales with
tokens*top_k*capacity_factor instead of tokens*E.

Three paths:
  * ``moe_ffn`` — single logical program; GSPMD shards the expert einsum over
    'model' (E axis) and tokens over 'data'. Collectives are inferred by XLA.
  * ``moe_ffn_ep`` — explicit expert parallelism under shard_map with a
    static-capacity all_to_all (production EP; used by the hillclimbed
    configs).
  * ``held_moe_ffn`` — one expert-parallel device's share (``moe.held``):
    every token routed over all experts, the held experts' part computed
    with no capacity and no drop by the grouped ``expert_ffn`` kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.expert_ffn import pick_tile
from repro.models import layers as L


def init_moe(key, cfg, n_layers: int, dtype=jnp.bfloat16):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_held
    ks = jax.random.split(key, 6)
    p = {
        "router": L.dense_init(ks[0], (d, m.num_experts), dtype=jnp.float32),
        "wi": L.dense_init(ks[1], (e, d, f), dtype=dtype),
        "wg": L.dense_init(ks[2], (e, d, f), dtype=dtype),
        "wo": L.dense_init(ks[3], (e, f, d),
                           scale=1.0 / np.sqrt(2 * n_layers), dtype=dtype),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        p["shared"] = L.init_mlp(ks[4], d, fs, cfg.act, n_layers, dtype)
    return p


def _route(x2d, router_w, m):
    """x2d: (T, D) -> (top_w, top_i) each (T, k); plus aux loss.

    With ``m.n_group`` > 1, group-limited greedy (DeepSeek-V2): a group's
    score is its best expert's, and the top k come from the
    ``m.topk_group`` best groups. The top-k weights are renormalised
    (``norm_topk_prob``) or scaled by ``routed_scaling_factor``."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w,
                     precision=jax.lax.Precision.HIGHEST)      # (T, E)
    if getattr(m, "router_act", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    pick = scores
    if m.n_group > 1:
        t = scores.shape[0]
        best = scores.reshape(t, m.n_group, -1).max(-1)        # (T, G)
        _, top_g = jax.lax.top_k(best, m.topk_group)
        kept = jax.nn.one_hot(top_g, m.n_group, dtype=bool).any(-2)
        kept = jnp.repeat(kept, m.num_experts // m.n_group, axis=-1)
        pick = jnp.where(kept, scores, 0.0)
    top_w, top_i = jax.lax.top_k(pick, m.top_k)                # (T, k)
    if m.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    else:
        top_w = top_w * m.routed_scaling_factor
    # load-balance aux loss (Switch-style)
    probs_mean = jnp.mean(scores, axis=0)                      # (E,)
    counts = jnp.zeros((m.num_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    aux = m.num_experts * jnp.sum(frac * probs_mean) * m.aux_loss_coef
    return top_w, top_i, aux


def _bucketed_expert_compute(xs, seg, pos_in_seg, num_experts, capacity,
                             wi, wg, wo, act):
    """xs:(N,D) sorted tokens, seg:(N,) expert ids, pos_in_seg:(N,).

    Scatter into (E, C, D), dense expert einsums, gather back (N, D).
    Overflow (pos >= C) tokens are dropped (standard capacity drop).
    """
    n, d = xs.shape
    keep = pos_in_seg < capacity
    slot = jnp.where(keep, pos_in_seg, capacity)               # overflow -> C
    buf = jnp.zeros((num_experts, capacity + 1, d), xs.dtype)
    buf = buf.at[seg, slot].set(xs)                            # drop row C later
    buf = buf[:, :capacity]                                    # (E, C, D)
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    h = L.act_fn(act)(g) * h
    y = jnp.einsum("ecf,efd->ecd", h, wo)                      # (E, C, D)
    y = jnp.pad(y, ((0, 0), (0, 1), (0, 0)))                   # slot C = 0
    return y[seg, slot] * keep[:, None].astype(y.dtype)        # (N, D)


def moe_ffn(x, p, cfg, *, group_size: int = 0):
    """x: (B, S, D) -> (out, aux_loss). Routed + shared experts.

    group_size > 0 processes tokens in groups under lax.scan (bounds the
    transient (E, C, D) buffer for very long sequences).
    """
    m = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    if group_size <= 0 or group_size >= t:
        out, aux = _moe_tokens(x2d, p, cfg)
    else:
        assert t % group_size == 0, (t, group_size)
        xg = x2d.reshape(t // group_size, group_size, d)

        def step(_, xi):
            o, a = _moe_tokens(xi, p, cfg)
            return None, (o, a)

        _, (outs, auxs) = jax.lax.scan(step, None, xg)
        out, aux = outs.reshape(t, d), jnp.mean(auxs)
    if m.num_shared_experts:
        out = out + L.mlp(x2d, p["shared"], cfg.act)
    return out.reshape(b, s, d), aux


def _moe_tokens(x2d, p, cfg):
    m = cfg.moe
    t, d = x2d.shape
    k = m.top_k
    top_w, top_i, aux = _route(x2d, p["router"], m)
    capacity = int(np.ceil(t * k / m.num_experts * m.capacity_factor))
    capacity = max(capacity, 4)

    flat_e = top_i.reshape(-1)                                 # (T*k,)
    sort_idx = jnp.argsort(flat_e)                             # stable
    tok_idx = sort_idx // k
    seg = flat_e[sort_idx]
    xs = x2d[tok_idx]                                          # (T*k, D)
    counts = jnp.bincount(flat_e, length=m.num_experts)
    starts = jnp.cumsum(counts) - counts
    pos_in_seg = jnp.arange(t * k) - starts[seg]

    ys = _bucketed_expert_compute(xs, seg, pos_in_seg, m.num_experts,
                                  capacity, p["wi"], p["wg"], p["wo"], cfg.act)
    w_sorted = top_w.reshape(-1)[sort_idx].astype(ys.dtype)    # (T*k,)
    out = jnp.zeros((t, d), ys.dtype).at[tok_idx].add(ys * w_sorted[:, None])
    return out.astype(x2d.dtype), aux


# --------------------------------------------------------------------- #
# One device's share: held experts, routed over all
# --------------------------------------------------------------------- #
def held_moe_ffn(x, p, cfg, *, layer=None):
    """x: (B, S, D) -> (out, aux_loss): the part of the routed experts'
    output that the held experts ``cfg.moe.held`` give, plus the shared
    experts. Every token is routed over all ``num_experts``; each of its
    (token, expert) pairs whose expert is held is computed, none dropped.

    ``p``'s ``wi``, ``wg``, ``wo`` hold the held experts only: (E_h, D, F)
    and (E_h, F, D), or, with ``layer`` given, the stage's stacked (L,
    E_h, ...) weights, read at ``layer`` where they lie."""
    m = cfg.moe
    if cfg.act != "swiglu":
        raise ValueError(f"held experts run SwiGLU, not {cfg.act!r}")
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    with jax.named_scope("moe_router"):
        top_w, top_i, aux = _route(x2d, p["router"], m)
    with jax.named_scope("held_experts"):
        w = [p[k] if layer is not None else p[k][None]
             for k in ("wi", "wg", "wo")]
        out = _held_experts(x2d, top_w, top_i, w,
                            0 if layer is None else layer, m)
    if m.num_shared_experts:
        with jax.named_scope("shared_experts"):
            out = out + L.mlp(x2d, p["shared"], cfg.act)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _held_experts(x2d, top_w, top_i, w, layer, m):
    """Sum over each token's held (token, expert) pairs of weight times
    expert output, (T, D) in f32. Pairs sort by held expert into tiles of
    one expert each (an expert's last tile padded with zero rows), which the
    grouped kernel runs; at most every pair is held, a static bound."""
    t, d = x2d.shape
    n_held = m.num_held
    start = 0 if m.held is None else m.held[0]
    n = t * m.top_k
    tm = pick_tile(t * m.top_k / m.num_experts)   # an expert's expected rows
    n_tiles = -(-n // tm) + n_held               # each group's last tile
    rows = n_tiles * tm

    local = top_i.reshape(-1) - start
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)                      # n_held: not here
    order = jnp.argsort(key)
    key_s, tok_s = key[order], order // m.top_k
    counts = jnp.bincount(key, length=n_held + 1)[:n_held]
    tiles = -(-counts // tm)                                   # per expert
    first_tile = jnp.cumsum(tiles) - tiles
    first_pair = jnp.cumsum(counts) - counts
    k = jnp.minimum(key_s, n_held - 1)
    row = jnp.where(key_s < n_held,
                    first_tile[k] * tm + jnp.arange(n) - first_pair[k], rows)
    xs = jnp.zeros((rows, d), x2d.dtype).at[row].set(x2d[tok_s], mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(tiles), jnp.arange(n_tiles),
                         side="right"), n_held - 1).astype(jnp.int32)
    ys = ops.expert_ffn(xs, *w, tile_expert, jnp.sum(tiles), layer, tm)
    y = ys.at[row].get(mode="fill", fill_value=0).astype(jnp.float32)
    wt = top_w.reshape(-1)[order][:, None]
    return jnp.zeros((t, d), jnp.float32).at[tok_s].add(y * wt)


# --------------------------------------------------------------------- #
# Explicit expert parallelism (shard_map) — used by hillclimbed configs
# --------------------------------------------------------------------- #
def moe_ffn_ep_sharded(x, p, cfg, mesh):
    """shard_map wrapper: tokens split over (dp, 'model'·seq), experts over
    'model'; inside, a static-capacity all_to_all moves tokens to their
    expert shard and back (production EP — replaces GSPMD-inferred gathers).
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding import dp_axes
    dp = dp_axes(mesh)
    x_spec = P(dp, "model", None)                    # B@dp, S@model (SP)
    e_specs = {
        "router": P(None, None),
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }
    if "shared" in p:
        e_specs["shared"] = jax.tree_util.tree_map(lambda _: P(None, None),
                                                   p["shared"])
    p_specs = {k: e_specs[k] for k in p}

    def inner(xl, pl):
        out, aux = moe_ffn_ep(xl, pl, cfg, axis="model")
        axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        return out, jax.lax.pmean(aux, axes)

    out, aux = jax.shard_map(
        inner, mesh=mesh, in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P()), check_vma=False)(x, p)
    return out, aux
def _quant_rows(x):
    """Per-row symmetric int8 quantization: (q int8, scales f32)."""
    xf = x.astype(jnp.float32)
    sc = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / sc), -127, 127).astype(jnp.int8)
    return q, sc


def _dequant_rows(q, sc, dtype):
    return (q.astype(jnp.float32) * sc).astype(dtype)


def moe_ffn_ep(x, p, cfg, *, axis: str = "model"):
    """Expert-parallel MoE under shard_map along ``axis``.

    Call *inside* shard_map: x is the per-device token shard (B_l, S_l, D);
    expert weights p['wi'] etc. are the per-device expert shard (E_l, D, F).
    Tokens are exchanged with a static-capacity all_to_all keyed by the
    target expert shard, computed locally, and returned.
    """
    m = cfg.moe
    n_sh = jax.lax.axis_size(axis)
    e_local = m.num_experts // n_sh
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    k = m.top_k

    top_w, top_i, aux = _route(x2d, p["router"], m)
    flat_e = top_i.reshape(-1)
    target = flat_e // e_local                                 # shard id (T*k,)

    # bucket by target shard with per-shard capacity
    cap = int(np.ceil(t * k / n_sh * m.capacity_factor))
    sort_idx = jnp.argsort(target)
    tok_idx = sort_idx // k
    tgt_sorted = target[sort_idx]
    counts = jnp.bincount(target, length=n_sh)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[tgt_sorted]
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)

    send_x = jnp.zeros((n_sh, cap + 1, d), x2d.dtype).at[tgt_sorted, slot].set(x2d[tok_idx])
    send_e = jnp.full((n_sh, cap + 1), -1, jnp.int32).at[tgt_sorted, slot].set(flat_e[sort_idx])
    send_x, send_e = send_x[:, :cap], send_e[:, :cap]

    int8_a2a = getattr(m, "a2a_dtype", "bf16") == "int8"
    if int8_a2a:
        q, sc = _quant_rows(send_x)
        recv_x = _dequant_rows(jax.lax.all_to_all(q, axis, 0, 0),
                               jax.lax.all_to_all(sc, axis, 0, 0), x2d.dtype)
    else:
        recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis, 0, 0, tiled=False)
    rx = recv_x.reshape(-1, d)                                 # (n_sh*cap, D)
    re = recv_e.reshape(-1)

    # local expert ids; invalid slots -> expert e_local (dropped)
    shard_id = jax.lax.axis_index(axis)
    le = jnp.where(re >= 0, re - shard_id * e_local, e_local)
    # bucket by local expert
    cap_e = int(np.ceil(n_sh * cap / e_local * 1.0))
    s_idx = jnp.argsort(le)
    le_s = le[s_idx]
    cnt = jnp.bincount(le, length=e_local + 1)
    st = jnp.cumsum(cnt) - cnt
    pe = jnp.arange(rx.shape[0]) - st[le_s]
    keep_e = (pe < cap_e) & (le_s < e_local)
    slot_e = jnp.where(pe < cap_e, pe, cap_e)
    buf = jnp.zeros((e_local + 1, cap_e + 1, d), rx.dtype).at[
        jnp.where(keep_e, le_s, e_local), slot_e].set(rx[s_idx])
    buf = buf[:e_local, :cap_e]

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    y = jnp.einsum("ecf,efd->ecd", L.act_fn(cfg.act)(g) * h, p["wo"])
    y = jnp.pad(y, ((0, 1), (0, 1), (0, 0)))
    ye = y[jnp.where(keep_e, le_s, e_local), slot_e]           # sorted order
    # unsort back to recv order
    y_recv = jnp.zeros_like(rx).at[s_idx].set(ye)
    if int8_a2a:
        q, sc = _quant_rows(y_recv.reshape(n_sh, cap, d))
        y_send = _dequant_rows(jax.lax.all_to_all(q, axis, 0, 0),
                               jax.lax.all_to_all(sc, axis, 0, 0), rx.dtype)
    else:
        y_send = jax.lax.all_to_all(y_recv.reshape(n_sh, cap, d), axis, 0, 0)

    # back on source device: slots -> tokens
    y_tok = y_send.reshape(n_sh, cap, d)
    y_flat = jnp.pad(y_tok, ((0, 0), (0, 1), (0, 0)))[tgt_sorted, slot]
    y_flat = y_flat * keep[:, None].astype(y_flat.dtype)
    w_sorted = top_w.reshape(-1)[sort_idx].astype(y_flat.dtype)
    out = jnp.zeros((t, d), y_flat.dtype).at[tok_idx].add(y_flat * w_sorted[:, None])

    if m.num_shared_experts:
        out = out + L.mlp(x2d, p["shared"], cfg.act)
    return out.reshape(b, s, d).astype(x.dtype), aux
