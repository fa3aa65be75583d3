"""DeepSeek-V2 as one chip's share of expert parallelism, at a CPU test size
(the program's ``reduced`` deepseek-v2-236b-ep8: 16 experts in 4 groups,
2 groups and 3 experts a token, one shared, group 0 held, 1 dense + 2
expert layers), in float32 against the benchmark's plain reference
(``benchmarks/chip/equations/deepseek_v2.py``) and against numpy
transcriptions of Hugging Face's ``MoEGate`` and YaRN helpers.

Tolerances: program and reference both run float32 (matmuls at
``highest``), so only the equations can differ; what is left is the order
of float32 sums (XLA's CPU backend splits larger ones over threads), a few
ulps of logits of size ~1, held to 2e-5 absolute and 1e-4 relative. A
wrong equation (a missed routing weight, a mask, a scale) moves them by
1e-3 or more.
"""
import dataclasses
import importlib.util
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
CONF = json.loads((CHIP / "tests" / "data" / "tiny-deepseek-v2.json")
                  .read_text())
TOL = dict(atol=2e-5, rtol=1e-4)


def _equations():
    if str(CHIP) not in sys.path:
        sys.path.insert(0, str(CHIP))
    spec = importlib.util.spec_from_file_location(
        "test_deepseek_v2_equations", CHIP / "equations" / "deepseek_v2.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


EQ = _equations()


@pytest.fixture(scope="module")
def tiny():
    """The reduced program config and float32 weights drawn as the
    benchmark draws them."""
    import weights
    cfg = dataclasses.replace(reduced(get_config("deepseek-v2-236b-ep8")),
                              dtype="float32")
    EQ.check(cfg, CONF)
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    params = weights.make_params(shapes, CONF["initializer_range"],
                                 weights.base_key(5, 0))
    return cfg, params


def tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              CONF["vocab_size"])


def test_prefill_matches_reference(tiny):
    cfg, params = tiny
    tok = tokens(1, (2, 48))
    with jax.default_matmul_precision("highest"):
        prog = T.forward(params, cfg, {"tokens": tok})[0]
    ref = EQ.Reference(CONF).prefill_logits(params, tok)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), **TOL)


def test_prefill_then_decode_matches_full_forward(tiny):
    """The program prefills 16 tokens into its cache, then decodes the
    next 16 one at a time through it; every logit equals the reference's
    full forward over all 32."""
    cfg, params = tiny
    tok = tokens(2, (3, 32))
    full = EQ.Reference(CONF).prefill_logits(params, tok)
    with jax.default_matmul_precision("highest"):
        caches = T.init_decode_caches(cfg, 3, 32, jnp.float32)
        got, caches = T.prefill(params, cfg, {"tokens": tok[:, :16]}, caches)
        got = [got]
        step = jax.jit(lambda c, x, t: T.decode_step(params, cfg, c, x, t))
        for t in range(16, 32):
            lg, caches = step(caches, tok[:, t], jnp.int32(t))
            got.append(lg[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(full), **TOL)


def _uncut(cfg):
    """The same layer with every expert held."""
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            held=None))


def test_shares_add_up_to_the_uncut_layer():
    """Each of the 4 groups' chips computes its held experts' part; those
    parts, plus the shared expert counted once, are the whole layer as the
    reference computes it with all 16 experts."""
    cfg = reduced(get_config("deepseek-v2-236b-ep8"))
    full = _uncut(cfg)
    m = full.moe
    p = M.init_moe(jax.random.PRNGKey(3), full, 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    size = m.num_experts // m.n_group
    total = 0.0
    for g in range(m.n_group):
        held = (g * size, (g + 1) * size)
        cut = dataclasses.replace(cfg, moe=dataclasses.replace(
            m, held=held, num_shared_experts=0))
        share = {k: (a[held[0]:held[1]] if k in ("wi", "wg", "wo") else a)
                 for k, a in p.items()}
        with jax.default_matmul_precision("highest"):
            total = total + M.held_moe_ffn(x, share, cut)[0]
    with jax.default_matmul_precision("highest"):
        total = total + L.mlp(x, p["shared"], cfg.act)
    conf = dict(CONF, n_routed_experts=m.num_experts,
                deployment=dict(CONF["deployment"],
                                held_experts=[0, m.num_experts]))
    want = EQ.Reference(conf).moe(x.reshape(-1, cfg.d_model), p, None)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), **TOL)


# --------------------------------------------------------------------- #
# routing against Hugging Face's MoEGate (group_limited_greedy)
# --------------------------------------------------------------------- #
def hf_moe_gate(logits, top_k, n_group, topk_group, norm_topk_prob,
                routed_scaling_factor):
    """numpy transcription of modeling_deepseek.MoEGate.forward after the
    gate's linear layer: (topk_idx, topk_weight), each (T, top_k)."""
    e = np.exp(logits - logits.max(-1, keepdims=True))
    scores = e / e.sum(-1, keepdims=True)
    n = scores.shape[0]
    group_scores = scores.reshape(n, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, -1, kind="stable")[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, -1)
    score_mask = np.repeat(group_mask, scores.shape[1] // n_group, -1)
    tmp = np.where(score_mask.astype(bool), scores, 0.0)
    idx = np.argsort(-tmp, -1, kind="stable")[:, :top_k]
    w = np.take_along_axis(tmp, idx, -1)
    if top_k > 1 and norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * routed_scaling_factor
    return idx, w


def test_routing_matches_hf_moe_gate():
    cfg = reduced(get_config("deepseek-v2-236b-ep8"))
    m = cfg.moe
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 1.0, (63, m.num_experts)).astype(np.float32)
    # a token whose 3 best experts are 0 (group 0), 4 (group 1) and 8
    # (group 2), but whose 2 best groups are 0 and 1 (group maxes 3.0,
    # 2.9, 2.85): expert 8 is left out, and 5 (group 1) taken instead
    special = np.full(m.num_experts, -4.0, np.float32)
    special[[0, 4, 5, 8]] = [3.0, 2.9, 2.8, 2.85]
    logits = np.concatenate([logits, special[None]])
    x = np.eye(logits.shape[0], cfg.d_model, dtype=np.float32)
    router = np.zeros((cfg.d_model, m.num_experts), np.float32)
    router[:logits.shape[0]] = logits
    w, idx, _ = M._route(jnp.asarray(x), jnp.asarray(router), m)
    want_idx, want_w = hf_moe_gate(logits, m.top_k, m.n_group, m.topk_group,
                                   m.norm_topk_prob, m.routed_scaling_factor)
    got = {(t, int(e)): float(v) for t in range(len(logits))
           for e, v in zip(np.asarray(idx)[t], np.asarray(w)[t])}
    want = {(t, int(e)): float(v) for t in range(len(logits))
            for e, v in zip(want_idx[t], want_w[t])}
    assert got.keys() == want.keys()
    np.testing.assert_allclose([got[k] for k in want],
                               [want[k] for k in want], rtol=1e-5)
    assert {e for t, e in got if t == len(logits) - 1} == {0, 4, 5}


# --------------------------------------------------------------------- #
# YaRN against Hugging Face's helpers
# --------------------------------------------------------------------- #
def hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
            mscale_all_dim):
    """numpy transcription of modeling_deepseek's yarn_find_correction_dim,
    yarn_find_correction_range, yarn_linear_ramp_mask, yarn_get_mscale and
    DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache: (inv_freq, the cos
    and sin scale, the attention's mscale)."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    ar = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** ar)
    freq_inter = 1.0 / (factor * base ** ar)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    return (inv_freq, get_mscale(factor, mscale)
            / get_mscale(factor, mscale_all_dim),
            get_mscale(factor, mscale_all_dim))


def test_yarn_matches_hf_at_published_numbers():
    cfg = get_config("deepseek-v2-236b")
    y = cfg.yarn
    inv, cos_scale, mscale = hf_yarn(
        cfg.mla.qk_rope_dim, cfg.rope_theta, y.factor,
        y.original_max_position_embeddings, y.beta_fast, y.beta_slow,
        y.mscale, y.mscale_all_dim)
    np.testing.assert_allclose(
        L.yarn_frequencies(cfg.mla.qk_rope_dim, cfg.rope_theta, y), inv,
        rtol=1e-6)
    rs = json.loads((CHIP / "configs" / "deepseek-v2-236b-ep8.json")
                    .read_text())["rope_scaling"]
    np.testing.assert_allclose(EQ.yarn_inv_freq(64, 10000.0, rs), inv,
                               rtol=1e-6)
    assert cos_scale == 1.0
    assert L.yarn_mscale(y.factor, y.mscale_all_dim) == pytest.approx(
        mscale) == pytest.approx(1.2608, abs=1e-4)
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    # the ramp really blends: dims below 10 extrapolate (rope's own
    # frequency), dims from 23 interpolate (divided by the factor)
    extra = L.rope_frequencies(64, 10000.0)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)


def test_yarn_scales_the_mla_softmax():
    """The program's MLA attention at one query over two keys equals the
    reference's, whose scale is (nope + rope)**-0.5 times mscale**2; the
    scale left at (nope + rope)**-0.5 would not."""
    cfg = reduced(get_config("deepseek-v2-236b-ep8"))
    assert cfg.yarn is not None
    p = T.init_params(cfg, jax.random.PRNGKey(6), jnp.float32)
    bare = dataclasses.replace(cfg, yarn=None)
    tok = tokens(6, (1, 8))
    with jax.default_matmul_precision("highest"):
        with_yarn = T.forward(p, cfg, {"tokens": tok})[0]
        without = T.forward(p, bare, {"tokens": tok})[0]
    ref = EQ.Reference(CONF).prefill_logits(p, tok)
    np.testing.assert_allclose(np.asarray(with_yarn), np.asarray(ref), **TOL)
    assert float(jnp.max(jnp.abs(without - ref))) > 1e-3


# --------------------------------------------------------------------- #
# no capacity, no drop
# --------------------------------------------------------------------- #
def test_no_token_is_dropped_when_all_route_to_one_expert():
    """A router that sends every one of 64 tokens to held expert 0 (and
    two more): 64 rows for one expert where a capacity path keeps
    ceil(64 * 3 / 16 * 1.25) = 15. The layer still equals the
    reference."""
    cfg = reduced(get_config("deepseek-v2-236b-ep8"))
    m = cfg.moe
    p = M.init_moe(jax.random.PRNGKey(8), cfg, 3, jnp.float32)
    p["router"] = p["router"].at[0].set(0.0).at[0, 0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 64, cfg.d_model))
    x = x.at[..., 0].set(5.0)
    _, idx, _ = M._route(x[0], p["router"], m)
    assert bool(jnp.all(jnp.any(idx == 0, axis=-1)))
    with jax.default_matmul_precision("highest"):
        got = M.held_moe_ffn(x, p, cfg)[0]
    want = EQ.Reference(CONF).moe(x[0], p, None)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), **TOL)


def test_cost_model_counts_the_held_share():
    """``cell_cost``, which profiles every submitted job, counts an expert
    layer of the ep8 share as routing over all 160 experts, the held
    group's expected pairs (6 x 20 / 160 a token) through SwiGLU experts,
    and the shared experts; and the share's parameters as the 20 held."""
    from repro.core.costs import layer_flops_fwd
    cfg = get_config("deepseek-v2-236b-ep8")
    b, s = 2, 512
    t, d, f = b * s, cfg.d_model, cfg.moe.d_ff_expert
    moe = (layer_flops_fwd(cfg, b, s, "attn", True)
           - layer_flops_fwd(cfg, b, s, "attn", False))
    want = (2 * t * d * 160 + t * 160 + 2 * (t * 6 * 20 / 160) * d * f * 3
            + 2 * t * d * 2 * f * 3 - 2 * t * d * cfg.d_ff * 3)
    assert moe == pytest.approx(want, rel=1e-12)
    per_expert = 3 * d * f
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes["stage1"]["sub0"]["moe"]) if a.shape[-2:] in ((d, f), (f, d))
        and a.shape[1] == 20)
    assert held == 4 * 20 * per_expert
    assert cfg.param_count() == pytest.approx(T.count_params(shapes),
                                              rel=1e-3)
