"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes and no NaNs; plus decode<->forward consistency.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced, applicable_shapes
from repro.data.synthetic import make_batch
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    arch = request.param
    cfg = reduced(get_config(arch))
    params = T.init_params(cfg, KEY)
    return arch, cfg, params


def test_config_registry_complete():
    assert len(ARCH_IDS) == 11
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.name == a
        assert cfg.param_count() > 0


def test_long_500k_applicability():
    """Only sub-quadratic archs get the long_500k shape (per assignment)."""
    subq = {a for a in ARCH_IDS
            if any(s.name == "long_500k" for s in applicable_shapes(get_config(a)))}
    assert subq == {"rwkv6-1.6b", "recurrentgemma-9b"}


def test_forward_shapes_and_finite(arch_setup):
    arch, cfg, params = arch_setup
    b, s = 2, 32
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, b, s).items()}
    batch.pop("labels")
    logits, _, aux = T.forward(params, cfg, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


def test_train_step_no_nan(arch_setup):
    arch, cfg, params = arch_setup
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 2, 32).items()}
    # jit: one XLA compile beats per-op eager dispatch through the big graph
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p: T.train_loss(p, cfg, batch), has_aux=True))
    (loss, metrics), grads = grad_fn(params)
    assert bool(jnp.isfinite(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


def test_decode_matches_forward(arch_setup):
    """Incremental decode must reproduce the teacher-forced logits."""
    arch, cfg, _ = arch_setup
    if cfg.moe is not None:  # disable capacity drops for exactness
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = T.init_params(cfg, KEY, dtype=jnp.float32)
    b, s, prompt = 2, 32, 16
    raw = make_batch(cfg, b, s)
    fwd = {"tokens": jnp.asarray(raw["tokens"])}
    if "patches" in raw:
        fwd["patches"] = jnp.asarray(raw["patches"][:, :8])
    if "audio" in raw:
        fwd["audio"] = jnp.asarray(raw["audio"])
    full_logits, _, _ = T.forward(params, cfg, fwd)
    caches = T.init_decode_caches(cfg, b, s, dtype=jnp.float32)
    pre = dict(fwd)
    pre["tokens"] = fwd["tokens"][:, :prompt]
    lp, caches = T.prefill(params, cfg, pre, caches)
    errs = [float(jnp.max(jnp.abs(lp[:, -1] - full_logits[:, prompt - 1])))]
    # jit the step once: the eager loop re-dispatched the whole layer stack
    # per token and dominated the tier-1 suite's runtime
    step = jax.jit(lambda p, c, tok, t: T.decode_step(p, cfg, c, tok, t))
    for t in range(prompt, s):
        lg, caches = step(params, caches, fwd["tokens"][:, t], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 5e-4, f"{arch}: {max(errs)}"


def test_param_counts_match_published():
    expected = {  # billions, loose tolerance (published totals)
        "rwkv6-1.6b": (1.6, 0.25), "stablelm-12b": (12.1, 0.15),
        "starcoder2-15b": (16.0, 0.15), "phi3-mini-3.8b": (3.8, 0.15),
        "deepseek-v2-236b": (236, 0.05), "deepseek-v3-671b": (671, 0.05),
        "qwen2-vl-7b": (7.6, 0.15),
    }
    for a, (target, tol) in expected.items():
        n = get_config(a).param_count() / 1e9
        assert abs(n - target) / target < tol, (a, n)
    # MoE active params
    assert abs(get_config("deepseek-v3-671b").param_count(active_only=True) / 1e9 - 37) < 3
    assert abs(get_config("deepseek-v2-236b").param_count(active_only=True) / 1e9 - 21) < 2


def test_causal_skip_matches_dense_attention():
    """causal_skip (coarse KV-block skipping) is numerically identical."""
    cfg = reduced(get_config("starcoder2-15b"))
    params = T.init_params(cfg, KEY, dtype=jnp.float32)
    # long enough to hit the chunked path with multiple groups
    raw = make_batch(cfg, 1, 4096)
    batch = {"tokens": jnp.asarray(raw["tokens"])}
    base, _, _ = T.forward(params, cfg, batch)
    skip_cfg = dataclasses.replace(cfg, causal_skip=True)
    skip, _, _ = T.forward(params, skip_cfg, batch)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip),
                               atol=2e-4, rtol=2e-4)
