"""The plain reference (the dense equations module's) against the
program's own forward and decode step at a CPU test size, both in float32
(so only the equations can differ), against its own logits from before it
moved into ``equations/dense.py``, and the lower-precision control against
the limit."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correct
import run
import weights

DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIGS = ["tiny-phi3", "tiny-stablelm"]
dense = run.load_plugin("equations", "dense")


def setup(name, seed, dtype):
    from repro.configs import get_config, reduced
    from repro.models import transformer as T
    conf = json.loads((DATA / f"{name}.json").read_text())
    cfg = reduced(get_config(conf["program"]["arch"]))
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k, dtype),
                            jax.random.PRNGKey(0))
    params = weights.make_params(shapes, conf["initializer_range"],
                                 weights.base_key(seed, 0))
    return conf, cfg, params


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_matches_program_in_f32(name):
    from repro.models import transformer as T
    conf, cfg, params = setup(name, 3, jnp.float32)
    tok = weights.make_tokens((2, 32), conf["vocab_size"],
                              weights.base_key(3, 1))
    with jax.default_matmul_precision("highest"):
        prog = T.forward(params, cfg, {"tokens": tok})[0]
    ref = dense.Reference(conf).prefill_logits(params, tok)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_matches_program_in_f32(name):
    from repro.models import transformer as T
    conf, cfg, params = setup(name, 4, jnp.float32)
    shapes = jax.eval_shape(
        lambda: T.init_decode_caches(cfg, 3, 32, jnp.float32))
    caches = weights.make_caches(shapes, weights.base_key(4, 2))
    tok = weights.make_tokens((3,), conf["vocab_size"],
                              weights.base_key(4, 1))
    with jax.default_matmul_precision("highest"):
        prog = T.decode_step(params, cfg, caches, tok, jnp.int32(16))[0]
    ref = dense.Reference(conf).decode_logits(params, caches, tok, 16)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_lower_precision_fails_the_comparison(name):
    """The control: the reference in fp8 (e4m3, scaled), put where the
    program's bf16 step would be, exceeds the limit; the program's bf16
    step on the same seeds stays under it."""
    import functools

    from repro.launch.serve import prefill_logits
    conf = json.loads((DATA / f"{name}.json").read_text())
    limit = conf["limits"]["prefill_max_logit_gap"]
    ref = dense.Reference(conf)
    for seed in (11, 12, 13):
        _, cfg, params = setup(name, seed, jnp.bfloat16)
        tok = weights.make_tokens((2, 64), conf["vocab_size"],
                                  weights.base_key(seed, 1))
        want = ref.prefill_logits(params, tok)
        ctl = ref.prefill_logits(params, tok, quant="fp8")
        assert correct.widest_gap(ctl, want) > limit
        prog = jax.jit(functools.partial(prefill_logits, cfg=cfg))(
            params, {"tokens": tok})
        assert correct.widest_gap(prog, want) <= limit


BEFORE = json.loads((DATA / "dense-reference-logits.json").read_text())


@pytest.mark.parametrize("quant", [None, "fp8", "int8"])
@pytest.mark.parametrize("name", CONFIGS)
def test_logits_unchanged_by_the_move(name, quant):
    """The module's logits are the ones ``reference.Dense`` gave on the
    same seeded weights. Decode is compared bit for bit. Prefill's larger
    matmuls are split over XLA's CPU threads, whose count moves a float32
    sum by a few ulps (<= 2e-6 seen between one thread and eight), so it
    is held to 1e-5; a change to an equation moves it by far more."""
    from repro.models import transformer as T
    conf, cfg, params = setup(name, 7, jnp.float32)
    tok = weights.make_tokens((2, 32), conf["vocab_size"],
                              weights.base_key(7, 1))
    shapes = jax.eval_shape(
        lambda: T.init_decode_caches(cfg, 3, 32, jnp.float32))
    caches = weights.make_caches(shapes, weights.base_key(7, 2))
    dtok = weights.make_tokens((3,), conf["vocab_size"],
                               weights.base_key(7, 3))
    ref = dense.Reference(conf)
    key = f"{name}.{quant or 'f32'}"
    pre = np.asarray(ref.prefill_logits(params, tok, quant=quant))
    dec = np.asarray(ref.decode_logits(params, caches, dtok, 16,
                                       quant=quant))
    np.testing.assert_allclose(
        pre[:, ::8, :8].ravel(),
        np.float32(BEFORE["logits"][f"{key}.prefill"]), rtol=0, atol=1e-5)
    np.testing.assert_array_equal(
        dec[:, :8].ravel(), np.float32(BEFORE["logits"][f"{key}.decode"]))


def test_gap_reads_the_served_tokens():
    ref = jnp.array([[0.0, 1.0, 0.5], [2.0, 0.0, 1.9]])
    assert correct.widest_gap(ref, ref) == 0.0
    served = jnp.array([[0.0, 0.0, 9.0], [0.0, 0.0, 3.0]])  # picks 2, 2
    assert correct.widest_gap(served, ref) == pytest.approx(0.5)
    assert correct.widest_gap(served.at[0, 0].set(jnp.nan), ref) == \
        float("inf")
    assert correct.widest_gap(served[:1], ref) == float("inf")
