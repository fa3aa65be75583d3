"""The decode attention kernel (interpret mode) against the einsum path,
and the decode step that reads the stacked cache in place against the one
that slices it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.launch.steps import make_serve_step
from repro.models import attention as A
from repro.models import transformer as T

L, B, S, BLOCK = 2, 2, 256, 128
# (q heads, kv heads, head size): phi3's MHA head of 96, and GQA g = 2, 4
HEADS = {"mha_hd96": (4, 4, 96), "gqa_g2": (4, 2, 32), "gqa_g4": (8, 2, 64)}
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}


@functools.lru_cache(maxsize=None)
def _case(heads, dtype, positions_minor):
    """Inputs of one head layout and dtype, and both paths jitted over the
    layer and position."""
    h, kvh, hd = HEADS[heads]
    ks = jax.random.split(jax.random.PRNGKey(list(HEADS).index(heads)), 5)
    q = jax.random.normal(ks[0], (B, h, hd), dtype)
    kc, vc = (jax.random.normal(k, (L, B, S, kvh, hd), dtype) for k in ks[1:3])
    kn, vn = (jax.random.normal(k, (B, kvh, hd), dtype) for k in ks[3:5])

    @jax.jit
    def kernel(layer, t):
        return da.decode_attention(q, kc, vc, layer, t, kn, vn, block=BLOCK,
                                   positions_minor=positions_minor,
                                   interpret=True)

    @jax.jit
    def einsum(layer, t):
        k = jax.lax.dynamic_update_slice_in_dim(kc[layer], kn[:, None], t, 1)
        v = jax.lax.dynamic_update_slice_in_dim(vc[layer], vn[:, None], t, 1)
        return A.decode_attention(q[:, None], k, v, t + 1)[:, 0]

    return kernel, einsum


@pytest.mark.parametrize("positions_minor", [True, False],
                         ids=["positions_minor", "row_major"])
@pytest.mark.parametrize("t", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, S - 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_kernel_matches_einsum_decode(heads, dtype, t, positions_minor):
    kernel, einsum = _case(heads, dtype, positions_minor)
    layer = jnp.int32(1)
    got = kernel(layer, jnp.int32(t))
    want = einsum(layer, jnp.int32(t))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_pick_block_divides_and_fits():
    assert da.pick_block(512, 32, 96, 2) == 128     # phi3's cache
    assert da.pick_block(40, 4, 32, 4) == 40        # not a multiple of 128
    for s, kv, hd in [(4096, 8, 128), (1024, 2, 64), (384, 32, 96)]:
        bs = da.pick_block(s, kv, hd, 2)
        assert s % bs == 0 and bs % 128 == 0
        assert bs == 128 or 2 * bs * kv * hd * 2 <= da.BLOCK_BYTES


def test_cpu_cache_is_row_major():
    assert not ops.cache_positions_minor((L, B, S, 4, 96), jnp.bfloat16)


@pytest.fixture(scope="module")
def decode_setup():
    """A reduced phi3 in f32, a cache of random contents, two tokens."""
    cfg = reduced(get_config("phi3-mini-3.8b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    caches = T.init_decode_caches(cfg, B, 32, dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    caches = jax.tree_util.tree_map(
        lambda a: jax.random.normal(next(keys), a.shape, a.dtype), caches)
    tok = jnp.array([3, 11], jnp.int32)
    return cfg, params, caches, tok


@pytest.mark.parametrize("t", [0, 17, 31])
def test_decode_step_in_place_writes_caches_like_slicing(decode_setup, t):
    cfg, params, caches, tok = decode_setup
    tt = jnp.int32(t)

    def run(in_place):
        with pytest.MonkeyPatch.context() as mp:
            if not in_place:
                mp.setattr(A, "reads_cache_in_place", lambda *a: False)
            step = jax.jit(lambda p, c, k, i: T.decode_step(p, cfg, c, k, i))
            jaxpr = str(jax.make_jaxpr(step)(params, caches, tok, tt))
            assert ("decode_attention" in jaxpr) == in_place
            return step(params, caches, tok, tt)

    (lg_new, c_new), (lg_old, c_old) = run(True), run(False)
    np.testing.assert_allclose(lg_new, lg_old, atol=5e-5, rtol=5e-5)
    leaves = jax.tree_util.tree_leaves
    for (path, new), old, before in zip(
            jax.tree_util.tree_leaves_with_path(c_new), leaves(c_old),
            leaves(caches)):
        assert new.shape == before.shape, path
        # layers past the first see hidden states summed in another order
        np.testing.assert_allclose(new, old, atol=1e-5, rtol=1e-5,
                                   err_msg=str(path))
        np.testing.assert_array_equal(new[0], old[0], err_msg=str(path))
        # the new token at t in every layer, everything else untouched
        untouched = np.delete(np.asarray(new), t, axis=2)
        np.testing.assert_array_equal(
            untouched, np.delete(np.asarray(before), t, axis=2))
        assert not np.array_equal(new[:, :, t], before[:, :, t])

    serve_step = jax.jit(make_serve_step(cfg))
    lg_s, c_s = serve_step(params, caches, tok, tt)
    np.testing.assert_array_equal(lg_s, lg_new)
    jax.tree_util.tree_map(np.testing.assert_array_equal, c_s, c_new)
