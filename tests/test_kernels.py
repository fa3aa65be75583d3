"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles,
executed with pallas interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,ss", [(256, 128, 256, 1), (128, 256, 384, 3),
                                      (384, 128, 128, 4)])
def test_sliced_matmul(m, k, n, ss, dtype):
    k1, k2 = jax.random.split(KEY)
    a, b = rand(k1, (m, k), dtype), rand(k2, (k, n), dtype)
    out = ops.sliced_matmul(a, b, slice_size=ss)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.matmul(a, b), np.float32),
        **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("run_a,run_b", [(1, 1), (2, 1), (1, 3)])
def test_coschedule(run_a, run_b, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    a, b = rand(k1, (256, 128), dtype), rand(k2, (128, 256), dtype)
    x = rand(k3, (1024, 256), dtype)
    mm, st = ops.coschedule(a, b, x, run_a=run_a, run_b=run_b)
    mref, sref = ref.coschedule(a, b, x, 2.0)
    np.testing.assert_allclose(np.asarray(mm, np.float32),
                               np.asarray(mref, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(sref, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,s,d,causal", [(1, 2, 256, 64, True),
                                            (2, 1, 128, 128, True),
                                            (1, 2, 256, 64, False)])
def test_flash_attention(b, h, s, d, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, h, s, d), dtype)
    k = rand(ks[1], (b, h, s, d), dtype)
    v = rand(ks[2], (b, h, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=128, bk=128)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,n,chunk", [(2, 64, 2, 32, 16),
                                           (1, 128, 4, 64, 32)])
def test_rwkv6_scan(b, s, h, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    r = rand(ks[0], (b, s, h, n), dtype)
    k = rand(ks[1], (b, s, h, n), dtype)
    v = rand(ks[2], (b, s, h, n), dtype)
    w_log = -jnp.exp(rand(ks[3], (b, s, h, n), jnp.float32) - 1.0)
    u = rand(ks[4], (h, n), jnp.float32) * 0.1
    out = ops.rwkv6_scan(r, k, v, w_log, u, chunk=chunk)
    want, _ = ref.rwkv6(r, k, v, w_log, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("b,s,w,chunk,bw", [(2, 256, 512, 64, 256),
                                            (1, 128, 1024, 128, 512)])
def test_rg_lru(b, s, w, chunk, bw):
    ks = jax.random.split(KEY, 2)
    x = rand(ks[0], (b, s, w), jnp.float32)
    a_log = -jnp.exp(rand(ks[1], (b, s, w), jnp.float32))
    out = ops.rg_lru(x, a_log, chunk=chunk, bw=bw)
    want = ref.rg_lru(x, a_log)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_kernels_match_model_layers():
    """Pallas rwkv6 kernel agrees with the model's chunked implementation."""
    from repro.models import recurrent as R
    ks = jax.random.split(KEY, 5)
    b, s, h, n = 2, 64, 2, 32
    r = rand(ks[0], (b, s, h, n), jnp.float32)
    k = rand(ks[1], (b, s, h, n), jnp.float32)
    v = rand(ks[2], (b, s, h, n), jnp.float32)
    w_log = -jnp.exp(rand(ks[3], (b, s, h, n), jnp.float32) - 1.0)
    u = rand(ks[4], (h, n), jnp.float32) * 0.1
    state = jnp.zeros((b, h, n, n), jnp.float32)
    want, _ = R.rwkv6_chunked(r, k, v, w_log, u, state, chunk=16)
    got = ops.rwkv6_scan(r, k, v, w_log, u, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def _expert_tiles(sizes, tm, d, dtype, key):
    """Rows for experts with ``sizes`` rows each, laid out as the held
    layer lays them out: each expert's rows in whole ``tm``-row tiles
    (zero-padded), then spare tiles up to a static bound. Returns (xs,
    tile_expert, tiles in use, each row's expert or -1 for padding)."""
    tiles = [-(-n // tm) for n in sizes]
    n_tiles = sum(tiles) + 2                  # two spare tiles, unused
    xs = np.zeros((n_tiles * tm, d), np.float32)
    owner = np.full(n_tiles * tm, -1)
    rows = np.asarray(rand(key, (sum(sizes), d), jnp.float32))
    tile_expert, r, at = [], 0, 0
    for e, (n, t) in enumerate(zip(sizes, tiles)):
        xs[at:at + n] = rows[r:r + n]
        owner[at:at + n] = e
        tile_expert += [e] * t
        r, at = r + n, at + t * tm
    tile_expert += [len(sizes) - 1] * 2
    return (jnp.asarray(xs, dtype), jnp.asarray(tile_expert, jnp.int32),
            sum(tiles), owner)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes", [(0, 5, 0, 3), (37, 0, 0, 0),
                                   (1, 16, 17, 40), (0, 0, 0, 0)],
                         ids=["empty-groups", "all-in-one", "ragged",
                              "no-rows"])
def test_expert_ffn(sizes, dtype):
    """The grouped expert FFN against a plain per-expert SwiGLU, on the
    rows of the tiles in use: experts with no rows, all rows on one expert
    (over several tiles), ragged counts, and no rows at all."""
    n_layers, d, f, tm, layer = 2, 128, 256, 16, 1
    ks = jax.random.split(KEY, 4)
    wi, wg = (rand(k, (n_layers, len(sizes), d, f), dtype) * 0.1
              for k in ks[:2])
    wo = rand(ks[2], (n_layers, len(sizes), f, d), dtype) * 0.1
    xs, tile_expert, used, owner = _expert_tiles(sizes, tm, d, dtype, ks[3])
    got = np.asarray(ops.expert_ffn(xs, wi, wg, wo, tile_expert,
                                    jnp.int32(used), jnp.int32(layer), tm),
                     np.float32)
    assert got.shape == xs.shape
    for e in range(len(sizes)):
        rows = owner == e
        x = xs[rows]
        h = (jax.nn.silu(jnp.dot(x, wg[layer, e],
                                 preferred_element_type=jnp.float32))
             * jnp.dot(x, wi[layer, e], preferred_element_type=jnp.float32))
        want = jnp.dot(h.astype(dtype), wo[layer, e],
                       preferred_element_type=jnp.float32)
        np.testing.assert_allclose(got[rows], np.asarray(want, np.float32),
                                   **tol(dtype))
    np.testing.assert_allclose(               # and the oracle the VJP uses
        got[owner >= 0], np.asarray(ref.expert_ffn(
            xs, wi, wg, wo, tile_expert, layer, tm=tm),
            np.float32)[owner >= 0], **tol(dtype))


def test_expert_ffn_gradient_is_the_oracles():
    d, f, tm = 128, 128, 16
    ks = jax.random.split(KEY, 4)
    wi, wg = (rand(k, (1, 2, d, f), jnp.float32) * 0.1 for k in ks[:2])
    wo = rand(ks[2], (1, 2, f, d), jnp.float32) * 0.1
    xs, te, used, owner = _expert_tiles((9, 20), tm, d, jnp.float32, ks[3])
    live = jnp.asarray(owner >= 0)[:, None]

    def loss(fn, *w):          # rows of tiles not in use are unwritten
        return jnp.sum(jnp.square(jnp.where(live, fn(*w), 0.0)))

    got = jax.grad(lambda *w: loss(lambda *a: ops.expert_ffn(
        *a, te, jnp.int32(used), jnp.int32(0), tm), *w),
        argnums=(0, 1, 2, 3))(xs, wi, wg, wo)
    want = jax.grad(lambda *w: loss(lambda *a: ref.expert_ffn(
        *a, te, 0, tm=tm), *w), argnums=(0, 1, 2, 3))(xs, wi, wg, wo)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)
