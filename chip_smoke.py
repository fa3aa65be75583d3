"""Smoke run of the Kernelet serving path on a TPU.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # a four-chip host

On one chip it runs, in one process:
  1. device check: fails unless JAX's backend is a TPU;
  2. kernels: the Pallas kernels compiled for the chip (never interpreted)
     at real widths, each compared with ``repro.kernels.ref``;
  3. serving: a phi3-mini-3.8b prefill tenant and a decode tenant at the
     published widths, submitted to ``SharedPodServer`` and drained; each
     tenant's logits are compared with the same step run alone.
With ``--four-chips`` it runs only a few training steps of phi3-mini-3.8b
(published widths, 4 layers) on a 2x2 (data, model) mesh, and the same
steps on one chip, whose losses must agree.

Any failure exits non-zero. Only when every phase passed is the last line
of stdout ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The IPC and decision stores live in a fresh directory for the run, so
nothing outside what git tracks is read.
"""
import argparse
import functools
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "phi3-mini-3.8b"


def log(msg):
    print(msg, flush=True)


def device_check(jax, count):
    if jax.default_backend() != "tpu":
        sys.exit(f"no TPU found: JAX backend is {jax.default_backend()!r}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")
    if count is not None and len(devs) != count:
        sys.exit(f"need {count} chips, found {len(devs)}")
    return device


def peak_bytes(jax, device=None):
    """Peak device memory since the process started."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compare(got, want, atol, rtol):
    import jax.numpy as jnp
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    err = jnp.abs(g - w)
    return (jnp.sum(~(err <= atol + rtol * jnp.abs(w))), jnp.max(err),
            jnp.all(jnp.isfinite(g)))


def close(name, got, want, atol, rtol):
    """Elementwise |got - want| <= atol + rtol |want|, one fused reduction
    on the device (no f32 copies of the operands); raises on any mismatch
    or non-finite value."""
    import jax
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    bad, max_err, finite = jax.jit(_compare)(got, want, atol, rtol)
    bad, finite = int(bad), bool(finite)
    log(f"  {name}: shape {tuple(got.shape)} max|err| {float(max_err):.3e}"
        f" mismatches {bad} finite {finite}")
    if bad or not finite:
        raise AssertionError(f"{name}: {bad} elements outside atol={atol} "
                             f"rtol={rtol}, finite={finite}")


def kernels(jax, seed):
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    log("phase kernels")
    bf16, f32 = jnp.bfloat16, jnp.float32
    bf16_tol = dict(atol=2e-2, rtol=2e-2)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, f32) * scale
                ).astype(dtype)

    # the fused-interleave bench shapes: an 8192^3 matmul beside a
    # 65536 x 8192 stream; a is scaled so outputs are O(1)
    a = rand((8192, 8192), bf16, 8192 ** -0.5)
    b = rand((8192, 8192), bf16)
    x = rand((65536, 8192), bf16)
    t0 = time.perf_counter()
    mm, st = jax.block_until_ready(ops.coschedule(a, b, x))
    log(f"  coschedule first call {time.perf_counter() - t0:.2f}s")
    mref, sref = ref.coschedule(a, b, x, 2.0)
    close("coschedule.matmul", mm, mref, **bf16_tol)
    close("coschedule.stream", st, sref, **bf16_tol)
    del a, b, x, mm, st, mref, sref

    a = rand((4096, 4096), bf16, 4096 ** -0.5)
    b = rand((4096, 4096), bf16)
    close("sliced_matmul", ops.sliced_matmul(a, b, slice_size=64),
          ref.matmul(a, b), **bf16_tol)

    with jax.default_matmul_precision("highest"):   # f32 references
        q, k, v = (rand((1, 32, 2048, 96), bf16) for _ in range(3))
        close("flash_attention(hd=96)", ops.flash_attention(q, k, v),
              ref.flash_attention(q, k, v), **bf16_tol)

        r, kk, vv = (rand((1, 2048, 32, 64), f32) for _ in range(3))
        w_log = -jnp.exp(rand((1, 2048, 32, 64), f32) - 1.0)
        u = rand((32, 64), f32, 0.1)
        close("rwkv6_scan", ops.rwkv6_scan(r, kk, vv, w_log, u),
              ref.rwkv6(r, kk, vv, w_log, u)[0], atol=1e-2, rtol=1e-2)

        xs = rand((1, 2048, 4096), f32)
        a_log = -jnp.exp(rand((1, 2048, 4096), f32))
        close("rg_lru", ops.rg_lru(xs, a_log), ref.rg_lru(xs, a_log),
              atol=1e-3, rtol=1e-3)
    log(f"  peak_bytes_in_use after kernels {peak_bytes(jax)}")


def serving(jax, seed):
    from repro.configs import get_config
    from repro.launch.serve import (Job, SharedPodServer, decode_logits,
                                    prefill_logits)
    log("phase serving")
    cfg = get_config(ARCH)
    log(f"  {ARCH}: d_model {cfg.d_model} layers {cfg.num_layers} "
        f"vocab {cfg.vocab_size} params {cfg.param_count() / 1e9:.2f}B")
    srv = SharedPodServer(seed=seed)
    t0 = time.perf_counter()
    srv.submit(Job("phi3-prefill", ARCH, "prefill", num_slices=4,
                   batch_per_slice=2, seq=512, published=True))
    srv.submit(Job("phi3-decode", ARCH, "decode", num_slices=4,
                   batch_per_slice=8, seq=512, published=True))
    log(f"  submit {time.perf_counter() - t0:.2f}s (weights init + compile);"
        f" compile s: " + json.dumps(
            {n: round(s, 3) for n, s in srv.compile_s.items()}))
    if srv._args["phi3-prefill"][0] is not srv._args["phi3-decode"][0]:
        raise AssertionError("tenants of one arch must share one weight copy")
    res = srv.drain()
    log(f"  drain wall {res['wall_s']:.3f}s rounds {len(res['rounds'])}: "
        + json.dumps(res["rounds"]))
    if any(j.num_slices for j in srv.jobs.values()):
        raise AssertionError("drain left slices pending")
    # the serial reference: each tenant's step run alone, outside the server
    solo = {"phi3-prefill": functools.partial(prefill_logits, cfg=cfg),
            "phi3-decode": functools.partial(decode_logits, cfg=cfg)}
    for name, step in solo.items():
        close(f"{name} logits vs serial", srv.outputs[name],
              jax.jit(step)(*srv._args[name]), atol=2e-2, rtol=2e-2)
    log(f"  peak_bytes_in_use after serving {peak_bytes(jax)}")


def four_chip_training(jax, seed):
    import numpy as np

    from repro.launch.train import train
    log("phase four-chip training")
    devs = jax.devices()
    kw = dict(use_reduced=False, num_layers=4, steps=3, batch=8, seq=128,
              ckpt_dir=None, seed=seed)
    t0 = time.perf_counter()
    mesh_run = train(ARCH, model_parallel=2, **kw)
    mesh_losses = mesh_run["losses"]
    log(f"  2x2 mesh: losses {mesh_losses} "
        f"({time.perf_counter() - t0:.2f}s)")
    leaf = mesh_run["params"]["stage0"]["sub0"]["mlp"]["wi"]
    log(f"  mlp.wi {leaf.shape} sharding {leaf.sharding.spec} over "
        f"{len(leaf.sharding.device_set)} devices")
    peaks = [peak_bytes(jax, d) or 0 for d in devs]
    log(f"  peak_bytes_in_use per device {peaks}")
    del mesh_run, leaf
    if min(peaks) < 0.5 * max(peaks):
        raise AssertionError(f"uneven device memory {peaks}: not every "
                             "chip holds a share")
    t0 = time.perf_counter()
    one_run = train(ARCH, devices=devs[:1], **kw)
    log(f"  1 chip: losses {one_run['losses']} "
        f"({time.perf_counter() - t0:.2f}s)")
    # bf16 weights, reductions in another order across shards
    np.testing.assert_allclose(mesh_losses, one_run["losses"], rtol=1e-2)
    log("  2x2 mesh losses match the one-chip losses")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh training comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache
    device = device_check(jax, 4 if args.four_chips else None)
    log(f"compile cache: {use_compile_cache()}")
    store = tempfile.mkdtemp(prefix="kernelet-smoke-stores-")
    os.environ["REPRO_IPC_CACHE"] = store
    try:
        if args.four_chips:
            four_chip_training(jax, args.seed)
        else:
            kernels(jax, args.seed)
            serving(jax, args.seed)
    finally:
        shutil.rmtree(store, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
