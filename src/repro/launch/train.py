"""End-to-end training driver.

Builds the model for ``--arch`` (full or reduced config), shards it on the
available mesh, and runs the resilient training loop (checkpoint/restart,
straggler-aware slicing hooks). Params and optimizer state are created on
the devices with ``param_shardings``, the batch is placed with
``batch_shardings``. On a CPU host use ``--reduced``.

  PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
      --reduced --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax

from repro.configs import ARCH_IDS, get_config, reduced
from repro.data.synthetic import SyntheticLoader
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import sharding as SH
from repro.models import transformer as T
from repro.optim import adamw
from repro.runtime.fault_tolerance import ResilientLoop


def build(arch: str, use_reduced: bool, opt_cfg=None):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    opt_cfg = opt_cfg or adamw.OptConfig()
    return cfg, opt_cfg


class _NoCheckpoints:
    """Checkpoint store that keeps nothing: a restart begins again from
    the initial state."""

    @staticmethod
    def save(directory, step, state):
        pass

    @staticmethod
    def latest_step(directory):
        return None


def train(arch: str = "phi3-mini-3.8b", *, use_reduced: bool = True,
          steps: int = 20, batch: int = 8, seq: int = 128,
          ckpt_dir: Optional[str] = "artifacts/ckpt",
          model_parallel: int = 1, seed: int = 0, fail_at=None,
          log_every: int = 5, compress_grads: bool = False,
          num_layers: Optional[int] = None,
          devices: Optional[Sequence] = None):
    """``num_layers`` cuts the depth of the config (widths stay);
    ``devices`` (default: all) spans the mesh; ``ckpt_dir=None`` trains
    without checkpoints."""
    use_compile_cache()
    cfg, opt_cfg = build(arch, use_reduced,
                         adamw.OptConfig(warmup_steps=10, total_steps=steps,
                                         compress_grads=compress_grads))
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    mesh = make_host_mesh(model_parallel, devices)
    with mesh, SH.use_mesh(mesh):
        key = jax.random.PRNGKey(seed)
        p_shard = SH.param_shardings(
            jax.eval_shape(functools.partial(T.init_params, cfg), key), mesh)
        params = jax.jit(T.init_params, static_argnums=0,
                         out_shardings=p_shard)(cfg, key)
        o_shard = SH.param_shardings(
            jax.eval_shape(functools.partial(adamw.init, opt_cfg), params),
            mesh)
        opt_state = jax.jit(adamw.init, static_argnums=0,
                            out_shardings=o_shard)(opt_cfg, params)
        loader = SyntheticLoader(cfg, batch, seq, seed=seed)
        b_shard = SH.batch_shardings(loader.load(0), mesh)
        step_fn_raw = jax.jit(make_train_step(cfg, opt_cfg),
                              in_shardings=(p_shard, o_shard, b_shard),
                              out_shardings=(p_shard, o_shard, None),
                              donate_argnums=(0, 1))

        history = []

        def step_fn(state, np_batch):
            params, opt_state = state
            jbatch = jax.device_put(np_batch, b_shard)
            params, opt_state, metrics = step_fn_raw(params, opt_state, jbatch)
            history.append(float(metrics["loss"]))
            return (params, opt_state), metrics

        loop = ResilientLoop(step_fn, (params, opt_state), loader,
                             ckpt_dir, ckpt_every=max(steps // 4, 5),
                             store=_NoCheckpoints if ckpt_dir is None
                             else None)
        t0 = time.time()
        (params, opt_state), end_step = loop.run(steps, fail_at=fail_at)
        dt = time.time() - t0
    return {"cfg": cfg, "params": params, "losses": history,
            "steps": end_step, "seconds": dt}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="phi3-mini-3.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    args = ap.parse_args()
    res = train(args.arch, use_reduced=args.reduced, steps=args.steps,
                batch=args.batch, seq=args.seq,
                model_parallel=args.model_parallel,
                ckpt_dir=args.ckpt_dir,
                compress_grads=args.compress_grads)
    losses = res["losses"]
    print(f"arch={args.arch} steps={res['steps']} "
          f"loss[0]={losses[0]:.3f} loss[-1]={losses[-1]:.3f} "
          f"({res['seconds']:.1f}s)")


if __name__ == "__main__":
    main()
