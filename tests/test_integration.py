"""Integration tests: dry-run machinery on a small mesh, collective parsing,
scheduler -> fused-kernel handoff, serving queue, analytic cost sanity."""
import dataclasses
import functools
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (DECODE_32K, SHAPES, TRAIN_4K, get_config, reduced,
                           applicable_shapes)
from repro.core.costs import cell_cost, model_flops_fwd
from repro.core.profiles import V5E
from repro.launch.dryrun import collective_bytes


def test_collective_parser():
    hlo = """
  %ag = bf16[16,128]{1,0} all-gather(bf16[1,128]{1,0} %x), replica_groups={}, metadata={op_name="jit(f)/while/body/foo"}
  %ar.1 = f32[64]{0} all-reduce(f32[64]{0} %y), to_apply=%add, metadata={op_name="jit(f)/bar"}
  %ags = (bf16[8,4]{1,0}, bf16[64,4]{1,0}) all-gather-start(bf16[8,4]{1,0} %z), metadata={op_name="jit(f)/while/body/while/body/baz"}
  %agd = bf16[64,4]{1,0} all-gather-done((bf16[8,4]{1,0}, bf16[64,4]{1,0}) %ags)
"""
    out = collective_bytes(hlo, trips=[10, 4])
    assert out["all-reduce"]["bytes"] == 64 * 4
    assert out["all-reduce"]["bytes_corrected"] == 64 * 4        # depth 0
    assert out["all-gather"]["count"] == 2                       # done not counted
    ag_plain = 16 * 128 * 2
    ag_start = (8 * 4 * 2 + 64 * 4 * 2) // 2
    assert out["all-gather"]["bytes"] == ag_plain + ag_start
    assert out["all-gather"]["bytes_corrected"] == \
        ag_plain * 10 + ag_start * 10 * 4                        # depths 1, 2


def test_analytic_costs_scale_sanely():
    cfg = get_config("phi3-mini-3.8b")
    c_train = cell_cost(cfg, TRAIN_4K)
    c_dec = cell_cost(cfg, DECODE_32K)
    # train impl flops within [3x, 8x] of MODEL_FLOPS (remat + attention)
    ratio = c_train["flops"] / c_train["model_flops"]
    assert 1.0 < ratio < 8.0, ratio
    # decode flops tiny vs train but dominated by params*batch
    assert c_dec["flops"] < c_train["flops"] / 100
    # MoE active-param accounting
    ds = get_config("deepseek-v2-236b")
    d_train = cell_cost(ds, TRAIN_4K)
    assert d_train["model_flops"] == 6.0 * ds.param_count(True) * TRAIN_4K.tokens


def test_absorbed_mla_cuts_decode_flops():
    ds = get_config("deepseek-v2-236b")
    absorbed = cell_cost(ds, DECODE_32K)["flops"]
    expand = cell_cost(dataclasses.replace(ds, mla_decode="expand"),
                       DECODE_32K)["flops"]
    assert expand / absorbed > 10, (expand, absorbed)


def test_dryrun_cell_on_host_mesh(tmp_path, monkeypatch):
    """The dry-run machinery end-to-end on the in-process device set (the
    512-device run is exercised by launch/dryrun.py itself)."""
    import repro.launch.dryrun as DR
    from repro.launch import specs as SP
    from repro.launch.steps import make_train_step
    from repro.models import sharding as SH

    cfg = reduced(get_config("stablelm-3b"))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with mesh, SH.use_mesh(mesh):
        args, shardings = SP.input_specs(cfg, shape, mesh)
        step = make_train_step(cfg, SP.default_opt_config(cfg))
        compiled = jax.jit(step, in_shardings=shardings,
                           donate_argnums=(0, 1)).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    colls = DR.collective_bytes(compiled.as_text(), trips=[cfg.num_layers])
    assert isinstance(colls, dict)


def test_long_500k_cells_exist_only_for_subquadratic():
    for arch in ("rwkv6-1.6b", "recurrentgemma-9b"):
        shapes = [s.name for s in applicable_shapes(get_config(arch))]
        assert "long_500k" in shapes
    for arch in ("phi3-mini-3.8b", "deepseek-v3-671b", "whisper-small"):
        shapes = [s.name for s in applicable_shapes(get_config(arch))]
        assert "long_500k" not in shapes


def test_scheduler_feeds_fused_kernel():
    """Kernelet's balanced slice ratio drives the fused Pallas interleave."""
    from repro.core.calibrate import calibrated_benchmarks
    from repro.core.markov import MarkovModel, balanced_slice_sizes
    from repro.core.profiles import C2050
    from repro.kernels import ops, ref

    profs = calibrated_benchmarks(C2050)
    model = MarkovModel(C2050.virtual())
    pc, tea = profs["PC"], profs["TEA"]
    c1, c2 = model.pair_ipc(pc, 2, tea, 2)
    s1, s2 = balanced_slice_sizes(pc, c1, tea, c2, 14, 14, 14)
    run_a = max(1, round(s1 / 14))
    run_b = max(1, round(s2 / 14))
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (512, 256), jnp.float32)
    mm, st = ops.coschedule(a, b, x, run_a=min(run_a, 8), run_b=min(run_b, 8))
    mref, sref = ref.coschedule(a, b, x, 2.0)
    np.testing.assert_allclose(np.asarray(mm), np.asarray(mref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sref), atol=1e-6)


def test_workload_replay_policies():
    """fig13-style workload replay end-to-end through the batched/cached
    measurement path: prefilled IPC table, memoized co-schedule search,
    reduced rounds so the whole replay takes seconds, not minutes."""
    from repro.core.calibrate import calibrated_benchmarks
    from repro.core.profiles import C2050, WORKLOADS
    from repro.core.queue import make_workload, run_policy
    from repro.core.simulator import IPCTable

    gpu = C2050
    profs = calibrated_benchmarks(gpu)
    truth = IPCTable(gpu.virtual(), rounds=4000, persist=False)
    truth.prefill(profs)                 # pre-execution: one batched sweep
    for wl in ("MIX", "ALL"):
        order = make_workload(profs, WORKLOADS[wl], instances=100)
        res = {pol: run_policy(pol, profs, order, gpu, truth)
               for pol in ("BASE", "KERNELET", "OPT")}
        base = res["BASE"].total_cycles
        knl = res["KERNELET"].total_cycles
        opt = res["OPT"].total_cycles
        assert res["KERNELET"].n_coschedules >= 1
        assert knl < base * 0.95, (wl, knl / base)   # co-scheduling pays
        assert knl < opt * 1.10, (wl, knl / opt)     # close to the oracle


def test_serving_queue_drains():
    from repro.launch.serve import Job, SharedPodServer
    srv = SharedPodServer(device_kind=V5E)
    srv.submit(Job("a-prefill", "phi3-mini-3.8b", "prefill", 6, 1, 32))
    srv.submit(Job("b-decode", "starcoder2-15b", "decode", 6, 1, 32))
    res = srv.drain()
    assert all(j.num_slices == 0 for j in srv.jobs.values())
    assert res["predicted_gain"] > 0.05      # complementary pair found


def test_serve_drain_through_daemon(tmp_path):
    """The planner-issued drain rides the durable job path: lease-gated
    external job, round-boundary checkpoints, pause at a round boundary
    with slices preserved, resume under a fresh fencing epoch, finish
    with a durable result — and fleet pods never steal it."""
    from repro.core.jobstore import CANCELLED, FINISHED, PAUSED
    from repro.launch.serve import Job, SharedPodServer
    from repro.runtime.daemon import ServingDaemon
    srv = SharedPodServer(device_kind=V5E)
    srv.submit(Job("a-prefill", "phi3-mini-3.8b", "prefill", 12, 1, 32))
    srv.submit(Job("b-decode", "starcoder2-15b", "decode", 12, 1, 32))
    dmn = ServingDaemon(str(tmp_path / "serve.sqlite"))
    calls = []
    orig = srv._exec["a-prefill"]

    def pause_after_first_slice(*args):
        calls.append(1)
        if len(calls) == 1:
            dmn.pause("serve-drain")
        return orig(*args)

    srv._exec["a-prefill"] = pause_after_first_slice
    res = srv.drain(daemon=dmn, plan_first=False)
    assert res["state"] == PAUSED
    assert res["job_id"] == "serve-drain"
    assert dmn.store.state("serve-drain") == PAUSED
    remaining = {n: j.num_slices for n, j in srv.jobs.items()}
    assert any(v > 0 for v in remaining.values())
    _, ck = dmn.store.load_checkpoint("serve-drain")
    assert ck["pending"] == {n: v for n, v in remaining.items() if v}
    assert dmn.serve_once() is None     # external: pods never claim it
    res2 = srv.drain(daemon=dmn, plan_first=False)   # resume remainder
    assert res2["state"] == FINISHED
    assert all(j.num_slices == 0 for j in srv.jobs.values())
    stored = dmn.store.result("serve-drain")
    assert stored["rounds"] == len(res2["rounds"])
    pod, epoch, _ = dmn.store.lease_of("serve-drain")
    assert (pod, epoch) == ("", 2)      # resumed under a fresh epoch
    # queued external jobs stay cancellable before dispatch starts
    dmn.submit("serve-drain-2", {"external": True})
    assert dmn.serve_once() is None
    dmn.cancel("serve-drain-2")
    assert dmn.store.state("serve-drain-2") == CANCELLED
    dmn.close()


def _two_phi3_tenants():
    from repro.launch.serve import Job, SharedPodServer
    srv = SharedPodServer(device_kind=V5E)
    srv.submit(Job("p", "phi3-mini-3.8b", "prefill", 3, 1, 32))
    srv.submit(Job("d", "phi3-mini-3.8b", "decode", 3, 2, 32))
    return srv


def test_tenants_of_one_arch_share_one_weight_copy():
    from repro.launch.serve import Job
    srv = _two_phi3_tenants()
    assert srv._args["p"][0] is srv._args["d"][0]
    srv.submit(Job("s", "starcoder2-15b", "decode", 1, 1, 32))
    assert srv._args["s"][0] is not srv._args["p"][0]
    assert len(srv._weights) == 2


def test_submitted_steps_take_weights_as_arguments():
    """No constant in a compiled tenant step is as large as a weight
    matrix: the weights reach the program as arguments, not folded-in
    literals."""
    import re
    srv = _two_phi3_tenants()
    smallest = reduced(get_config("phi3-mini-3.8b")).d_model ** 2
    for name in ("p", "d"):
        hlo = srv._exec[name].as_text()
        shapes = re.findall(r"\w+\[([\d,]*)\]\{[^}]*\} constant\(", hlo)
        consts = [np.prod([int(d) for d in dims.split(",") if d])
                  for dims in shapes]
        assert max(consts, default=0) < smallest, (name, max(consts))


def test_drain_outputs_match_each_step_run_alone():
    from repro.launch.serve import decode_logits, prefill_logits
    srv = _two_phi3_tenants()
    srv.drain(plan_first=False)
    assert all(j.num_slices == 0 for j in srv.jobs.values())
    cfg = reduced(get_config("phi3-mini-3.8b"))
    for name, step in (("p", prefill_logits), ("d", decode_logits)):
        want = jax.jit(functools.partial(step, cfg=cfg))(*srv._args[name])
        got = srv.outputs[name]
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)


def test_server_needs_a_known_planning_device():
    from repro.launch.serve import SharedPodServer
    with pytest.raises(ValueError, match="no TPU attached"):
        SharedPodServer()
    with pytest.raises(ValueError, match="no published peaks"):
        SharedPodServer(device_kind="TPU v0")


def test_compile_cache_location(tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the cache is one absolute path
    from any working directory; with it, compiles land there."""
    import subprocess
    import sys
    from repro.launch import compile_cache
    src = str(pathlib.Path(compile_cache.__file__).resolve().parents[2])
    code = ("import jax; from repro.launch.compile_cache import "
            "use_compile_cache as u; print(u()); "
            "jax.jit(lambda x: x + 1)(1.0).block_until_ready(); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=src, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def run(cwd, **extra):
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd,
                             env=dict(env, **extra), capture_output=True,
                             text=True, timeout=120, check=True)
        return out.stdout.split()

    default = compile_cache.DEFAULT_DIR
    assert os.path.isabs(default)
    assert default.endswith(os.path.join("artifacts", "jax_cache"))
    assert run(tmp_path) == run(src) == [default, default]
    mine = str(tmp_path / "cache")
    assert run(tmp_path, JAX_COMPILATION_CACHE_DIR=mine) == [mine, mine]
    assert os.listdir(mine)


def test_structural_collective_accounting():
    """Loop-aware accounting: trip counts from while-condition constants;
    hoisted (entry-level) ops counted once."""
    from repro.launch.dryrun import collective_bytes_structural
    hlo = """
HloModule jit_f, is_scheduled=true

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag.in = f32[128]{0} all-gather(f32[8]{0} %x), channel_id=1
  ROOT %t = (s32[], f32[8]) tuple(%i, %y)
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c), direction=LT
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %ag.out = f32[64]{0} all-gather(f32[8]{0} %a), channel_id=2
  %w = (s32[], f32[8]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
    out = collective_bytes_structural(hlo)
    assert out["all-gather"]["count"] == 2
    assert out["all-gather"]["bytes"] == 128 * 4 + 64 * 4
    # in-loop op x12 trips, entry op x1
    assert out["all-gather"]["bytes_corrected"] == 128 * 4 * 12 + 64 * 4
