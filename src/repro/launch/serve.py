"""Multi-tenant shared-pod serving — Kernelet as a first-class feature.

Tenants submit jobs (arch x phase); each job's step is sliced into
microbatch slices (the thread-block analogue). Every job gets a
two-resource profile (PUR = compute-roofline utilization, MUR =
memory-roofline utilization) derived from its compiled cost analysis; the
KerneletScheduler picks the complementary pair with max predicted CP and
the balanced slice ratio (Eq. 8), and the dispatcher interleaves their
slices on the shared mesh. On TPU the fused path is
``repro.kernels.coschedule``; on CPU the interleaved dispatch is executed
for correctness and the co-scheduling profit is reported from the
TPU-adapted Markov model.

Every admitted job keeps a record (``JobRecord``): when it was admitted,
when its first slice was enqueued, and when the wait that covered its
last slice returned; ``drain()`` returns the records of the jobs it
completed. The drain's stages are ``jax.profiler.TraceAnnotation`` spans
named ``kernelet.<stage>``, on the profiler's clock, so a trace says
which stage held the chip idle; with no profiler running a span costs
about a microsecond.

Scheduling runs on the workload engine (``repro.core.engine``): the server
first *plans* the drain as a simulated engine replay lane — yielding the
predicted makespan and warming the shared decision cache (persisted across
processes via ``REPRO_DECISION_CACHE``) — then dispatches real work with
the same shared scheduler, so every dispatch-loop decision is a cache hit.

  PYTHONPATH=src python -m repro.launch.serve --demo \
      [--device-kind "TPU v5 lite"]   # required when no TPU is attached
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import time
from typing import Callable, Deque, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig, get_config, reduced
from repro.core.engine import LaneSpec, WorkloadEngine, run_fleet
from repro.core.jobstore import (CANCELLED, FINISHED, PAUSED, QUEUED,
                                 RUNNING, JobStoreError, StaleLease)
from repro.core.markov import MarkovModel
from repro.core.profiles import (TPU_V5E, V5E, KernelProfile, device_peaks,
                                 tpu_profile_from_costs)
from repro.core.simulator import IPCTable
from repro.data.synthetic import make_batch, poisson_arrivals
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T


@dataclasses.dataclass
class Job:
    name: str
    arch: str
    phase: str                  # "prefill" | "decode" | "train"
    num_slices: int             # microbatch slices pending
    batch_per_slice: int = 2
    seq: int = 64
    published: bool = False     # the arch's published widths, else the
                                # reduced CPU-smoke config


@dataclasses.dataclass
class JobRecord:
    """One admitted job of a tenant, in ``time.perf_counter`` seconds:
    admitted at ``admitted_at``; its first slice enqueued at
    ``first_dispatch``; ``done`` when the wait that covered its last
    slice returned. ``left`` counts its slices not yet enqueued."""
    job_id: int
    tenant: str
    slices: int
    admitted_at: float
    first_dispatch: Optional[float] = None
    done: Optional[float] = None
    left: int = 0


def _span(stage: str, **args):
    """The profiler span ``kernelet.<stage>``, carrying ``args``."""
    return jax.profiler.TraceAnnotation(f"kernelet.{stage}", **args)


def _named(step: Callable, name: str) -> Callable:
    """``step`` under ``name``, which names its program on the device."""
    step.__name__ = name
    return step


def prefill_logits(params, batch, *, cfg):
    """One prefill slice: logits over the whole prompt."""
    return T.forward(params, cfg, batch)[0]


def decode_logits(params, caches, tok, t, *, cfg):
    """One decode slice: next-token logits at position ``t``."""
    return T.decode_step(params, cfg, caches, tok, t)[0]


class SharedPodServer:
    """Kernelet executor over a queue of tenant jobs.

    ``device_kind`` names the chip the scheduler plans for (a key of
    ``repro.core.profiles.DEVICE_PEAKS``). On a TPU it defaults to the
    attached chip; on any other backend it must be given."""

    def __init__(self, *, gpu_spec=TPU_V5E, seed: int = 0,
                 device_kind: Optional[str] = None):
        if device_kind is None:
            if jax.default_backend() != "tpu":
                raise ValueError(
                    f"no TPU attached (backend {jax.default_backend()!r}): "
                    f"name the device to plan for, e.g. "
                    f"device_kind={V5E!r}")
            device_kind = jax.devices()[0].device_kind
        device_peaks(device_kind)          # unknown kinds fail here
        use_compile_cache()
        self.device_kind = device_kind
        self.spec = gpu_spec
        self.model = MarkovModel(gpu_spec.virtual(), three_state=True)
        self.jobs: Dict[str, Job] = {}
        self.profiles: Dict[str, KernelProfile] = {}
        self._exec: Dict[str, Callable] = {}
        self._args: Dict[str, tuple] = {}
        self._weights: Dict[ModelConfig, dict] = {}
        self.outputs: Dict[str, jax.Array] = {}
        self.compile_s: Dict[str, float] = {}
        self.key = jax.random.PRNGKey(seed)
        # per tenant, the admitted jobs not yet completed, oldest first
        self._queue: Dict[str, Deque[JobRecord]] = {}
        self._next_job = 0
        self._drains = 0
        self._plan_truth: Optional[IPCTable] = None

    def weights(self, cfg: ModelConfig) -> dict:
        """The one weight copy every tenant of ``cfg`` shares, initialised
        on the device by a jitted init (no whole-model f32 copy)."""
        if cfg not in self._weights:
            init = jax.jit(T.init_params, static_argnums=0)
            self._weights[cfg] = init(cfg, self.key)
        return self._weights[cfg]

    # ---- job admission: build, profile, register ---- #
    def submit(self, job: Job):
        cfg = get_config(job.arch)
        if not job.published:
            cfg = reduced(cfg)
        params = self.weights(cfg)
        raw = make_batch(cfg, job.batch_per_slice, job.seq)
        if job.phase == "decode":
            step = _named(functools.partial(decode_logits, cfg=cfg),
                          "decode_step")
            args = (params,
                    T.init_decode_caches(cfg, job.batch_per_slice, job.seq),
                    jnp.asarray(raw["tokens"][:, 0]),
                    jnp.int32(job.seq // 2))
        else:
            step = _named(functools.partial(prefill_logits, cfg=cfg),
                          "prefill_step")
            args = (params, {k: jnp.asarray(v) for k, v in raw.items()
                             if k != "labels"})
        # weights, caches and inputs are arguments, never constants folded
        # into the program
        t0 = time.perf_counter()
        compiled = jax.jit(step).lower(*args).compile()
        self.compile_s[job.name] = time.perf_counter() - t0
        # profile at FULL scale: the tenant's real job is the full config
        # on the production pod; its analytic FLOPs/bytes give the PUR/MUR
        # the scheduler reasons about (reduced-config compiled costs would
        # be uniformly memory-bound and hide complementarity)
        from repro.configs import SHAPES
        from repro.core.costs import cell_cost
        full_cfg = get_config(job.arch)
        shape = SHAPES[{"prefill": "prefill_32k", "decode": "decode_32k",
                        "train": "train_4k"}[job.phase]]
        cost = cell_cost(full_cfg, shape)
        prof = tpu_profile_from_costs(
            job.name, cost["flops"], cost["hbm_bytes"],
            num_blocks=job.num_slices, device_kind=self.device_kind)
        # slice-level book-keeping: one block == one microbatch slice
        prof = dataclasses.replace(prof, insns_per_block=1000.0,
                                   num_blocks=job.num_slices)
        self.jobs[job.name] = job
        self.profiles[job.name] = prof
        self._exec[job.name] = compiled
        self._args[job.name] = args
        self._queue[job.name] = collections.deque()
        if job.num_slices > 0:
            self._record(job.name, job.num_slices)

    def admit(self, tenant: str, slices: int) -> int:
        """Add a job of ``slices`` slices to the submitted ``tenant``;
        returns its job id. Slices added to ``jobs[tenant].num_slices``
        directly are served alike but belong to no job record."""
        if tenant not in self._queue:
            raise KeyError(f"no submitted tenant {tenant!r}")
        if slices < 1:
            raise ValueError(f"a job needs a slice, got {slices}")
        self.jobs[tenant].num_slices += slices
        return self._record(tenant, slices)

    def _record(self, tenant: str, slices: int) -> int:
        job_id, self._next_job = self._next_job, self._next_job + 1
        self._queue[tenant].append(JobRecord(
            job_id, tenant, slices, time.perf_counter(), left=slices))
        return job_id

    # ---- engine-backed planning ---- #
    def plan(self, engine: WorkloadEngine, *, rounds: int = 1500) -> dict:
        """Simulated drain of the pending jobs as one engine replay lane:
        predicts the fleet-style makespan and — because the lane shares the
        engine's scheduler for this (spec, profiles, alphas) identity —
        pre-warms every drain decision the dispatcher is about to make."""
        order = [n for n, j in self.jobs.items() if j.num_slices > 0]
        if not order:
            return {"predicted_makespan_cycles": 0.0, "time_line": [],
                    "n_coschedules": 0}
        # one measurement table for the server's lifetime: entries are
        # keyed by profile content, so repeated drains re-simulate nothing
        if self._plan_truth is None:
            self._plan_truth = IPCTable(self.spec.virtual(), rounds=rounds,
                                        persist=False)
        lane = LaneSpec("KERNELET", self.profiles, order, self.spec,
                        self._plan_truth,
                        alpha_p=0.2, alpha_m=0.2, cp_margin=0.0)
        res = engine.run([lane])[0]
        return {"predicted_makespan_cycles": float(res.total_cycles),
                "time_line": res.time_line,
                "n_coschedules": res.n_coschedules}

    def plan_arrivals(self, engine: WorkloadEngine, rate: float, *,
                      seed: int = 0, slo_deadline: Optional[float] = None,
                      rounds: int = 1500,
                      policy: str = "KERNELET") -> dict:
        """Arrival-timed drain plan: instead of assuming every pending job
        is a known backlog, jobs land on a Poisson stream at ``rate``
        (events per simulated cycle) and the engine lane admits, truncates
        and fast-forwards accordingly — predicting per-job queue wait,
        tail latency, and SLO attainment at ``slo_deadline`` in addition
        to the makespan. Like ``plan``, the replay warms the shared
        decision cache for the real dispatcher. ``policy`` selects the
        planning policy — ``"EDF-KERNELET"`` plans a deadline-aware drain
        (instance deadlines at ``arrival + slo_deadline``) and
        ``"PWAIT-CP"`` a predicted-wait-weighted one."""
        order = [n for n, j in self.jobs.items() if j.num_slices > 0]
        if not order:
            return {"predicted_makespan_cycles": 0.0, "time_line": [],
                    "n_coschedules": 0, "latency": {}, "energy": {},
                    "completions": []}
        if self._plan_truth is None:
            self._plan_truth = IPCTable(self.spec.virtual(), rounds=rounds,
                                        persist=False)
        arrivals = poisson_arrivals(rate, len(order), seed=seed)
        lane = LaneSpec(policy, self.profiles, order, self.spec,
                        self._plan_truth, alpha_p=0.2, alpha_m=0.2,
                        cp_margin=0.0, arrivals=list(arrivals),
                        slo_deadline=slo_deadline)
        res = engine.run([lane])[0]
        return {"predicted_makespan_cycles": float(res.total_cycles),
                "time_line": res.time_line,
                "n_coschedules": res.n_coschedules,
                "policy": policy,
                "latency": dict(res.latency_metrics(slo_deadline)),
                "energy": dict(res.energy_metrics()),
                "completions": res.completions}

    def plan_fleet(self, n_pods: int, rate: float, *,
                   pod_specs=None, seed: int = 0,
                   slo_deadline: Optional[float] = None,
                   rounds: int = 1500, policy: str = "KERNELET",
                   deal="auto") -> dict:
        """Fleet-dealing plan: replays the pending jobs' Poisson stream
        over ``n_pods`` simulated pods through ``run_fleet``, dealing
        with ``deal`` (``"auto"`` = least-predicted-backlog under
        arrivals — see ``repro.core.engine.DealPolicy``). Returns the
        pooled latency prediction plus the per-pod split, so capacity
        planning can compare dealing policies before committing pods.

        ``pod_specs`` (one ``GPUSpec`` per pod) plans a *mixed-pod* fleet:
        pod g replays on ``pod_specs[g]`` with its own measurement table
        (one per distinct spec content — the server's plan table serves
        matching pods and templates the rest), and the load-aware deal
        weighs per-pod speed, so capacity planning can ask what adding a
        faster or slower pod generation buys before committing it."""
        order = [n for n, j in self.jobs.items() if j.num_slices > 0]
        if not order:
            return {"predicted_makespan_cycles": 0.0, "latency": {},
                    "energy": {}, "per_pod": [], "pods": [], "deal": None}
        if pod_specs is not None:
            pod_specs = list(pod_specs)
            if len(pod_specs) != n_pods:
                raise ValueError(f"n_pods={n_pods} but {len(pod_specs)} "
                                 "pod_specs given")
        if self._plan_truth is None:
            self._plan_truth = IPCTable(self.spec.virtual(), rounds=rounds,
                                        persist=False)
        arrivals = list(poisson_arrivals(rate, len(order), seed=seed))
        fleet = run_fleet(policy, self.profiles, order, self.spec,
                          self._plan_truth, n_pods, alpha_p=0.2,
                          alpha_m=0.2, cp_margin=0.0, arrivals=arrivals,
                          slo_deadline=slo_deadline, deal=deal,
                          gpus=pod_specs)
        return {"predicted_makespan_cycles": float(fleet.makespan),
                "latency": dict(fleet.latency),
                "energy": dict(fleet.energy),
                "per_pod": [[n for n, _, _ in lane.completions]
                            for lane in fleet.lanes],
                "pods": [s.name for s in fleet.gpus],
                "deal": fleet.deal,
                "policy": policy}

    # ---- daemon-backed drain control ---- #
    def _register_drain_job(self, daemon, job_name: str,
                            plan_policy: str):
        """Register this drain as an ``external`` job in the daemon's
        durable store and take its lease — the single-writer
        ``queued → running`` gate, so the dispatch below is cancellable,
        pausable and visible exactly like a daemon-drained lane (fleet
        pods never steal it: ``serve_once`` skips external specs). A
        previously paused drain re-acquires from ``paused`` and resumes
        the remaining slices."""
        pending = {n: j.num_slices for n, j in self.jobs.items()
                   if j.num_slices > 0}
        st = daemon.store.state(job_name)
        if st is None:
            daemon.submit(job_name, {
                "external": True, "kind": "serve-drain",
                "policy": plan_policy, "pending": pending})
            st = QUEUED
        epoch = daemon.store.acquire_lease(
            job_name, daemon.pod_id, daemon.lease_ttl,
            from_state=PAUSED if st == PAUSED else QUEUED,
            info=f"serve-drain dispatch ({len(pending)} tenants)")
        if epoch is None:
            raise RuntimeError(
                f"drain job {job_name!r} is not claimable "
                f"(state {daemon.store.state(job_name)!r})")
        return job_name, (daemon.pod_id, epoch)

    def _drain_control(self, daemon, job_id: str, fence,
                       round_idx: int) -> Optional[str]:
        """One round-boundary control check: honor pending cancel/pause
        requests, heartbeat the lease, checkpoint remaining slices.
        Returns the state the drain stopped in (``cancelled``,
        ``paused``, or ``"lost"`` when the lease was stolen), or None to
        keep dispatching."""
        pod_id, epoch = fence

        def ckpt():
            daemon.store.save_checkpoint(
                job_id, round_idx,
                {"pending": {n: j.num_slices
                             for n, j in self.jobs.items()
                             if j.num_slices > 0}},
                fence=fence)
        try:
            ctl = daemon.poll_control(job_id)
            st = daemon.store.state(job_id)
            if st != RUNNING:
                return st      # requeued/cancelled behind our back
            if ctl == "cancel":
                ckpt()
                daemon.store.transition(
                    job_id, CANCELLED,
                    f"cancelled at round {round_idx}", fence=fence)
                return CANCELLED
            if ctl == "pause":
                ckpt()
                daemon.store.transition(
                    job_id, PAUSED, f"paused at round {round_idx}",
                    fence=fence)
                return PAUSED
            daemon.store.renew_lease(job_id, pod_id, epoch,
                                     daemon.lease_ttl)
            ckpt()
        except StaleLease:
            return "lost"
        except JobStoreError:
            return None    # transient store trouble never stops work
        return None

    # ---- scheduling + interleaved dispatch ---- #
    def drain(self, *, max_rounds: int = 10000, plan_first: bool = True,
              arrival_rate: Optional[float] = None,
              slo_deadline: Optional[float] = None,
              plan_policy: str = "KERNELET", daemon=None,
              job_name: str = "serve-drain") -> dict:
        """Dispatch every pending job. ``arrival_rate`` switches the
        planning stage to the arrival-timed replay (``plan_arrivals``), so
        the returned plan carries predicted queue-wait/SLO metrics for the
        drain the dispatcher is about to execute; ``plan_policy`` selects
        the planning policy (e.g. ``"EDF-KERNELET"`` for a deadline-aware
        plan).

        Returns the ``rounds`` run (``(k1, k2, n1, n2, cp)``, ``k2`` None
        for a tenant run alone), ``wall_s`` from the first dispatch to the
        end, ``jobs``: the ``JobRecord`` of every job completed, each
        done, ``returned_at``: the drain's return, and the plan's coverage:
        ``planned_slices`` (the slices the plan simulated, 0 without a
        plan) of ``pending_slices`` (the slices pending at the start).

        ``daemon`` (a ``repro.runtime.daemon.ServingDaemon``) routes the
        drain through the durable job path: the dispatch runs under a
        lease-gated ``external`` job named ``job_name``, checkpoints its
        remaining slices every round, and honors ``daemon.cancel`` /
        ``daemon.pause`` at round boundaries — a paused drain keeps its
        undrained slices and a later ``drain(daemon=...)`` with the same
        ``job_name`` resumes it. The result gains ``job_id`` and
        ``state`` (``finished`` / ``cancelled`` / ``paused`` /
        ``"lost"`` if the lease was stolen)."""
        self._drains += 1
        with _span("drain", seq=self._drains):
            return self._drain(max_rounds, plan_first, arrival_rate,
                               slo_deadline, plan_policy, daemon, job_name)

    def _drain(self, max_rounds, plan_first, arrival_rate, slo_deadline,
               plan_policy, daemon, job_name) -> dict:
        # fail fast with a clear message, not a KeyError mid-dispatch: a
        # pending job must have completed submit() (profile + executable)
        missing = sorted(n for n, j in self.jobs.items() if j.num_slices > 0
                         and (n not in self._exec or n not in self.profiles))
        if missing:
            raise ValueError(
                f"pending jobs with no registered profile/executable: "
                f"{missing} — submit() must complete for every job "
                "before drain()")
        pending = {n: j.num_slices for n, j in self.jobs.items()
                   if j.num_slices > 0}
        # the plan replays each pending tenant's profile, whose num_blocks
        # is fixed at submit, whatever is pending now
        counts = {"planned_slices": sum(self.profiles[n].num_blocks
                                        for n in pending)
                  if plan_first else 0,
                  "pending_slices": sum(pending.values())}
        with _span("plan", planned=counts["planned_slices"],
                   pending=counts["pending_slices"]):
            engine = WorkloadEngine()
            sched = engine.scheduler_for(self.spec, self.profiles,
                                         alpha_p=0.2, alpha_m=0.2,
                                         cp_margin=0.0)
            plan = None
            if plan_first:
                plan = (self.plan_arrivals(engine, arrival_rate,
                                           slo_deadline=slo_deadline,
                                           policy=plan_policy)
                        if arrival_rate is not None else self.plan(engine))
        jid = fence = None
        if daemon is not None:
            jid, fence = self._register_drain_job(daemon, job_name,
                                                  plan_policy)
        t0 = time.perf_counter()
        executed, completed = [], []

        def result(**extra) -> dict:
            end = time.perf_counter()
            return {"rounds": executed, "wall_s": end - t0,
                    "predicted_gain": self._predicted_gain(executed),
                    "plan": plan, "jobs": completed, "returned_at": end,
                    **counts, **extra}

        while any(j.num_slices > 0 for j in self.jobs.values()):
            with _span("round", index=len(executed)):
                if daemon is not None:
                    with _span("control"):
                        stopped = self._drain_control(daemon, jid, fence,
                                                      len(executed))
                    if stopped is not None:
                        return result(job_id=jid, state=stopped)
                act = [n for n, j in self.jobs.items() if j.num_slices > 0]
                with _span("decide"):
                    cs = sched.find_coschedule(act)
                if cs.k2 is None:
                    n_run = min(self.jobs[cs.k1].num_slices, 8)
                    for _ in range(n_run):
                        with _span("dispatch"):
                            out, last = self._dispatch(cs.k1)
                        with _span("block"):
                            self.outputs[cs.k1] = out.block_until_ready()
                        self._complete([last], completed)
                    self.jobs[cs.k1].num_slices -= n_run
                    executed.append((cs.k1, None, n_run, 0, 0.0))
                    continue
                # balanced interleave: enqueue s1:s2 slices per round, async
                r1 = max(1, round(cs.s1 / self.spec.n_sm))
                r2 = max(1, round(cs.s2 / self.spec.n_sm))
                j1, j2 = self.jobs[cs.k1], self.jobs[cs.k2]
                outs, lasts = [], []
                n1 = min(r1, j1.num_slices)
                n2 = min(r2, j2.num_slices)
                with _span("dispatch"):
                    for i in range(max(n1, n2)):
                        for name, n in ((cs.k1, n1), (cs.k2, n2)):
                            if i < n:
                                out, last = self._dispatch(name)
                                outs.append((name, out))
                                lasts.append(last)
                with _span("block"):
                    for name, o in outs:
                        self.outputs[name] = o.block_until_ready()
                self._complete(lasts, completed)
                j1.num_slices -= n1
                j2.num_slices -= n2
                executed.append((cs.k1, cs.k2, n1, n2, cs.cp))
                if len(executed) > max_rounds:
                    raise RuntimeError("scheduler did not drain")
        out = result()
        if daemon is not None:
            out["job_id"] = jid
            try:
                daemon.store.transition(
                    jid, FINISHED, "drained",
                    result={"rounds": len(executed), "wall_s": out["wall_s"],
                            "predicted_gain": out["predicted_gain"]},
                    fence=fence)
                out["state"] = FINISHED
            except StaleLease:
                out["state"] = "lost"
            out["returned_at"] = time.perf_counter()
        return out

    def _dispatch(self, name: str):
        """Enqueue one slice of tenant ``name`` for the oldest of its
        jobs, under the span ``kernelet.slice``. Returns the output and
        that job's record if this was its last slice, else None."""
        queue = self._queue.get(name)
        job = queue[0] if queue else None
        ids = {} if job is None else {"job_id": job.job_id}
        with _span("slice", tenant=name, **ids):
            if job is not None and job.first_dispatch is None:
                job.first_dispatch = time.perf_counter()
            out = self._run(name)
        if job is None:
            return out, None
        job.left -= 1
        if job.left:
            return out, None
        return out, queue.popleft()

    @staticmethod
    def _complete(lasts, completed: list):
        """The wait covering these slices returned: their jobs are done."""
        now = time.perf_counter()
        for job in lasts:
            if job is not None:
                job.done = now
                completed.append(job)

    def _run(self, name: str):
        return self._exec[name](*self._args[name])

    def _predicted_gain(self, executed) -> float:
        """Aggregate modeled co-scheduling profit over executed rounds."""
        cps, weights = [], []
        for k1, k2, n1, n2, cp in executed:
            if k2 is not None:
                cps.append(cp)
                weights.append(n1 + n2)
        if not cps:
            return 0.0
        return float(np.average(cps, weights=weights))


def demo(device_kind: Optional[str] = None):
    server = SharedPodServer(device_kind=device_kind)
    server.submit(Job("tenantA-phi3-prefill", "phi3-mini-3.8b", "prefill", 24))
    server.submit(Job("tenantB-dsv2-decode", "deepseek-v2-236b", "decode", 24))
    server.submit(Job("tenantC-rwkv-prefill", "rwkv6-1.6b", "prefill", 16))
    server.submit(Job("tenantD-sc2-decode", "starcoder2-15b", "decode", 16))
    for name, prof in server.profiles.items():
        print("submitted", name,
              f"PUR={prof.pur:.2f} MUR={prof.mur:.2f} R_m={prof.rm:.2f}")
    res = server.drain()
    if res["plan"]:
        print(f"engine plan: predicted makespan "
              f"{res['plan']['predicted_makespan_cycles']:.0f} cycles over "
              f"{len(res['plan']['time_line'])} phases "
              f"({res['plan']['n_coschedules']} co-scheduled)")
    for k1, k2, n1, n2, cp in res["rounds"]:
        print(f"co-schedule {k1} x {k2}: slices {n1}:{n2}  "
              f"predicted CP={cp:+.3f}")
    print(f"drained in {res['wall_s']:.1f}s; "
          f"mean predicted co-scheduling profit {res['predicted_gain']:+.1%}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--device-kind", default=None,
                    help="chip to plan for (default: the attached TPU; "
                         f"required off-TPU, e.g. {V5E!r})")
    demo(ap.parse_args().device_kind)
