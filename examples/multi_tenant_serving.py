"""Shared-pod multi-tenant serving with Kernelet slicing/co-scheduling.

Four tenants submit jobs with different compute/memory profiles; the
scheduler pairs complementary ones and interleaves their microbatch slices.
The drain runs on the workload engine (``repro.core.engine``): a simulated
replay lane first predicts the makespan and warms the shared decision
cache, then the dispatcher executes with every decision a cache hit.

  PYTHONPATH=src python examples/multi_tenant_serving.py                  # real dispatch (compiles with jax)
  PYTHONPATH=src python examples/multi_tenant_serving.py --fleet 4        # pure-simulation multi-pod replay (no jax)
  PYTHONPATH=src python examples/multi_tenant_serving.py --arrivals 1e-5  # arrival-timed replay: Poisson job
                                                                          # arrivals, queue-wait/SLO metrics (no jax)
  PYTHONPATH=src python examples/multi_tenant_serving.py \
      --pods v5e,v5e-2x --arrivals 1e-5                                   # mixed-pod fleet: per-pod GPUSpecs,
                                                                          # speed-aware least-backlog dealing
"""
import argparse
import dataclasses
import sys
import time


def _pod_spec(token: str):
    """Resolve a ``--pods`` token to a GPUSpec: ``v5e`` is the stock
    TPU v5e pod; ``v5e-<k>x`` a generation with k times the cores (e.g.
    ``v5e-2x``) — the mixed-pod capacity-planning knob."""
    from repro.core.profiles import TPU_V5E
    if token == "v5e":
        return TPU_V5E
    if token.startswith("v5e-") and token.endswith("x"):
        k = int(token[len("v5e-"):-1])
        if k < 1:
            raise ValueError(f"pod scale must be >= 1: {token!r}")
        return dataclasses.replace(TPU_V5E, name=f"TPUv5e-{k}x",
                                   n_sm=TPU_V5E.n_sm * k)
    raise ValueError(f"unknown pod spec {token!r}: expected 'v5e' or "
                     "'v5e-<k>x'")


def fleet_replay(n_pods: int, arrival_rate: float = 0.0,
                 policy: str = "KERNELET", deal: str = "auto",
                 pods: str = "") -> None:
    """Replay the demo tenant mix over a simulated fleet of shared pods —
    one engine batch, one measurement service, one decision cache. Builds
    the tenant profiles analytically (compiled cost analysis is not needed
    for the replay), so this path never imports jax.

    With ``arrival_rate`` > 0 the replay is arrival-timed: tenant jobs
    land on a Poisson stream at that rate (events per simulated cycle)
    instead of forming a known backlog, and the fleet result reports
    per-job queue wait and SLO attainment alongside the makespan.
    ``policy`` picks the per-pod schedule (``EDF-KERNELET`` / ``PWAIT-CP``
    are the arrival-aware family) and ``deal`` how the stream is split
    over pods (``auto`` = least-predicted-backlog under arrivals)."""
    from repro.configs import SHAPES, get_config
    from repro.core.costs import cell_cost
    from repro.core.engine import WorkloadEngine, run_fleet
    from repro.core.profiles import TPU_V5E, tpu_profile_from_costs
    from repro.core.simulator import IPCTable
    from repro.data.synthetic import poisson_arrivals

    tenants = [  # (name, arch, phase, slices) — the demo() mix
        ("tenantA-phi3-prefill", "phi3-mini-3.8b", "prefill", 24),
        ("tenantB-dsv2-decode", "deepseek-v2-236b", "decode", 24),
        ("tenantC-rwkv-prefill", "rwkv6-1.6b", "prefill", 16),
        ("tenantD-sc2-decode", "starcoder2-15b", "decode", 16),
    ]
    shape_of = {"prefill": "prefill_32k", "decode": "decode_32k",
                "train": "train_4k"}
    profiles = {}
    for name, arch, phase, slices in tenants:
        cost = cell_cost(get_config(arch), SHAPES[shape_of[phase]])
        prof = tpu_profile_from_costs(name, cost["flops"],
                                      cost["hbm_bytes"], num_blocks=slices)
        profiles[name] = dataclasses.replace(
            prof, insns_per_block=1000.0, num_blocks=slices)
    truth = IPCTable(TPU_V5E.virtual(), rounds=1500, persist=False)
    order = [name for name, *_ in tenants]
    pod_specs = None
    if pods:
        pod_specs = [_pod_spec(tok.strip()) for tok in pods.split(",")]
        n_pods = len(pod_specs)
    arrivals = None
    slo = None
    if arrival_rate > 0:
        arrivals = list(poisson_arrivals(arrival_rate, len(order), seed=0))
        slo = 2.0 / arrival_rate          # two mean interarrival gaps
    engine = WorkloadEngine()
    t0 = time.perf_counter()
    fleet = run_fleet(policy, profiles, order, TPU_V5E, truth, n_pods,
                      alpha_p=0.2, alpha_m=0.2, engine=engine,
                      arrivals=arrivals, slo_deadline=slo, deal=deal,
                      gpus=pod_specs)
    dt = time.perf_counter() - t0
    mix = ("" if pod_specs is None
           else " [" + ", ".join(s.name for s in fleet.gpus) + "]")
    print(f"fleet of {n_pods} pods{mix} ({policy}, {fleet.deal} dealing): "
          f"makespan {fleet.makespan:.0f} cycles, "
          f"{fleet.n_coschedules} co-schedules, replay took {dt * 1e3:.1f}ms")
    for g, lane in enumerate(fleet.lanes):
        events = ", ".join(ev for _, ev in lane.time_line)
        print(f"  pod{g} ({fleet.gpus[g].name}): "
              f"{lane.total_cycles:.0f} cycles  [{events}]")
    if fleet.latency is not None:
        lat = fleet.latency
        print(f"arrival-timed (rate={arrival_rate:g}/cycle): "
              f"wait p50 {lat['wait_p50']:.0f} / p95 {lat['wait_p95']:.0f} "
              f"cycles; SLO({lat['slo_deadline']:.0f}) attainment "
              f"{lat['slo_attainment']:.0%}")
        for name, arr, comp in sorted(
                (c for lane in fleet.lanes for c in lane.completions),
                key=lambda c: c[2]):
            print(f"  {name}: arrived {arr:.0f}, done {comp:.0f} "
                  f"(wait {comp - arr:.0f})")
    print(f"engine: {engine.stats['steps']} steps, "
          f"{engine.stats['pair_lookups']} pair + "
          f"{engine.stats['solo_lookups']} solo lookups batched, "
          f"{engine.stats['idle_ffwd']} idle fast-forwards")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet", type=int, default=0, metavar="N_PODS",
                    help="simulated multi-pod fleet replay instead of "
                         "real dispatch")
    ap.add_argument("--arrivals", type=float, default=0.0, metavar="RATE",
                    help="arrival-timed replay: tenant jobs land on a "
                         "Poisson stream at RATE events per simulated "
                         "cycle (implies --fleet 1 unless given)")
    ap.add_argument("--policy", default="KERNELET",
                    choices=["BASE", "KERNELET", "OPT", "MC",
                             "EDF-KERNELET", "PWAIT-CP"],
                    help="per-pod scheduling policy for the simulated "
                         "replay (EDF-KERNELET / PWAIT-CP are "
                         "arrival-aware)")
    ap.add_argument("--deal", default="auto",
                    choices=["auto", "round_robin", "least_backlog"],
                    help="fleet dealing policy (auto = least-predicted-"
                         "backlog under arrivals, round-robin otherwise)")
    ap.add_argument("--pods", default="", metavar="SPEC,SPEC,...",
                    help="mixed-pod fleet: comma-separated pod specs "
                         "('v5e' or 'v5e-<k>x', e.g. v5e,v5e-2x); "
                         "overrides --fleet's pod count")
    args = ap.parse_args()
    if args.fleet or args.arrivals or args.pods:
        fleet_replay(max(args.fleet, 1), arrival_rate=args.arrivals,
                     policy=args.policy, deal=args.deal, pods=args.pods)
        sys.exit(0)
    from repro.core.profiles import V5E
    from repro.launch.serve import demo
    demo(device_kind=V5E)      # plans for a v5e whatever device runs it
