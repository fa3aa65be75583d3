"""Chip benchmark of Kernelet's shared-pod serving path.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

One run, in one process that holds the chip: check the chip; build one
``SharedPodServer``; load the benchmark's weights (made on the device from
the seed) and submit the cell's tenants at published widths; warm up with
one drain of one job per tenant; drive the cell's traffic through
``drain()`` for ``--seconds``; compare what the window served with the
plain float32 reference; print one JSON line.

Everything about a cell is data found by name: ``BENCHMARK.json`` at the
checkout's root names the cell's configuration (``configs/<name>.json``)
and traffic mix (``traffic/<name>.json``); the configuration names its
equations (``equations/<name>.py``: the program check, the plain
reference, the work of a step and of each kernel); the mix names its loop
kind (``loops/<kind>.py``); each per-layer metric is read by
``metrics/<name>.py``. A new architecture is new files and entries.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` they are its per-layer metrics, read from a profiler trace
of the window's first ``TRACE_MAX_S`` seconds and from the run's record.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import peaks as peaks_mod  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import work as work_mod  # noqa: E402

TRACE_MAX_S = 10.0       # the traced part of a --trace 1 window
POOL = {"prefill": 4, "decode": 64}  # input sets a tenant's slices cycle
COVER = 256              # served positions compared per tenant and round kind
PLAN_FOR = "TPU v5 lite"  # chip the scheduler plans for off-TPU (tests)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, as a module."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def equations(config: dict):
    """The equations module the configuration file names under
    ``program.equations``. There is no default: a dense count or
    reference silently applied to another architecture would read work
    that no program does."""
    name = config.get("program", {}).get("equations")
    if not name:
        raise RuntimeError(
            f"configuration {config.get('name')!r} names no equations "
            f"module: set \"program\": {{\"equations\": <name>}} to a file "
            f"equations/<name>.py under {HERE}")
    return load_plugin("equations", name)


def resolve(workload: str) -> dict:
    """The cell, its configuration, traffic mix and metrics, by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell,
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


# --------------------------------------------------------------------- #
# the chip
# --------------------------------------------------------------------- #
def device_check(jax, chips: int) -> dict:
    """Fail unless JAX's backend is a TPU in the peaks table with at least
    ``chips`` chips."""
    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU found: JAX backend is "
                         f"{jax.default_backend()!r}")
    devs = jax.devices()
    peaks_mod.peaks_for(devs[0].device_kind)      # unknown kinds fail
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCount:
    """JAX's compile requests (each one a look into the persistent cache)
    and how many of them the cache served, from ``jax.monitoring``."""
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.requests = self.hits = 0

    def __call__(self, event: str, **_):
        self.requests += event == self.REQUEST
        self.hits += event == self.HIT


def peak_bytes(jax, chips: int):
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:chips]]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


# --------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------- #
class Session:
    """One server with the cell's tenants submitted and warmed up, and the
    benchmark's weights and inputs it serves."""

    def __init__(self, jax, config: dict, traffic: dict, seed: int, *,
                 on_chip: bool):
        import weights
        from repro.configs import get_config, reduced
        from repro.launch.serve import Job, SharedPodServer
        from repro.models import transformer as T

        self.eq = equations(config)
        prog = config["program"]
        cfg = get_config(prog["arch"])
        if not prog["published"]:
            cfg = reduced(cfg)
        self.eq.check(cfg, config)
        self.config = config
        self.tenants = {t["name"]: t for t in traffic["tenants"]}
        self.srv = SharedPodServer(
            seed=seed % 2**31, device_kind=None if on_chip else PLAN_FOR)
        # the benchmark's weights, in the program's layout, as its one
        # shared weight copy for this configuration
        shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        self.params = weights.make_params(
            shapes, config["initializer_range"], weights.base_key(seed, 0))
        jax.block_until_ready(self.params)
        self.srv._weights[cfg] = self.params
        t1 = time.perf_counter()
        self.inputs, self.pools = {}, {}
        for i, (name, t) in enumerate(self.tenants.items()):
            self.srv.submit(Job(name, prog["arch"], t["phase"], num_slices=1,
                                batch_per_slice=t["batch"], seq=t["seq"],
                                published=prog["published"]))
            self._own_inputs(jax, weights, name, t, seed, 1 + i)
        if any(self.srv._args[n][0] is not self.params for n in self.tenants):
            raise RuntimeError("a tenant is not served the shared weights")
        t2 = time.perf_counter()
        self.srv.drain()                         # warm-up: one job each
        log(f"set-up: weights {t1 - t0:.3f}s, submit and inputs "
            f"{t2 - t1:.3f}s (compile " + json.dumps(
                {n: round(c, 3) for n, c in self.srv.compile_s.items()})
            + f"), warm-up drain {time.perf_counter() - t2:.3f}s")
        self.work = {n: self.eq.step(config, t["phase"], t["batch"],
                                     t["seq"])
                     for n, t in self.tenants.items()}
        self.kernels = {n: self.eq.kernels(config, t["phase"], t["batch"],
                                           t["seq"])
                        for n, t in self.tenants.items()}

    def _own_inputs(self, jax, weights, name, tenant, seed, stream):
        """Replace the inputs ``submit`` made (the same for every seed) by
        a pool of ``POOL[phase]`` input sets of the same shapes, drawn from
        the seed: the window's slices cycle through it, so no two slices
        in a row compute the same thing. A decode tenant's sets share one
        cache and position and differ in their tokens."""
        args = self.srv._args[name]
        key = weights.base_key(seed, stream)
        k_tok, k_cache = jax.random.split(key)
        vocab = self.config["vocab_size"]
        n = POOL[tenant["phase"]]
        if tenant["phase"] == "prefill":
            if set(args[1]) != {"tokens"}:
                raise RuntimeError(f"unexpected prefill inputs {set(args[1])}")
            shape = args[1]["tokens"].shape
            toks = list(weights.make_tokens((n, *shape), vocab, k_tok))
            self.pools[name] = [(args[0], {"tokens": x}) for x in toks]
            self.inputs[name] = [{"tokens": x} for x in toks]
        else:
            params, caches, tok, t = args
            if int(t) != tenant["seq"] // 2:
                raise RuntimeError(f"decode position {int(t)} is not "
                                   "seq // 2")
            shapes = jax.eval_shape(lambda c: c, caches)
            self.srv._args[name] = None
            del args, caches                     # free the program's cache
            caches = weights.make_caches(shapes, k_cache)
            toks = list(weights.make_tokens((n, *tok.shape), vocab, k_tok))
            self.pools[name] = [(params, caches, x, t) for x in toks]
            self.inputs[name] = [{"caches": caches, "tokens": x, "t": int(t)}
                                 for x in toks]
        self.srv._args[name] = self.pools[name][0]


# --------------------------------------------------------------------- #
# the measured window
# --------------------------------------------------------------------- #
class Job:
    """One job: ``slices`` slices of a tenant, ``tokens`` tokens of work,
    due (open loop) or admitted (closed loop) at ``due``, completed at
    ``done``."""
    __slots__ = ("tenant", "slices", "tokens", "due", "done")

    def __init__(self, tenant, slices, tokens, due):
        self.tenant, self.slices, self.tokens = tenant, slices, tokens
        self.due, self.done = due, None


class Window:
    """What a loop drives: admit jobs, drain, wait. Times are
    ``time.perf_counter`` seconds; ``start`` is the window's start.

    Every tenant's step is wrapped where the drain calls it: the wrapper
    counts the slices the step really runs, moves the tenant on to the
    next input set of its pool, names the dispatch for the trace, and
    keeps the outputs of the slices sampled for the comparison."""

    def __init__(self, session: Session, seconds: float, seed: int,
                 jax, trace_dir=None):
        self.s = session
        self.seconds = seconds
        self.jax = jax
        self.trace_dir = trace_dir
        self.jobs, self.pending, self.drains = [], [], []
        self._rng = traffic_mod.rng_for(seed, "sample")
        self.kept = {}                  # (tenant, paired) -> [(key, pool, out)]
        self.ran = dict.fromkeys(session.tenants, 0)   # slices the step ran
        self.admitted = dict.fromkeys(session.tenants, 0)
        self._turn = dict.fromkeys(session.tenants, 0)
        self._count, self._pick, self._held = {}, {}, []
        self.dispatch = []              # tenant of each slice, traced part
        self.traced_drains = 0
        self.start = self.deadline = self.end = None
        self._window_span = None
        execs = session.srv._exec
        for name in session.tenants:
            execs[name] = self._slice_call(name, execs[name])

    def now(self) -> float:
        return time.perf_counter()

    def _span(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def _slice_call(self, name, fn):
        pool = self.s.pools[name]

        def call(*args):
            out = fn(*args)
            turn, n = self._turn[name], self._count.get(name, 0)
            if self._window_span is not None:
                self.dispatch.append(name)
            if n in self._pick.get(name, ()):
                self._held.append((name, n, turn % len(pool), out))
            self._count[name] = n + 1
            self._turn[name] = turn + 1
            self.ran[name] += 1
            self.s.srv._args[name] = pool[(turn + 1) % len(pool)]
            return out
        return call

    def admit(self, tenant: str, slices: int, due=None) -> Job:
        with self._span("admit"):
            t = self.s.tenants[tenant]
            per = t["batch"] * (t["seq"] if t["phase"] == "prefill" else 1)
            job = Job(tenant, slices, slices * per,
                      self.now() if due is None else due)
            self.s.srv.jobs[tenant].num_slices += slices
            self.admitted[tenant] += slices
            self.jobs.append(job)
            self.pending.append(job)
            return job

    def drain(self) -> list:
        jobs = self.s.srv.jobs
        self._count, self._held = {}, []
        self._pick = {n: set(self._rng.choice(
            jobs[n].num_slices, min(cap, jobs[n].num_slices),
            replace=False).tolist())
            for n, cap in self._caps().items() if jobs[n].num_slices}
        with self._span("drain"):
            t0 = self.now()
            res = self.s.srv.drain()
            t1 = self.now()
        done, self.pending = self.pending, []
        for job in done:
            job.done = t1
        self.drains.append({"host_s": t1 - t0, "wall_s": res["wall_s"],
                            "rounds": res["rounds"],
                            "traced": self._window_span is not None})
        self._keep(res["rounds"])
        if self._window_span is not None and t1 - self.start >= TRACE_MAX_S:
            self._stop_trace()
        return done

    def wait_until(self, t: float):
        with self._span("wait_arrival"):
            delay = t - self.now()
            if delay > 0:
                time.sleep(delay)

    # -- the sample the comparison reads --------------------------------
    def _caps(self) -> dict:
        """Outputs kept per tenant and round kind: enough to cover
        ``COVER`` served positions."""
        caps = {}
        for name, t in self.s.tenants.items():
            per = t["batch"] * (t["seq"] if t["phase"] == "prefill" else 1)
            caps[name] = -(-COVER // per)
        return caps

    def _keep(self, rounds):
        """Sort the drain's held outputs by the kind of round their slice
        ran in (paired with another tenant, or alone), then keep a seeded
        bottom-k sample of each tenant's outputs of each kind."""
        paired = {}
        for k1, k2, n1, n2, _cp in rounds:
            for name, n in ((k1, n1), (k2, n2)):
                if name is not None:
                    paired.setdefault(name, []).extend([k2 is not None] * n)
        caps = self._caps()
        for name, n, pool, out in self._held:
            kind = paired.get(name, [])
            if n >= len(kind):          # more slices run than the drain says
                continue
            kept = self.kept.setdefault((name, kind[n]), [])
            kept.append((float(self._rng.random()), pool, out))
            kept.sort(key=lambda e: e[0])
            del kept[caps[name]:]
        self._held = []

    # -- tracing --------------------------------------------------------
    def _start_trace(self):
        self.jax.profiler.start_trace(self.trace_dir)
        self._window_span = self._span("window")
        self._window_span.__enter__()

    def _stop_trace(self):
        self._window_span.__exit__(None, None, None)
        self._window_span = None
        self.traced_drains = sum(d["traced"] for d in self.drains)
        self.jax.profiler.stop_trace()

    def run(self, loop, traffic: dict, seed: int):
        if self.trace_dir is not None:
            self._start_trace()
        self.start = self.now()
        self.deadline = self.start + self.seconds
        loop.drive(self, traffic, seed)
        self.end = self.now()
        if self.pending:
            raise RuntimeError(f"{len(self.pending)} jobs left undrained")
        if self._window_span is not None:
            self._stop_trace()


# --------------------------------------------------------------------- #
# results
# --------------------------------------------------------------------- #
def end_to_end(win: Window, setup_s: float) -> dict:
    done = [j for j in win.jobs if j.done is not None]
    window_s = win.end - win.start
    lat_ms = np.array([(j.done - j.due) * 1e3 for j in done])
    vals = {"tokens_per_s": sum(j.tokens for j in done) / window_s,
            "job_p50_ms": float(np.percentile(lat_ms, 50)),
            "job_p95_ms": float(np.percentile(lat_ms, 95)),
            "setup_s": setup_s}
    n_beyond = int(np.sum(lat_ms > vals["job_p95_ms"]))
    log(f"window {window_s:.3f}s: {len(done)} jobs completed "
        f"({n_beyond} beyond p95), {len(win.drains)} drains, "
        f"{sum(j.tokens for j in done)} tokens")
    return vals


class Record:
    """What a per-layer metric reads: the window's drains, the reduced
    trace, the required work of each tenant's step and of its kernels, and
    the chip's peaks."""

    def __init__(self, win: Window, trace: dict, peaks):
        self.drains = win.drains
        self.trace = trace
        self.peaks = peaks
        self.tenants = win.s.tenants
        self.work = win.s.work
        self.kernels = win.s.kernels
        self.per_phase = {
            t["phase"]: trace["modules"][t["phase"]]
            for t in self.tenants.values()
            if trace and t["phase"] in trace["modules"]}

    def per_call_s(self, phase: str):
        """Mean device time of one step program of ``phase``."""
        p = self.per_phase.get(phase)
        return p["device_s"] / p["count"] if p and p["count"] else None

    def _same(self, by_tenant: dict, phase: str):
        """The value of ``by_tenant`` that every tenant of ``phase``
        shares, or None."""
        vals = [by_tenant[n] for n, t in self.tenants.items()
                if t["phase"] == phase]
        if not vals or any(v != vals[0] for v in vals):
            return None
        return vals[0]

    def step_work(self, phase: str):
        """Required work of one step of ``phase``, if every tenant of that
        phase runs the same step."""
        return self._same(self.work, phase)

    def least_s(self, phase: str):
        """Least time of one step of ``phase`` at the table's peaks."""
        w = self.step_work(phase)
        return None if w is None else work_mod.least_time(w, self.peaks)

    def kernel_s(self, phase: str, stem: str):
        """Mean device time, per step of ``phase``, of the runs of the
        kernel whose trace name has the stem ``stem``; None where it never
        ran in the traced window."""
        p = self.per_phase.get(phase)
        k = (self.trace or {}).get("kernels", {}).get(phase, {}).get(stem)
        if not p or not p["count"] or not k:
            return None
        return k["device_s"] / p["count"]

    def kernel_least_s(self, phase: str, stem: str):
        """Least time of that kernel's required work per step of
        ``phase`` (the configuration's ``equations.kernels``) at the
        table's peaks; None where the equations define none."""
        ks = self._same(self.kernels, phase)
        if not ks or stem not in ks:
            return None
        return work_mod.least_time(ks[stem], self.peaks)


def read_trace(trace_dir: str, dispatched: list) -> dict:
    """Reduce the window's trace. The program names every tenant step
    alike, so the k-th program run on the device is named by the phase
    of the k-th slice the harness saw dispatched (one device runs them in
    dispatch order). Where the counts differ the run fails: its step
    metrics would otherwise vanish unseen."""
    import trace_reduce
    path = trace_reduce.find_xplane(trace_dir)
    raw = trace_reduce.read(path)
    runs = trace_reduce.module_runs(raw)
    if len(runs) != len(dispatched):
        raise RuntimeError(
            f"trace: {len(runs)} program runs but {len(dispatched)} slices "
            "dispatched, so no run can be named by its step")
    red = trace_reduce.reduce(raw, run_labels=dispatched)
    red["xplane_bytes"] = os.path.getsize(path)
    return red


def correctness(jax, win: Window, session: Session, config: dict) -> dict:
    """Each tenant's widest logit gap over its sampled outputs, each read
    against the reference on the weights and input set it was served
    from, beside its limit; and the slices each tenant was admitted but
    its step never ran (limit 0)."""
    import correct
    ref = session.eq.Reference(config)
    checks = {}
    for name, t in session.tenants.items():
        by_pool = {}
        for paired in (False, True):
            for _key, pool, out in win.kept.get((name, paired), []):
                by_pool.setdefault(pool, []).append((paired, out))
        gap, counts = -1.0, {False: 0, True: 0}
        for pool, outs in sorted(by_pool.items()):
            inp = session.inputs[name][pool]
            if t["phase"] == "prefill":
                want = ref.prefill_logits(session.params, inp["tokens"])
            else:
                want = ref.decode_logits(session.params, inp["caches"],
                                         inp["tokens"], inp["t"])
            for paired, out in outs:
                gap = max(gap, correct.widest_gap(out, want))
                counts[paired] += 1
            del want
        if not by_pool:                 # nothing served: nothing is right
            gap = float("inf")
        per = t["batch"] * (t["seq"] if t["phase"] == "prefill" else 1)
        limit = config["limits"][f"{t['phase']}_max_logit_gap"]
        checks[f"{name}.max_logit_gap"] = {
            "value": gap, "limit": limit,
            "note": f"{counts[True]} paired and {counts[False]} lone "
                    f"slices on {len(by_pool)} input sets: "
                    f"{per * len(by_pool)} distinct positions"}
        checks[f"{name}.slices_not_run"] = {
            "value": win.admitted[name] - win.ran[name], "limit": 0,
            "note": f"{win.admitted[name]} admitted, {win.ran[name]} run"}
    return checks


@contextlib.contextmanager
def process_settings():
    """JAX's compile cache at the checkout's fixed path, the program's IPC
    and decision stores in a fresh directory (so every run plans alike);
    all undone on exit. Yields JAX and a ``CompileCount``."""
    cache = str(ROOT / "artifacts" / "jax_cache")
    stores = tempfile.mkdtemp(prefix="chipbench-stores-")
    env = {"JAX_COMPILATION_CACHE_DIR": cache, "REPRO_IPC_CACHE": stores,
           "TPU_LOG_DIR": "disabled"}      # libtpu would log under /tmp
    saved_env = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    conf = {"jax_compilation_cache_dir": cache,
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_compilation_cache_max_size": -1}
    saved_conf = {k: getattr(jax.config, k) for k in conf}
    for k, v in conf.items():
        jax.config.update(k, v)
    compiles = CompileCount()
    jax.monitoring.register_event_listener(compiles)
    try:
        yield jax, compiles
    finally:
        jax.monitoring.unregister_event_listener(compiles)
        for k, v in saved_conf.items():
            jax.config.update(k, v)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(stores, ignore_errors=True)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             on_chip: bool = True, fault=None, keep_trace=None) -> dict:
    """One run of a cell; returns the result line as a dict. ``fault``,
    if given, is called with the warmed-up ``Session`` before the window
    (tests break the timed path with it); ``on_chip=False`` runs on
    whatever JAX has (tests)."""
    with process_settings() as (jax, compiles):
        return _run_cell(jax, compiles, spec, seed, seconds, trace,
                         on_chip=on_chip, fault=fault, keep_trace=keep_trace)


def _run_cell(jax, compiles, spec, seed, seconds, trace, *, on_chip, fault,
              keep_trace):
    trace_dir = None
    log(f"set-up: process start to JAX imported "
        f"{time.perf_counter() - T_START:.3f}s")
    try:
        chips = spec["cell"]["chips"]
        if on_chip:
            device = device_check(jax, chips)
        else:
            d = jax.devices()[0]
            device = {"platform": d.platform, "kind": d.device_kind,
                      "count": 1}
        peaks = peaks_mod.peaks_for(device["kind"] if on_chip else PLAN_FOR)
        session = Session(jax, spec["config"], spec["traffic"], seed,
                          on_chip=on_chip)
        if fault is not None:
            fault(session)
        loop = load_plugin("loops", spec["traffic"]["loop"])
        if trace:
            trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-tr-")
        setup_s = time.perf_counter() - T_START
        log(f"set-up: {compiles.requests} compiles, {compiles.hits} "
            "of them from the cache")
        before = compiles.requests
        win = Window(session, seconds, seed, jax, trace_dir)
        win.run(loop, spec["traffic"], seed)
        log(f"window: {compiles.requests - before} compiles")
        attempted = len(win.jobs)
        failed = sum(j.done is None for j in win.jobs)
        device["memory_peak_bytes"] = peak_bytes(jax, chips)
        if trace:
            red = read_trace(trace_dir, [session.tenants[n]["phase"]
                                         for n in win.dispatch])
            metrics = {}
            rec = Record(win, red, peaks)
            for m in spec["per_layer"]:
                v = load_plugin("metrics", m["name"]).read(rec)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            log(f"trace: {red['xplane_bytes']} bytes, {win.traced_drains} "
                f"drains traced; idle by span " +
                json.dumps(red["idle_by_span"]))
        else:
            vals = end_to_end(win, setup_s)
            metrics = {m["name"]: {"value": vals[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"]}
            breakdown = None
        # the program's state goes before the reference runs
        session.srv = None
        win.s.srv = None
        gc.collect()
        t0 = time.perf_counter()
        checks = correctness(jax, win, session, spec["config"])
        log(f"reference and comparison {time.perf_counter() - t0:.3f}s")
    finally:
        if trace_dir is not None and keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ok = (failed == 0 and attempted > 0
          and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} ({c['note']})")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in")
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   keep_trace=args.keep_trace)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
