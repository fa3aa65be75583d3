"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> \
      --seeds 101-112 --control-seeds 3 --seconds 10 \
      [--published rms_norm_eps=1e-05]

In one process (set-up is long): a short run of the cell, exactly as
``run.py`` makes it, on each seed (the program's readings), then for the
first ``--control-seeds`` seeds the control's readings: the reference put
in the program's place in a lower precision (fp8 e4m3, and int8 for
comparison) on the same weights and every input set of each tenant's
pool, read against the float32 reference. ``--published`` also reads the
reference with the configuration's keys set to their published values
against the reference as the file states it: the size, in the same
units, of what the program departs from. Prints one JSON line. Not part
of a benchmark run.
"""
import argparse
import gc
import json

import run


def control_readings(jax, spec: dict, seed: int, published: dict) -> dict:
    import correct
    import reference
    s = run.Session(jax, spec["config"], spec["traffic"], seed,
                    on_chip=True)
    s.srv = None
    gc.collect()
    ref = reference.Dense(spec["config"])
    pub = reference.Dense({**spec["config"], **published}) if published \
        else None
    out = {}
    for name, t in s.tenants.items():
        reads = {}
        for inp in s.inputs[name]:
            if t["phase"] == "prefill":
                def logits(q, r=ref):
                    return r.prefill_logits(s.params, inp["tokens"], quant=q)
            else:
                def logits(q, r=ref):
                    return r.decode_logits(s.params, inp["caches"],
                                           inp["tokens"], inp["t"], quant=q)
            want = logits(None)
            got = {q: correct.widest_gap(logits(q), want)
                   for q in ("fp8", "int8")}
            if pub is not None:
                got["published"] = correct.widest_gap(want, logits(None, pub))
            for k, v in got.items():
                reads[k] = max(reads.get(k, v), v)
            del want
        out[name] = reads
    return out


def seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--published", action="append", default=[],
                    help="key=value: a published value the program departs "
                         "from")
    args = ap.parse_args()
    published = {k: json.loads(v) for k, _, v in
                 (p.partition("=") for p in args.published)}
    spec = run.resolve(args.workload)
    program, control = {}, {}
    for seed in args.seeds:
        out = run.run_cell(spec, seed, args.seconds, False)
        program[seed] = {"correct": out["correct"], "checks": out["checks"],
                         "memory_peak_bytes":
                             out["device"]["memory_peak_bytes"]}
        run.log(f"seed {seed}: {json.dumps(program[seed])}")
        gc.collect()
    with run.process_settings() as (jax, _):
        run.device_check(jax, spec["cell"]["chips"])
        for seed in args.seeds[:args.control_seeds]:
            control[seed] = control_readings(jax, spec, seed, published)
            run.log(f"control seed {seed}: {json.dumps(control[seed])}")
            gc.collect()
    print(json.dumps({"workload": args.workload, "program": program,
                      "control": control}), flush=True)


if __name__ == "__main__":
    main()
