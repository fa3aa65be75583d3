"""Record the small chip trace of the program's own spans that
``test_program_trace.py`` reads.

  python3 benchmarks/chip/tests/record_program_trace.py <out_dir>

On a TPU: a ``SharedPodServer`` with a prefill and a decode tenant of the
reduced phi3-mini-3.8b, warmed up by one drain; then, inside a ``window``
span, the jobs of ``ADMITS`` admitted under an ``admit`` span and drained
under a ``drain`` span. Writes the ``.xplane.pb`` under ``out_dir``.
"""
import pathlib
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3] / "src"))

ADMITS = [("prefill", 2), ("decode", 3), ("prefill", 1), ("decode", 2)]


def main(out_dir: str):
    from repro.launch.serve import Job, SharedPodServer
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU found")
    srv = SharedPodServer()
    srv.submit(Job("prefill", "phi3-mini-3.8b", "prefill", 1, 2, 64))
    srv.submit(Job("decode", "phi3-mini-3.8b", "decode", 1, 4, 64))
    srv.drain()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("admit"):
            for tenant, slices in ADMITS:
                srv.admit(tenant, slices)
        with jax.profiler.TraceAnnotation("drain"):
            res = srv.drain()
    jax.profiler.stop_trace()
    print(res["rounds"], [(j.tenant, j.slices) for j in res["jobs"]])


if __name__ == "__main__":
    main(sys.argv[1])
