"""Records the small served trace ``test_program_spans.py`` reads: 0.15 s
of the 10-qubit QAOA cell of ``tiny.py`` on a TPU chip, with the
service's dispatch-loop spans. Python function tracing and the runtime's
own host events are off, and the file is gzipped: the HLO text of the
op events alone takes over a megabyte raw (a whole second holds some
30,000 device op events, 5 MB raw). The reduction reads only the device
ops and the named host spans.

    python3 benchmark/tests/record_served_trace.py <output .xplane.pb.gz>
"""

import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, REPO)

SECONDS = 0.15


def main(out: str) -> int:
    import jax
    from benchmark import run as bench_run, trace_reduce
    from benchmark.registry import Registry
    from benchmark.tests import tiny
    tmp = tempfile.mkdtemp()
    try:
        registry = Registry(tiny.make_root(tmp))
        bench_run.enable_cache(jax, registry.root)
        workload = registry.workload("qaoa-tiny")
        devices = bench_run.check_devices(jax, 1)
        if devices is None:
            return bench_run.NO_CHIP
        ns = bench_run.parse(["--workload", "qaoa-tiny", "--seed", "5",
                              "--seconds", str(SECONDS)])
        run = bench_run.Run(registry, workload, ns, devices)
        driver = registry.driver(run.traffic["kind"]).Driver(run)
        driver.setup()
        logdir = os.path.join(tmp, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=options)
        with run.span("window"):
            driver.window(SECONDS)
        jax.profiler.stop_trace()
        driver.release()
        with open(trace_reduce.find_xplane(logdir), "rb") as raw, \
                gzip.open(out, "wb") as packed:
            shutil.copyfileobj(raw, packed)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
