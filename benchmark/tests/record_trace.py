"""Records the small trace ``test_trace_reduce.py`` reads: one second of
the 12-qubit random circuit cell of ``tiny.py`` on a TPU chip.

    python3 benchmark/tests/record_trace.py <output .xplane.pb>
"""

import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, REPO)


def main(out: str) -> int:
    import jax
    from benchmark import run as bench_run, trace_reduce
    from benchmark.registry import Registry
    from benchmark.tests import tiny
    tmp = tempfile.mkdtemp()
    try:
        registry = Registry(tiny.make_root(tmp))
        bench_run.enable_cache(jax, registry.root)
        workload = registry.workload("rcs-tiny")
        devices = bench_run.check_devices(jax, 1)
        if devices is None:
            return bench_run.NO_CHIP
        ns = bench_run.parse(["--workload", "rcs-tiny", "--seed", "5",
                              "--seconds", "0.05"])
        run = bench_run.Run(registry, workload, ns, devices)
        driver = registry.driver(run.traffic["kind"]).Driver(run)
        driver.setup()
        logdir = os.path.join(tmp, "trace")
        jax.profiler.start_trace(logdir)
        with run.span("window"):
            driver.window(0.05)
        jax.profiler.stop_trace()
        shutil.copy(trace_reduce.find_xplane(logdir), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
