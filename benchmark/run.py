"""One run of one cell of ``BENCHMARK.json`` on TPU chips.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run loads the cell's configuration and traffic, sets up the program
(compiling, or loading from JAX's persistent cache, and warming every
shape the window uses), measures for ``--seconds``, and then checks what
the window produced against the configuration's plain reference. Its
last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
of the window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit. The same comparisons
are the last lines on standard error. An earlier line counts the
compiles of set-up and of the window.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory leads sys.path; its modules must not
# shadow the standard library's, so the package is imported from the root
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

from benchmark.compiles import CompileCounter  # noqa: E402
from benchmark.registry import Registry  # noqa: E402

NO_CHIP = 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


class Run:
    """What a driver is handed: the cell, its configuration and traffic,
    the program's package, the devices and the seed."""

    def __init__(self, registry, workload, args, devices):
        import jax
        import quest_tpu
        self.jax = jax
        self.qt = quest_tpu
        self.registry = registry
        self.workload = workload
        self.cfg = registry.config(workload["config"])
        self.traffic = registry.traffic(workload["traffic"])
        self.family = registry.family(self.cfg["family"])
        self.reference = registry.reference(self.cfg["family"])
        self.chips = int(workload["chips"])
        self.devices = devices[:self.chips]
        self.seed = args.seed
        self.seconds = args.seconds

    def span(self, name: str):
        """A host span on the profiler's clock (``bench.<name>``)."""
        return self.jax.profiler.TraceAnnotation(f"bench.{name}")


def enable_cache(jax, root: str) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache``: a
    fixed path inside the checkout (the path is part of the cache's key),
    with no size limit, so that only a cell's first run in a checkout
    compiles."""
    cache_dir = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # every program, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def check_devices(jax, chips: int):
    """The devices, or None (with the reason on standard error) when
    there is no TPU or too few chips."""
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"benchmark: JAX found no devices: {exc}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX reports {devices[0].platform!r})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    args = parse(argv)
    registry = Registry(ROOT)
    workload = registry.workload(args.workload)
    import jax
    devices = check_devices(jax, int(workload["chips"]))
    if devices is None:
        return NO_CHIP
    return run_cell(registry, workload, args, devices)


def run_cell(registry, workload, args, devices) -> int:
    """Set up, measure, check and print one run; returns the exit code."""
    import jax
    jax.config.update("jax_enable_x64", False)
    cache_dir = enable_cache(jax, registry.root)
    counter = CompileCounter()
    run = Run(registry, workload, args, devices)
    driver = registry.driver(run.traffic["kind"]).Driver(run)
    with run.span("setup"):
        driver.setup()
    setup_s = time.perf_counter() - T_START
    at_setup = counter.snapshot()

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        if logdir:
            jax.profiler.start_trace(logdir)
        try:
            with run.span("window"):
                e2e = driver.window(args.seconds)
        finally:
            if logdir:
                jax.profiler.stop_trace()
        window_compiles = counter.since(at_setup, counter.snapshot())
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in run.devices)
        driver.release()
        checks = driver.check()
        reduced = None
        if logdir:
            from benchmark import trace_reduce
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load(trace_reduce.find_xplane(logdir)))
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)

    emit({"compiles": {"setup": at_setup, "window": window_compiles,
                       "cache_dir": cache_dir}})
    for line in driver.notes():
        emit(line)
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak}
    name = workload["name"]
    metrics = {}
    if reduced is None:
        for m in registry.metrics("end_to_end", name):
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from benchmark.peaks import peaks
        ctx = {"workload": name, "trace": reduced,
               "peaks": peaks(run.devices[0].device_kind),
               "chips": run.chips, **driver.readings()}
        for m in registry.metrics("per_layer", name):
            value = registry.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    correct = driver.failed == 0 and all(c["value"] <= c["limit"]
                                         for c in checks)
    result = {"correct": correct, "attempted": driver.attempted,
              "failed": driver.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
