"""Readings that set a cell's limit: the program's gap and the control's
on many seeds, in one process.

    python3 benchmark/control.py --workload rcs28-circuit --seeds 1,2,3 --seconds 2

The cell is set up once. For each seed the run's window is made at the
cell's own size and load for ``--seconds``; then the number the run
compares is read twice: for the program (against the reference at
``highest``) and for the control (the reference at three bfloat16
passes put in the program's place). One JSON line per seed;
the last line gives the program's largest reading (the lower end of the
limit) and the control's smallest (the upper end).
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.registry import Registry  # noqa: E402


def readings(driver, seeds, seconds):
    """``(seed, program gap, control gap)`` for each seed."""
    out = []
    for seed in seeds:
        driver.reseed(seed)
        driver.window(seconds)
        driver.free_state()
        program = driver.compare()
        control = driver.control()
        out.append((seed, program, control))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    registry = Registry(ROOT)
    workload = registry.workload(args.workload)
    import jax
    devices = bench_run.check_devices(jax, int(workload["chips"]))
    if devices is None:
        return bench_run.NO_CHIP
    bench_run.enable_cache(jax, ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    ns = bench_run.parse(["--workload", args.workload, "--seed",
                          str(seeds[0]), "--seconds", str(args.seconds)])
    run = bench_run.Run(registry, workload, ns, devices)
    driver = registry.driver(run.traffic["kind"]).Driver(run)
    driver.setup()
    rows = readings(driver, seeds, args.seconds)
    for seed, program, control in rows:
        bench_run.emit({"seed": seed, "program": program,
                        "control": control, "failed": driver.failed})
    bench_run.emit({"workload": args.workload, "seeds": len(rows),
                    "program_max": max(r[1] for r in rows),
                    "control_min": min(r[2] for r in rows)})
    driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
