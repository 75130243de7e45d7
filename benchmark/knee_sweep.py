"""Finds the knee of an open-loop cell: the highest offered rate whose
completed rate keeps up without a growing backlog.

    python3 benchmark/knee_sweep.py --workload qaoa18-serve --rates 50,100,200 --seconds 10 --seed 1

One process sets the cell up once, then offers each rate in turn for
``--seconds`` (the cell's own traffic, with its rate replaced) and prints
one JSON line per rate: offered and completed rates, latency percentiles,
how late the generator ran, and the mean latency of the last quarter of
requests over that of the first (a backlog that grows reads well above
1). A rate keeps up when no request fails, that ratio stays under 2,
and the completed rate (answers over the time to the later of the close
and the last answer, so it pays the drain) is at least 90% of the rate
the schedule offered (its Poisson count over ``--seconds``); the sweep stops at the first rate that
does not keep up. The last line gives the knee and four fifths of it,
the rate the cell's traffic file is given by hand, with the sweep in
PERF.md.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.registry import Registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    registry = Registry(ROOT)
    workload = registry.workload(args.workload)
    import jax
    devices = bench_run.check_devices(jax, int(workload["chips"]))
    if devices is None:
        return bench_run.NO_CHIP
    bench_run.enable_cache(jax, ROOT)
    ns = bench_run.parse(["--workload", args.workload, "--seed",
                          str(args.seed), "--seconds", str(args.seconds)])
    run = bench_run.Run(registry, workload, ns, devices)
    driver = registry.driver(run.traffic["kind"]).Driver(run)
    driver.setup()
    import numpy as np
    knee = None
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        driver.schedule(rate, args.seconds, args.seed + k)
        offered = len(driver.due) / args.seconds
        e2e = driver.window(args.seconds)
        lat = driver.latency
        q = max(1, len(lat) // 4)
        window = driver.notes()[-1]["window"]
        growth = float(np.mean(lat[-q:]) / np.mean(lat[:q]))
        keeps_up = (driver.failed == 0 and growth < 2
                    and e2e["requests_per_s"] >= 0.9 * offered)
        bench_run.emit({"rate_per_s": rate, "offered_per_s": offered, **e2e,
                        "failed": driver.failed, "backlog_growth": growth,
                        "keeps_up": keeps_up, **window})
        if not keeps_up:
            break   # past the knee: higher rates only queue longer
        knee = rate
    bench_run.emit({"knee_per_s": knee,
                    "four_fifths_per_s": None if knee is None
                    else int(0.8 * knee)})
    driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
