#!/usr/bin/env python
"""Dump the planned trajectory schedule as JSON.

Offline inspection for the trajectory engine
(quest_tpu/ops/trajectories.py): replays the SAME wave planner the
convergence loop uses (:func:`quest_tpu.ops.trajectories.plan_waves`)
and the SAME priced sharding decision
(:func:`quest_tpu.parallel.layout.choose_batch_sharding`), and prints
every wave the loop would dispatch — start index, live draws, padded
bucket rows — annotated with the projected standard error after that
wave (``sigma / sqrt(n)`` for the stated per-trajectory spread) and the
early-stop decision point where the projection first fits the sampling
budget. Pure host-side planning: no device work, no trajectories run.

Usage::

    python tools/traj_trace.py --qubits 16 --trajectories 1024 \\
        --budget 0.02 --sigma 0.7
    python tools/traj_trace.py --qubits 24 --devices 8 --wave 64

``--sigma`` is the per-trajectory standard deviation estimate the
stderr projection divides down (the live loop measures it; the planner
can only be told); ``--cross-shard-ops`` feeds the amplitude-sharded
fallback's collective count (``traj_cross_shard_ops``) into the mode
pricing.
"""

from __future__ import annotations

import argparse
import math
import os
import sys


def trace_schedule(num_qubits: int, max_trajectories: int,
                   wave_size: int, num_devices: int, itemsize: int,
                   sampling_budget=None, sigma: float = 1.0,
                   cross_shard_ops: int = 0) -> dict:
    """The planned trajectory schedule + sharding decision, JSON-ready."""
    from quest_tpu.ops.trajectories import plan_waves
    from quest_tpu.parallel.layout import choose_batch_sharding

    mult = num_devices if num_devices > 1 else 1
    if wave_size < 1:
        wave_size = min(max_trajectories, max(32, mult))
    waves, bucket = plan_waves(max_trajectories, wave_size, mult)
    policy = choose_batch_sharding(
        num_qubits, bucket, num_devices, itemsize, cross_shard_ops)
    # projected early stop: stderr(n) = sigma / sqrt(n) fits the budget
    # from n* = ceil((sigma / budget)^2) draws on
    n_star = None
    if sampling_budget:
        n_star = max(2, math.ceil((sigma / float(sampling_budget)) ** 2))
    events = []
    cum = 0
    stop_wave = None
    for i, (start, live) in enumerate(waves):
        cum += live
        est = sigma / math.sqrt(cum) if cum >= 2 else None
        stops = n_star is not None and cum >= n_star \
            and stop_wave is None
        if stops:
            stop_wave = i
        events.append({
            "wave": i, "start": start, "live": live,
            "bucket": bucket, "padded_rows": bucket - live,
            "cumulative": cum,
            "est_stderr": round(est, 9) if est is not None else None,
            "early_stop": bool(stops),
        })
    planned = events if stop_wave is None else events[:stop_wave + 1]
    return {
        "num_qubits": num_qubits,
        "num_devices": num_devices,
        "max_trajectories": max_trajectories,
        "wave_bucket": bucket,
        "sampling_budget": (float(sampling_budget)
                            if sampling_budget else None),
        "sigma_estimate": sigma,
        "sharding": {
            "mode": policy["mode"],
            "per_device_bytes": policy.get("per_device_bytes", 0.0),
            "amp_comm_seconds": policy.get("amp_comm_seconds", 0.0),
            "cross_shard_ops": cross_shard_ops,
        },
        "projected_stop_after": (None if n_star is None
                                 else int(n_star)),
        "early_stop_wave": stop_wave,
        "projected_trajectories": planned[-1]["cumulative"],
        "projected_saved": max_trajectories - planned[-1]["cumulative"],
        "events": events,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qubits", type=int, default=16)
    ap.add_argument("--trajectories", type=int, default=1024,
                    help="max trajectory count (the early-stop ceiling)")
    ap.add_argument("--wave", type=int, default=0,
                    help="wave size (0 = the engine's default bucket)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--itemsize", type=int, default=8,
                    help="bytes per real amplitude component")
    ap.add_argument("--budget", type=float, default=None,
                    help="sampling budget (target standard error)")
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="per-trajectory standard deviation estimate")
    ap.add_argument("--cross-shard-ops", type=int, default=0,
                    help="paired ops touching sharded positions (the "
                         "amp-mode collective count per trajectory)")
    ap.add_argument("--no-events", action="store_true",
                    help="totals only (compact output)")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _trace_io
    _trace_io.add_output_argument(ap)
    args = ap.parse_args(argv)

    repo_root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    # the planner is pure host-side policy: it runs on the CPU and leaves the
    # chip to the process that serves
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    doc = trace_schedule(args.qubits, args.trajectories, args.wave,
                         args.devices, args.itemsize,
                         sampling_budget=args.budget, sigma=args.sigma,
                         cross_shard_ops=args.cross_shard_ops)
    if args.no_events:
        doc.pop("events")
    _trace_io.emit(doc, kind="traj", out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
