#!/usr/bin/env python
"""Dump the planned gradient/optimizer schedule as JSON.

Offline inspection for the gradient serving stack (ISSUE 15): replays
the SAME policies the live path uses — the coalescer's padded batch
bucket (:func:`quest_tpu.serve.coalesce.batch_bucket`) for a ``B``-
request gradient group, the priced sharding decision
(:func:`quest_tpu.parallel.layout.choose_batch_sharding` at the
gradient executables' ``mem_factor=2.0`` — primal + cotangent resident
together), the trajectory-gradient wave plan
(:func:`quest_tpu.ops.trajectories.plan_waves`) when ``--trajectories``
is given, and a modeled optimizer convergence schedule: iterate values
decay geometrically at ``--rate`` toward the stated floor, and the
decision point is the first iterate whose modeled ``|Δvalue|`` fits
``--tol`` (the live loop measures; the planner can only be told). Pure
host-side planning: no device work, no gradients run.

Usage::

    python tools/grad_trace.py --qubits 16 --params 32 --batch 64 \\
        --max-iters 50 --tol 1e-4 --rate 0.8
    python tools/grad_trace.py --qubits 20 --params 16 --devices 8 \\
        --trajectories 1024 --budget 0.02
"""

from __future__ import annotations

import argparse
import os
import sys


def trace_schedule(num_qubits: int, num_params: int, batch: int,
                   num_devices: int, itemsize: int,
                   num_relayouts: int = 0,
                   trajectories: int = 0, wave_size: int = 0,
                   sampling_budget=None, sigma: float = 1.0,
                   max_iters: int = 0, tol: float = 0.0,
                   rate: float = 0.9, v0: float = 1.0,
                   v_floor: float = 0.0) -> dict:
    """The planned gradient schedule + optimizer decision points,
    JSON-ready."""
    from quest_tpu.parallel.layout import choose_batch_sharding
    from quest_tpu.serve.coalesce import batch_bucket

    mult = num_devices if num_devices > 1 else 1
    # trajectory gradients coalesce at the plain power-of-two bucket
    # (the trajectory axis owns the mesh); deterministic gradients pad
    # to the device multiple like energy sweeps
    bucket = batch_bucket(batch, floor=1 if trajectories else mult)
    # the sharded axis: request rows for the adjoint path, request
    # rows x wave draws for the trajectory path (estimated at the
    # request bucket — the wave bucket multiplies in below)
    policy = choose_batch_sharding(
        num_qubits, bucket, num_devices, itemsize, num_relayouts,
        mem_factor=2.0)
    doc = {
        "num_qubits": num_qubits,
        "num_params": num_params,
        "num_devices": num_devices,
        "batch_requests": batch,
        "batch_bucket": bucket,
        "padded_rows": bucket - batch,
        "transfer_block": [bucket, num_params + 1],
        # what the one-executable path collapses: the parameter-shift
        # client pays (2P+1) energy dispatches per row
        "host_syncs_avoided": bucket * (2 * num_params + 1) - 1,
        "sharding": {
            "mode": policy["mode"],
            "mem_factor": 2.0,
            "per_device_bytes": policy.get("per_device_bytes", 0.0),
            "amp_comm_seconds": policy.get("amp_comm_seconds", 0.0),
        },
    }
    if trajectories:
        from quest_tpu.ops.trajectories import plan_waves
        if wave_size < 1:
            wave_size = min(trajectories, max(32, mult))
        waves, wbucket = plan_waves(trajectories, wave_size, mult)
        # all P+1 components must fit the budget; the value component
        # converges at sigma/sqrt(n) under the stated spread
        n_star = None
        if sampling_budget:
            import math
            n_star = max(2, math.ceil(
                (sigma / float(sampling_budget)) ** 2))
        wave_events = []
        cum = 0
        stop = None
        for i, (start, live) in enumerate(waves):
            cum += live
            stops = n_star is not None and cum >= n_star and stop is None
            if stops:
                stop = i
            wave_events.append({
                "wave": i, "start": start, "live": live,
                "bucket": wbucket, "cumulative": cum,
                "early_stop": bool(stops),
            })
        doc["trajectory_grad"] = {
            "max_trajectories": trajectories,
            "wave_bucket": wbucket,
            "components": num_params + 1,
            "sampling_budget": (float(sampling_budget)
                                if sampling_budget else None),
            "projected_stop_after": n_star,
            "early_stop_wave": stop,
            "waves": wave_events,
        }
    if max_iters:
        events = []
        v_prev = None
        decided = None
        v = float(v0)
        for k in range(max_iters):
            delta = None if v_prev is None else abs(v - v_prev)
            converged = (decided is None and delta is not None
                         and delta <= tol)
            if converged:
                decided = k
            events.append({
                "iteration": k, "modeled_value": round(v, 12),
                "modeled_delta": (round(delta, 12)
                                  if delta is not None else None),
                "converged": bool(converged),
            })
            v_prev = v
            v = v_floor + (v - v_floor) * float(rate)
            if decided is not None:
                break
        doc["optimizer"] = {
            "max_iters": max_iters,
            "tol": tol,
            "rate": float(rate),
            "decision_iteration": decided,
            "projected_iterations": len(events),
            "projected_gradient_dispatches": len(events),
            "events": events,
        }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qubits", type=int, default=16)
    ap.add_argument("--params", type=int, default=32,
                    help="declared circuit parameters P (the gradient "
                         "width; the transfer block is (B, P+1))")
    ap.add_argument("--batch", type=int, default=64,
                    help="coalesced gradient requests per dispatch")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--itemsize", type=int, default=8,
                    help="bytes per real amplitude component")
    ap.add_argument("--relayouts", type=int, default=0,
                    help="planned relayouts (the amp-mode collective "
                         "count per batch row)")
    ap.add_argument("--trajectories", type=int, default=0,
                    help="max draws for a TRAJECTORY gradient (0 = "
                         "deterministic adjoint path)")
    ap.add_argument("--wave", type=int, default=0,
                    help="wave size (0 = the engine's default bucket)")
    ap.add_argument("--budget", type=float, default=None,
                    help="sampling budget (target standard error, all "
                         "P+1 components)")
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="per-trajectory standard deviation estimate")
    ap.add_argument("--max-iters", type=int, default=0,
                    help="model an optimizer run of this many iterates")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="convergence tolerance on |delta value|")
    ap.add_argument("--rate", type=float, default=0.9,
                    help="modeled geometric convergence rate per "
                         "iterate")
    ap.add_argument("--v0", type=float, default=1.0,
                    help="modeled starting objective value")
    ap.add_argument("--floor", type=float, default=0.0,
                    help="modeled objective floor the iterates decay "
                         "toward")
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

    doc = trace_schedule(args.qubits, args.params, args.batch,
                         args.devices, args.itemsize,
                         num_relayouts=args.relayouts,
                         trajectories=args.trajectories,
                         wave_size=args.wave,
                         sampling_budget=args.budget, sigma=args.sigma,
                         max_iters=args.max_iters, tol=args.tol,
                         rate=args.rate, v0=args.v0,
                         v_floor=args.floor)
    _trace_io.emit(doc, kind="grad", out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
