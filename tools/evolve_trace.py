#!/usr/bin/env python
"""Dump the planned Hamiltonian-dynamics schedule as JSON.

Offline inspection for the dynamics serving stack (ISSUE 18): replays
the SAME policies the live path uses — the coalescer's padded batch
bucket (:func:`quest_tpu.serve.coalesce.batch_bucket`) for a ``B``-
request evolve group, the priced sharding decision
(:func:`quest_tpu.parallel.layout.choose_batch_sharding` at the
dynamics executables' ``mem_factor=1.0`` — only the evolving register
stays resident), the segment carve (``--steps`` total Trotter steps cut
into ``--segment``-step slices at constant ``dt``, so equal-length
segments REUSE one executable and only a trailing remainder compiles a
second), the step-fusion ledger (each segment folds ``B x steps``
per-step observable reads through the in-executable Welford carry and
pays exactly ONE packed ``(B, S + 3 + 2^(n+1))`` transfer), and — with
``--ground`` — a modeled imaginary-time convergence schedule: the
residual decays geometrically at ``--rate`` and the decision point is
the first segment whose modeled residual fits ``--tol`` (the live loop
measures the device-resident residual; the planner can only be told).
Pure host-side planning: no device work, no evolution runs.

Usage::

    python tools/evolve_trace.py --qubits 16 --terms 31 --steps 200 \\
        --segment 64 --batch 8 --devices 8
    python tools/evolve_trace.py --qubits 12 --terms 23 --ground \\
        --iters-per-segment 16 --tol 1e-9 --rate 0.3
"""

from __future__ import annotations

import argparse
import os
import sys


def trace_schedule(num_qubits: int, num_terms: int, steps: int,
                   order: int, segment_steps: int, batch: int,
                   num_devices: int, itemsize: int = 8,
                   num_relayouts: int = 0,
                   ground: bool = False, tau: float = 0.1,
                   max_segments: int = 64, tol: float = 0.0,
                   rate: float = 0.5, r0: float = 1.0) -> dict:
    """The planned dynamics schedule + convergence decision points,
    JSON-ready."""
    from quest_tpu.parallel.layout import choose_batch_sharding
    from quest_tpu.serve.coalesce import batch_bucket

    mult = num_devices if num_devices > 1 else 1
    # dynamics requests coalesce like energy sweeps: pad to the device
    # multiple so every shard carries whole rows
    bucket = batch_bucket(batch, floor=mult)
    policy = choose_batch_sharding(
        num_qubits, bucket, num_devices, itemsize, num_relayouts,
        mem_factor=1.0)
    # the Trotter synthesis rule: order 1 sweeps the terms once per
    # step; order 2 (Strang) sweeps half-dt forward then reversed
    rotations_per_step = num_terms if order == 1 else 2 * num_terms
    planes_width = 2 * (1 << num_qubits)

    if ground:
        seg_lengths = [int(steps)] * int(max_segments)
    else:
        total = int(steps)
        seg_lengths = []
        while total > 0:
            seg_lengths.append(min(int(segment_steps), total))
            total -= seg_lengths[-1]

    seen_lengths = set()
    segments = []
    fused = 0
    avoided = 0
    residual = float(r0)
    decided = None
    for k, ns in enumerate(seg_lengths):
        # one executable per distinct segment length: the carve keeps
        # dt constant, so every full-size slice replays one program and
        # only a trailing remainder compiles a second
        reuse = ns in seen_lengths
        seen_lengths.add(ns)
        width = ns + 3 + planes_width + (1 if ground else 0)
        seg = {
            "segment": k,
            "steps": ns,
            "rotations": ns * rotations_per_step,
            "transfer_block": [bucket, width],
            "steps_fused": bucket * ns,
            # what the one-executable path collapses: a per-step client
            # pays one energy read-back per step per row, and the
            # segment pays exactly one packed transfer instead
            "host_syncs_avoided": bucket * ns - 1,
            "reuses_executable": bool(reuse),
        }
        fused += seg["steps_fused"]
        avoided += seg["host_syncs_avoided"]
        if ground:
            residual *= float(rate) ** ns
            converged = decided is None and residual <= tol
            if converged:
                decided = k
            seg["modeled_residual"] = residual
            seg["converged"] = bool(converged)
        segments.append(seg)
        if decided is not None:
            break

    doc = {
        "num_qubits": num_qubits,
        "num_terms": num_terms,
        "order": order,
        "mode": "ground" if ground else "evolve",
        "total_steps": sum(s["steps"] for s in segments),
        "segment_steps": int(steps) if ground else int(segment_steps),
        "batch_requests": batch,
        "batch_bucket": bucket,
        "padded_rows": bucket - batch,
        "executables_compiled": len(seen_lengths),
        "evolve_steps_fused": fused,
        "host_syncs_avoided": avoided,
        "segments": segments,
        "sharding": {
            "mode": policy["mode"],
            "mem_factor": 1.0,
            "per_device_bytes": policy.get("per_device_bytes", 0.0),
            "amp_comm_seconds": policy.get("amp_comm_seconds", 0.0),
        },
    }
    if ground:
        doc["ground"] = {
            "tau": float(tau),
            "tol": float(tol),
            "rate": float(rate),
            "max_segments": int(max_segments),
            "decision_segment": decided,
            "projected_segments": len(segments),
        }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qubits", type=int, default=16)
    ap.add_argument("--terms", type=int, default=31,
                    help="Pauli terms in the Hamiltonian (the Trotter "
                         "sweep length)")
    ap.add_argument("--steps", type=int, default=128,
                    help="total Trotter steps (evolve) or steps per "
                         "segment (with --ground)")
    ap.add_argument("--order", type=int, default=2, choices=(1, 2),
                    help="Trotter order (2 = Strang splitting)")
    ap.add_argument("--segment", type=int, default=64,
                    help="steps carved into each serving segment")
    ap.add_argument("--batch", type=int, default=8,
                    help="coalesced evolve requests per dispatch")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--itemsize", type=int, default=8,
                    help="bytes per real amplitude component")
    ap.add_argument("--relayouts", type=int, default=0,
                    help="planned relayouts (the amp-mode collective "
                         "count per batch row)")
    ap.add_argument("--ground", action="store_true",
                    help="model an imaginary-time ground-state run "
                         "instead of real-time evolution")
    ap.add_argument("--iters-per-segment", type=int, default=0,
                    help="ground-state power iterations per segment "
                         "(0 = --steps)")
    ap.add_argument("--tau", type=float, default=0.1,
                    help="imaginary-time step")
    ap.add_argument("--max-segments", type=int, default=64,
                    help="ground-state segment bound")
    ap.add_argument("--tol", type=float, default=1e-9,
                    help="convergence tolerance on the modeled "
                         "residual")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="modeled geometric residual decay per "
                         "iteration")
    ap.add_argument("--r0", type=float, default=1.0,
                    help="modeled starting residual")
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

    steps = args.steps
    if args.ground and args.iters_per_segment:
        steps = args.iters_per_segment
    doc = trace_schedule(args.qubits, args.terms, steps, args.order,
                         args.segment, args.batch, args.devices,
                         args.itemsize, num_relayouts=args.relayouts,
                         ground=args.ground, tau=args.tau,
                         max_segments=args.max_segments, tol=args.tol,
                         rate=args.rate, r0=args.r0)
    _trace_io.emit(doc, kind="evolve", out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
