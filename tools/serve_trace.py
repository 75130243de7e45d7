#!/usr/bin/env python
"""Dump the serving runtime's coalescing schedule as JSON.

Offline inspection for the request coalescer
(quest_tpu/serve/coalesce.py): replays a synthetic timed request trace
through the SAME policy the live dispatcher uses
(:func:`quest_tpu.serve.coalesce.plan_schedule`) and prints every
dispatch it would issue — dispatch time, traffic class, live batch
size, padded bucket, per-request waits, and the trigger ("full" batch
vs "max_wait" maturity) — plus trace-level totals (occupancy, coalesce
ratio, padded fraction, wait percentiles). Pure host-side simulation:
no JAX import, no device work, so the tool runs anywhere instantly.

Usage::

    python tools/serve_trace.py --requests 512 --rate 20000
    python tools/serve_trace.py --max-batch 32 --max-wait 0.001 --classes 4

``--rate`` is the mean arrival rate (requests/sec, exponential
inter-arrival); ``--classes`` is how many distinct coalesce keys
(circuit/observable/shot-bucket classes) the traffic mixes — only
same-class requests may share a batch, so more classes means thinner
groups at the same total rate.
"""

from __future__ import annotations

import argparse
import os
import sys


def simulate_trace(num_requests: int, rate_hz: float, num_classes: int,
                   seed: int, burst: float = 0.0) -> list:
    """A deterministic synthetic arrival trace: ``(t, class_index)``
    pairs with exponential inter-arrival at ``rate_hz`` and classes
    drawn with a mild skew (class 0 is the hot circuit — real serving
    traffic is never uniform). ``burst`` > 0 injects that fraction of
    requests as zero-gap bursts (the coalescer's best case)."""
    import random
    rng = random.Random(seed)
    weights = [1.0 / (i + 1) for i in range(num_classes)]
    total_w = sum(weights)
    t = 0.0
    out = []
    for _ in range(num_requests):
        if burst <= 0.0 or rng.random() >= burst:
            t += rng.expovariate(rate_hz)
        draw = rng.random() * total_w
        cls = 0
        while draw > weights[cls]:
            draw -= weights[cls]
            cls += 1
        out.append((t, cls))
    return out


def trace_report(arrivals: list, policy, device_multiple: int = 1) -> dict:
    """The coalescing schedule + totals for a timed trace, JSON-ready."""
    from quest_tpu.serve.coalesce import plan_schedule
    from quest_tpu.serve.metrics import ServiceMetrics
    events = plan_schedule(arrivals, policy,
                           device_multiple=device_multiple)
    sizes = [e["size"] for e in events]
    waits = sorted(w for e in events
                   for w in (e["mean_wait_s"],) * e["size"])
    dispatched = sum(sizes)
    shared = sum(s for s in sizes if s > 1)
    padded = sum(e["padded_rows"] for e in events)
    pct = ServiceMetrics._pct     # one percentile convention everywhere

    return {
        "policy": {"max_batch": policy.max_batch,
                   "max_wait_s": policy.max_wait_s,
                   "bucket_batches": policy.bucket_batches},
        "device_multiple": device_multiple,
        "num_requests": len(arrivals),
        "num_classes": len({k for _, k in arrivals}),
        "events": events,
        "totals": {
            "requests": dispatched,
            "batches": len(events),
            "batch_occupancy": dispatched / max(1, len(events)),
            "max_batch_occupancy": max(sizes) if sizes else 0,
            "coalesce_ratio": shared / max(1, dispatched),
            "padded_rows": padded,
            "padded_fraction": padded / max(1, padded + dispatched),
            "full_batches": sum(1 for e in events
                                if e["reason"] == "full"),
            "p50_wait_s": pct(waits, 50.0),
            "p99_wait_s": pct(waits, 99.0),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--rate", type=float, default=20000.0,
                    help="mean arrival rate, requests/sec")
    ap.add_argument("--classes", type=int, default=2,
                    help="distinct coalesce keys in the traffic mix")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait", type=float, default=2e-3,
                    help="coalescer max_wait_s")
    ap.add_argument("--devices", type=int, default=1,
                    help="batch-bucket floor (mesh device count)")
    ap.add_argument("--burst", type=float, default=0.25,
                    help="fraction of requests arriving in zero-gap "
                         "bursts")
    ap.add_argument("--seed", type=int, default=2026)
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
    # the coalescer is pure host-side policy: it runs on the CPU and leaves the
    # chip to the process that serves
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from quest_tpu.serve.coalesce import CoalescePolicy

    arrivals = simulate_trace(args.requests, args.rate, args.classes,
                              args.seed, burst=args.burst)
    policy = CoalescePolicy(max_batch=args.max_batch,
                            max_wait_s=args.max_wait)
    doc = trace_report(arrivals, policy, device_multiple=args.devices)
    if args.no_events:
        doc.pop("events")
    _trace_io.emit(doc, kind="serve", out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
