#!/usr/bin/env python
"""Dump the multi-tenant scheduling stack's decisions as JSON.

Offline inspection for the WFQ scheduling layer
(quest_tpu/serve/sched.py): replays a synthetic timed multi-tenant
request trace through the SAME policy stack the live dispatcher uses
(:func:`quest_tpu.serve.sched.plan_wfq_schedule` — coalesce -> WFQ
dequeue -> segment preemption -> ledger-driven autoscale) and prints
every decision it makes — dispatches with per-batch waits, preemptions
of checkpointed long work when interactive traffic queues, and
scale-up/scale-down events from the modeled
:class:`~quest_tpu.resilience.AutoscalePolicy` — plus per-tenant wait
percentiles, mesh shares, and the Jain fairness index. Pure host-side
simulation: no device work, so the tool runs anywhere instantly.

Usage::

    python tools/sched_trace.py --requests 512 --rate 2000
    python tools/sched_trace.py --tenant ui:3:0:0.4 --tenant batch:1:2:0.6
    python tools/sched_trace.py --segment 0.05 --autoscale --max-replicas 4

Each ``--tenant`` spec is ``name:weight:priority:share`` — WFQ weight,
strict priority class (0 = interactive), and the fraction of the
traffic the tenant submits. ``--fifo`` replays the same trace with
every tenant collapsed to one contract (the pre-WFQ dispatcher), the
baseline ``bench.py bench_multitenant`` grades the fairness win
against.
"""

from __future__ import annotations

import argparse
import os
import sys


def parse_tenants(specs: list) -> tuple:
    """``name:weight:priority:share`` specs -> (policy kwargs by name,
    normalized traffic shares by name). Raises ValueError on a bad
    spec so the CLI fails with the offending string, not a traceback
    deep in the scheduler."""
    policies = {}
    shares = {}
    for spec in specs:
        parts = spec.split(":")
        if len(parts) != 4:
            raise ValueError(
                f"tenant spec {spec!r}: expected name:weight:priority:"
                "share")
        name, weight, priority, share = parts
        policies[name] = {"weight": float(weight),
                          "priority": int(priority)}
        shares[name] = float(share)
    total = sum(shares.values())
    if total <= 0.0:
        raise ValueError("tenant traffic shares sum to zero")
    return policies, {k: v / total for k, v in shares.items()}


def simulate_tenant_trace(num_requests: int, rate_hz: float,
                          shares: dict, num_classes: int, seed: int,
                          burst: float = 0.0) -> list:
    """A deterministic synthetic multi-tenant arrival trace:
    ``(t, tenant, class_index)`` triples with exponential inter-arrival
    at ``rate_hz``, tenants drawn by their traffic share, and classes
    drawn with a mild skew (class 0 is the hot circuit). ``burst`` > 0
    injects that fraction of requests as zero-gap bursts — the bursty
    two-class shape the live fairness bench replays."""
    import random
    rng = random.Random(seed)
    names = sorted(shares)
    t = 0.0
    out = []
    cls_w = [1.0 / (i + 1) for i in range(num_classes)]
    cls_total = sum(cls_w)
    for _ in range(num_requests):
        if burst <= 0.0 or rng.random() >= burst:
            t += rng.expovariate(rate_hz)
        draw = rng.random()
        tenant = names[-1]
        for name in names:
            if draw < shares[name]:
                tenant = name
                break
            draw -= shares[name]
        cdraw = rng.random() * cls_total
        cls = 0
        while cdraw > cls_w[cls]:
            cdraw -= cls_w[cls]
            cls += 1
        out.append((t, tenant, cls))
    return out


def trace_report(arrivals: list, policy, tenants, *,
                 device_multiple: int = 1, request_cost_s: float = 1e-3,
                 num_replicas: int = 1, segment_s=None, autoscale=None,
                 scale_ready_s: float = 0.25) -> dict:
    """The full scheduling replay + the policy header, JSON-ready."""
    from quest_tpu.serve.sched import plan_wfq_schedule
    doc = plan_wfq_schedule(
        arrivals, policy, tenants, device_multiple=device_multiple,
        request_cost_s=request_cost_s, num_replicas=num_replicas,
        segment_s=segment_s, autoscale=autoscale,
        scale_ready_s=scale_ready_s)
    doc["policy"] = {
        "max_batch": policy.max_batch,
        "max_wait_s": policy.max_wait_s,
        "device_multiple": device_multiple,
        "request_cost_s": request_cost_s,
        "num_replicas": num_replicas,
        "segment_s": segment_s,
        "autoscale": None if autoscale is None else {
            "min_replicas": autoscale.min_replicas,
            "max_replicas": autoscale.max_replicas,
            "scale_up_drain_s": autoscale.scale_up_drain_s,
            "scale_down_idle_s": autoscale.scale_down_idle_s,
            "cooldown_s": autoscale.cooldown_s,
        },
        "tenants": {name: dict(kw) for name, kw in sorted(
            tenants_kwargs(tenants).items())},
    }
    return doc


def tenants_kwargs(tenants) -> dict:
    """TenantPolicy map -> plain dicts for the JSON header."""
    out = {}
    for name, pol in (tenants or {}).items():
        out[name] = {"weight": pol.weight, "priority": pol.priority}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="mean arrival rate, requests/sec")
    ap.add_argument("--tenant", action="append", default=None,
                    metavar="NAME:WEIGHT:PRIORITY:SHARE",
                    help="one tenant contract + its traffic share "
                         "(repeatable; default ui:3:0:0.4 batch:1:2:0.6)")
    ap.add_argument("--classes", type=int, default=2,
                    help="distinct coalesce keys per tenant")
    ap.add_argument("--burst", type=float, default=0.25,
                    help="fraction of requests arriving in zero-gap "
                         "bursts")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait", type=float, default=2e-3,
                    help="coalescer max_wait_s")
    ap.add_argument("--devices", type=int, default=1,
                    help="batch-bucket floor (mesh device count)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="modeled replica pool size")
    ap.add_argument("--request-cost", type=float, default=1e-3,
                    help="modeled seconds of mesh time per padded row")
    ap.add_argument("--segment", type=float, default=None,
                    help="checkpoint segment seconds: long batches "
                         "yield at this boundary when interactive "
                         "work queues")
    ap.add_argument("--autoscale", action="store_true",
                    help="model ledger-driven elasticity")
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--scale-ready", type=float, default=0.25,
                    help="modeled scale-up-to-ready seconds")
    ap.add_argument("--fifo", action="store_true",
                    help="collapse every tenant to one default "
                         "contract (the pre-WFQ FIFO baseline)")
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--no-events", action="store_true",
                    help="totals + per-tenant stats only")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _trace_io
    _trace_io.add_output_argument(ap)
    args = ap.parse_args(argv)

    repo_root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    # the scheduler is pure host-side policy: it runs on the CPU and leaves the
    # chip to the process that serves
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from quest_tpu.resilience.recovery import AutoscalePolicy
    from quest_tpu.serve.coalesce import CoalescePolicy
    from quest_tpu.serve.sched import TenantPolicy

    specs = args.tenant or ["ui:3:0:0.4", "batch:1:2:0.6"]
    try:
        policy_kwargs, shares = parse_tenants(specs)
    except ValueError as e:
        ap.error(str(e))
    tenants = {name: TenantPolicy(**kw)
               for name, kw in policy_kwargs.items()}
    if args.fifo:
        tenants = {name: TenantPolicy() for name in tenants}

    arrivals = simulate_tenant_trace(args.requests, args.rate, shares,
                                     args.classes, args.seed,
                                     burst=args.burst)
    policy = CoalescePolicy(max_batch=args.max_batch,
                            max_wait_s=args.max_wait)
    autoscale = AutoscalePolicy(
        min_replicas=args.replicas, max_replicas=args.max_replicas,
    ) if args.autoscale else None
    doc = trace_report(arrivals, policy, tenants,
                       device_multiple=args.devices,
                       request_cost_s=args.request_cost,
                       num_replicas=args.replicas,
                       segment_s=args.segment, autoscale=autoscale,
                       scale_ready_s=args.scale_ready)
    if args.no_events:
        doc.pop("events")
    _trace_io.emit(doc, kind="sched", out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
