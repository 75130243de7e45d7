#!/usr/bin/env python
"""Live terminal console for a serving engine's telemetry.

Renders one human-readable snapshot (or a refreshing ``--watch`` view)
of everything the unified telemetry stack exposes: queue depths and
batch occupancy, breaker/degraded/stall states, the precision-tier mix,
p50/p99 latencies, warm-cache and trace-sampler counters, and the tail
of the unified event timeline (wall-clock epoch + monotonic offset +
trace id — :mod:`quest_tpu.telemetry.events`).

Three sources, cheapest first:

- ``--stats-file FILE`` — render a ``dispatch_stats()`` JSON document
  (service- or router-shaped) somebody else wrote
  (:func:`quest_tpu.telemetry.export.write_snapshot`, a chaos dump, a
  scraped ``/metrics.json``). Pure stdlib: no JAX import, runs
  anywhere instantly.
- ``--demo`` — stand up a tiny in-process stub service on the CPU
  backend, push a few requests through it, and render the live
  console (the zero-to-console smoke path; add ``--watch`` to keep
  refreshing while the demo traffic runs).
- ``--json`` — emit the machine-readable snapshot (the shared
  ``quest_tpu.trace/1`` header via ``tools/_trace_io.py``) instead of
  the human view, composable with both sources and ``--out``.

Usage::

    python tools/obs_console.py --stats-file stats.json
    python tools/obs_console.py --demo --once
    python tools/obs_console.py --demo --watch --interval 0.5
    python tools/obs_console.py --demo --json --out snap.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


# ---------------------------------------------------------------------------
# pure formatting (no quest_tpu / jax imports: --stats-file must render
# anywhere, instantly)
# ---------------------------------------------------------------------------

def _fmt_s(v) -> str:
    """Seconds, human-scaled."""
    try:
        v = float(v)
    except (TypeError, ValueError):
        return "-"
    if v <= 0.0:
        return "0"
    if v < 1e-3:
        return f"{v * 1e6:.0f}us"
    if v < 1.0:
        return f"{v * 1e3:.2f}ms"
    return f"{v:.3f}s"


def _kv(pairs) -> str:
    return "  ".join(f"{k}={v}" for k, v in pairs if v is not None)


def _service_lines(svc: dict, indent: str = "  ") -> list:
    """The per-service block of the console (a ServiceMetrics
    snapshot)."""
    lines = [
        indent + _kv((
            ("queue", svc.get("queue_depth", 0)),
            ("occupancy", f"{svc.get('batch_occupancy', 0.0):.2f}"
             f"/max{svc.get('max_batch_occupancy', 0)}"),
            ("coalesce", f"{svc.get('coalesce_ratio', 0.0):.2f}"),
            ("padded", f"{svc.get('padded_fraction', 0.0):.2f}"),
            ("batches", svc.get("batches", 0)),
        )),
        indent + _kv((
            ("p50", _fmt_s(svc.get("p50_latency_s"))),
            ("p99", _fmt_s(svc.get("p99_latency_s"))),
            ("wait_p50", _fmt_s(svc.get("p50_queue_wait_s"))),
            ("wait_p99", _fmt_s(svc.get("p99_queue_wait_s"))),
        )),
        indent + _kv((
            ("submitted", svc.get("submitted", 0)),
            ("completed", svc.get("completed", 0)),
            ("failed", svc.get("failed", 0)),
            ("retries", svc.get("retries", 0)),
            ("timeouts", svc.get("timeouts", 0)),
            ("rejected", svc.get("rejected_queue_full", 0)
             + svc.get("rejected_deadline", 0)),
        )),
    ]
    faulty = _kv(tuple(
        (k, svc.get(k)) for k in (
            "executor_faults", "quarantined", "breaker_trips",
            "breaker_fastfails", "degraded_dispatches",
            "watchdog_stalls", "health_failures")
        if svc.get(k)))
    if faulty:
        lines.append(indent + "faults: " + faulty)
    return lines


def _tenant_lines(svc: dict, stats: dict = None,
                  indent: str = "  ") -> list:
    """The per-tenant table (a ``ServiceMetrics.tenant_snapshot()``
    nested under the service snapshot) plus the WFQ scheduler state
    when the stats document carries one."""
    tenants = svc.get("tenants", {}) or {}
    if not tenants:
        return []
    sched = (stats or {}).get("scheduler", {}) or {}
    pols = sched.get("tenants", {}) or {}
    lines = [
        f"{indent}{'tenant':<12} {'w':>4} {'pri':>3} {'subm':>6} "
        f"{'done':>6} {'quota':>5} {'pre':>4} {'share':>6} "
        f"{'p50':>8} {'p99':>8} {'wait99':>8}"]
    for name, t in sorted(tenants.items()):
        pol = pols.get(name, {})
        lines.append(
            f"{indent}{str(name)[:12]:<12} "
            f"{pol.get('weight', '-'):>4} "
            f"{pol.get('priority', '-'):>3} "
            f"{t.get('submitted', 0):>6} "
            f"{t.get('completed', 0):>6} "
            f"{t.get('rejected_quota', 0):>5} "
            f"{t.get('preemptions', 0):>4} "
            f"{t.get('mesh_share', 0.0):>6.2f} "
            f"{_fmt_s(t.get('p50_latency_s')):>8} "
            f"{_fmt_s(t.get('p99_latency_s')):>8} "
            f"{_fmt_s(t.get('p99_queue_wait_s')):>8}")
    if sched:
        lines.append(indent + _kv((
            ("mode", sched.get("mode")),
            ("pipeline_depth", sched.get("pipeline_depth")),
            ("vclock", sched.get("vclock")),
        )))
    return lines


def _tier_lines(stats: dict, svc: dict, indent: str = "  ") -> list:
    res = stats.get("resilience", {}) or {}
    drift = res.get("tier_observed_drift", {}) or {}
    pairs = [
        ("compile_tier", stats.get("precision_tier")),
        ("fast_dispatches", svc.get("fast_tier_dispatches", 0)),
        ("violations", svc.get("tier_violations", 0)),
        ("escalations", svc.get("tier_escalations", 0)),
    ]
    line = indent + _kv(tuple(pairs))
    if drift:
        line += "  observed_drift: " + " ".join(
            f"{k}={v:.2e}" for k, v in sorted(drift.items()))
    return [line]


def _breaker_lines(stats: dict, indent: str = "  ") -> list:
    res = stats.get("resilience", {}) or {}
    brk = res.get("breaker", {}) or {}
    states = {}
    for st in (brk.get("programs", {}) or {}).values():
        state = st.get("state", "?") if isinstance(st, dict) else st
        states[str(state)] = states.get(str(state), 0) + 1
    degraded = res.get("degraded_programs", []) or []
    pairs = [("trips", brk.get("trips", 0)),
             ("breakers",
              " ".join(f"{k}:{v}" for k, v in sorted(states.items()))
              or "all-closed")]
    if degraded:
        pairs.append(("degraded", ",".join(degraded)))
    return [indent + _kv(tuple(pairs))]


def _replica_table(replicas: list, indent: str = "  ") -> list:
    hdr = (f"{indent}{'#':>2} {'state':<12} {'alive':<5} {'dev':>3} "
           f"{'queue':>5} {'infl':>4} {'rst':>3} {'ema':>8} "
           f"{'p99':>8}  breaker-note")
    lines = [hdr]
    for r in replicas:
        svc = r.get("service", {}) or {}
        note = r.get("quarantine_reason", "") or ""
        lines.append(
            f"{indent}{r.get('replica', '?'):>2} "
            f"{str(r.get('state', '?')):<12} "
            f"{('yes' if r.get('alive') else 'NO'):<5} "
            f"{r.get('devices', 0):>3} "
            f"{r.get('queue_depth', 0):>5} "
            f"{r.get('inflight', 0):>4} "
            f"{r.get('restarts', 0):>3} "
            f"{_fmt_s(r.get('ema_request_s')):>8} "
            f"{_fmt_s(svc.get('p99_latency_s')):>8}  {note}")
    return lines


def _profile_lines(prof: dict, indent: str = "  ") -> list:
    """The profiler panel: per-program wall-to-ready percentiles per
    key, then the drift-monitor gauges (a
    ``dispatch_stats()["profile"]`` section — plain dict, stdlib-only
    rendering)."""
    lines = [indent + _kv((
        ("rate", prof.get("sample_rate", 0.0)),
        ("sampled", f"{prof.get('dispatches_sampled', 0)}"
                    f"/{prof.get('dispatches_seen', 0)}"),
    ))]
    keys = prof.get("keys", {}) or {}
    if keys:
        lines.append(
            f"{indent}{'site':<22} {'program':<10} {'kind':<10} "
            f"{'bkt':>4} {'tier':<6} {'shard':<6} {'n':>5} "
            f"{'p50':>8} {'p99':>8}")
        ranked = sorted(keys.values(),
                        key=lambda k: -float(k.get("count", 0)))
        for k in ranked[:12]:
            lines.append(
                f"{indent}{str(k.get('site', '?'))[:22]:<22} "
                f"{str(k.get('program', ''))[:10]:<10} "
                f"{str(k.get('kind', ''))[:10]:<10} "
                f"{k.get('bucket', 0):>4} "
                f"{str(k.get('tier', '')):<6} "
                f"{str(k.get('sharding', ''))[:6]:<6} "
                f"{k.get('count', 0):>5} "
                f"{_fmt_s(k.get('p50_s')):>8} "
                f"{_fmt_s(k.get('p99_s')):>8}")
        if len(ranked) > 12:
            lines.append(f"{indent}... {len(ranked) - 12} more key(s)")
    drift = (prof.get("drift", {}) or {}).get("models", {}) or {}
    if drift:
        parts = []
        for name, st in sorted(drift.items()):
            tag = f"{name}={st.get('drift_ratio', 1.0):.3g}x"
            ev = st.get("drift_events", 0)
            if ev:
                tag += f"({ev} drift events)"
            if not st.get("baseline_locked", True):
                tag += "[baselining]"
            parts.append(tag)
        lines.append(indent + "drift: " + "  ".join(parts))
    return lines


def _event_lines(events: list, limit: int, indent: str = "  ") -> list:
    lines = []
    for ev in list(events)[-limit:]:
        wall = ev.get("wall")
        when = time.strftime("%H:%M:%S", time.localtime(wall)) \
            + f".{int((wall % 1) * 1000):03d}" if wall is not None \
            else f"t+{ev.get('t', 0.0):.3f}s"
        detail = _kv(tuple(
            (k, v) for k, v in ev.items()
            if k not in ("t", "wall", "event")))
        lines.append(f"{indent}{when}  {ev.get('event', '?'):<22} "
                     f"{detail}")
    return lines


def render(stats: dict, events: list = None, title: str = "engine",
           event_limit: int = 8) -> str:
    """One console frame from a ``dispatch_stats()``-shaped dict
    (service- or router-shaped) plus an optional event timeline."""
    now = time.strftime("%Y-%m-%d %H:%M:%S")
    lines = [f"quest_tpu obs console — {title} — {now}",
             "=" * 72]
    if "replicas" in stats and "router" in stats:       # router-shaped
        rt = stats.get("router", {}) or {}
        lines.append("ROUTER")
        lines.append("  " + _kv((
            ("replicas", rt.get("replicas")),
            ("routed", rt.get("routed", 0)),
            ("failovers", rt.get("failovers", 0)),
            ("hedges", rt.get("hedged_dispatches", 0)),
            ("parked", rt.get("parked", 0)),
            ("outstanding", rt.get("outstanding", 0)),
            ("unroutable", rt.get("failed_unroutable", 0)),
            ("p99", _fmt_s(rt.get("p99_latency_s"))),
        )))
        lines.append("REPLICAS")
        lines.extend(_replica_table(stats.get("replicas", [])))
        for r in stats.get("replicas", []):
            svc = r.get("service", {}) or {}
            if svc:
                lines.append(f"REPLICA {r.get('replica', '?')} SERVICE")
                lines.extend(_service_lines(svc))
                lines.extend(_tier_lines(r, svc))
                tl = _tenant_lines(svc)
                if tl:
                    lines.append(
                        f"REPLICA {r.get('replica', '?')} TENANTS")
                    lines.extend(tl)
    else:                                               # service-shaped
        svc = stats.get("service", {}) or {}
        lines.append("SERVICE")
        lines.extend(_service_lines(svc))
        tl = _tenant_lines(svc, stats)
        if tl:
            lines.append("TENANTS")
            lines.extend(tl)
        lines.append("TIERS")
        lines.extend(_tier_lines(stats, svc))
        lines.append("RESILIENCE")
        lines.extend(_breaker_lines(stats))
    prof = stats.get("profile")
    if prof:
        lines.append("PROFILER")
        lines.extend(_profile_lines(prof))
    wc = stats.get("warm_cache")
    if wc:
        lines.append("WARM CACHE")
        lines.append("  " + _kv(tuple(sorted(wc.items()))))
    tel = stats.get("telemetry")
    if tel:
        lines.append("TRACING")
        lines.append("  " + _kv((
            ("sample_rate", tel.get("sample_rate")),
            ("seen", tel.get("requests_seen")),
            ("sampled", tel.get("traces_sampled")),
            ("finished", tel.get("traces_finished")),
            ("retained", tel.get("traces_retained")),
        )))
    if events:
        lines.append(f"EVENTS (last {min(event_limit, len(events))} "
                     f"of {len(events)})")
        lines.extend(_event_lines(events, event_limit))
    elif events is not None:
        lines.append("EVENTS (none recorded)")
    return "\n".join(lines)


def snapshot_doc(stats: dict, events: list = None) -> dict:
    """The machine-readable console snapshot (``--json``)."""
    from quest_tpu.telemetry.events import EVENT_SCHEMA
    return {"event_schema": EVENT_SCHEMA, "stats": stats,
            "events": list(events or [])}


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

def _demo_service():
    """A tiny stub service with real traffic (CPU backend, 2 qubits):
    the zero-to-console path, also the smoke test's fixture."""
    import numpy as np
    import quest_tpu as qt
    from quest_tpu.serve import SimulationService, TenantPolicy
    from quest_tpu.telemetry import profile as _profile
    _profile.configure(sample_rate=1.0, reset=True)
    env = qt.createQuESTEnv(num_devices=1, seed=[11])
    c = qt.Circuit(2)
    c.ry(0, c.parameter("a"))
    c.cnot(0, 1)
    cc = c.compile(env, pallas="off")
    svc = SimulationService(env, max_batch=8, max_wait_s=1e-3,
                            trace_sample_rate=1.0,
                            tenants={"ui": TenantPolicy(weight=3.0,
                                                        priority=0)})
    rng = np.random.default_rng(11)
    ham = ([[(0, 3)], [(1, 3)]], [1.0, 0.5])
    futs = [svc.submit(cc, {"a": float(rng.uniform(0, 6.28))},
                       observables=ham,
                       tenant="ui" if i % 2 else "default")
            for i in range(8)]
    for f in futs:
        f.result(timeout=60)
    return svc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--stats-file", default=None, metavar="FILE",
                    help="render a dispatch_stats() JSON document "
                         "(service- or router-shaped; no JAX needed)")
    ap.add_argument("--events-file", default=None, metavar="FILE",
                    help="JSON list of timeline events to render under "
                         "the stats (or a dump with an 'events'/"
                         "'timeline' key)")
    ap.add_argument("--demo", action="store_true",
                    help="stand up a stub CPU service with live "
                         "traffic and render it")
    ap.add_argument("--once", action="store_true",
                    help="render exactly one frame (the default unless "
                         "--watch; accepted for explicitness)")
    ap.add_argument("--watch", action="store_true",
                    help="refresh the console every --interval seconds "
                         "(demo mode only; Ctrl-C to stop)")
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--frames", type=int, default=0,
                    help="with --watch: stop after N frames "
                         "(0 = until Ctrl-C)")
    ap.add_argument("--events", type=int, default=8,
                    help="timeline tail length")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable snapshot (shared "
                         "quest_tpu.trace/1 header) instead of the "
                         "human view")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _trace_io
    _trace_io.add_output_argument(ap)
    args = ap.parse_args(argv)

    repo_root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)

    if args.stats_file:
        with open(args.stats_file) as fh:
            stats = json.load(fh)
        # tolerate wrapped dumps (a chaos trace, a --json snapshot)
        for key in ("stats",):
            if key in stats and isinstance(stats[key], dict):
                stats = stats[key]
        events = None
        if args.events_file:
            with open(args.events_file) as fh:
                events = json.load(fh)
            if isinstance(events, dict):
                events = events.get("events") \
                    or events.get("timeline") or []
        if args.json:
            _trace_io.emit(snapshot_doc(stats, events), kind="console",
                           out=args.out)
        else:
            out = render(stats, events, title=args.stats_file,
                         event_limit=args.events)
            if args.out:
                with open(args.out, "w") as fh:
                    fh.write(out + "\n")
            else:
                print(out)
        return 0

    if not args.demo:
        ap.error("pass --stats-file FILE or --demo")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    svc = _demo_service()
    from quest_tpu.telemetry.events import read_timeline
    try:
        frames = 0
        while True:
            stats = svc.dispatch_stats()
            events = read_timeline(svc, tool="obs_console")
            if args.json:
                _trace_io.emit(snapshot_doc(stats, events),
                               kind="console", out=args.out)
            else:
                frame = render(stats, events, title="demo service",
                               event_limit=args.events)
                if args.out:
                    with open(args.out, "w") as fh:
                        fh.write(frame + "\n")
                else:
                    if args.watch and frames:
                        print("\033[2J\033[H", end="")
                    print(frame)
            frames += 1
            if not args.watch or args.once \
                    or (args.frames and frames >= args.frames):
                break
            time.sleep(max(args.interval, 0.05))
    except KeyboardInterrupt:
        pass
    finally:
        svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
