"""Charges a trace's device idle to the program's own host spans.

The program records host spans on the profiler's clock under names that
start ``quest_tpu.`` (the service's dispatch phases
``quest_tpu.serve.{wait,coalesce,issue,ready,complete,fan_out}``, the
sweep forms' ``quest_tpu.circuits.prepare``, the dispatch annotations).
:func:`idle_by_program` cuts each device idle interval inside the
benchmark's window at the edges of those spans, on any host thread, and
charges each piece to the shortest span covering it (the rule
``trace_reduce`` labels its idle gaps by), keyed by the span's name up to
the first ``:`` or ``#``; a piece no program span covers goes to
``"none"``. Seconds are averaged over the devices, so the values sum to
the window less ``trace_reduce``'s ``busy_s``.

The benchmark's trace reduction does not call this yet: a per-layer
metric read from it needs ``trace_reduce.reduce_trace`` to hand it over.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce

PROGRAM_PREFIX = "quest_tpu."


def program_spans(pd) -> dict:
    """Host line (``<plane>:<index>:<thread>``) -> ``[(name, start_ns,
    end_ns)]`` of the program's own host spans."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events
                     if ev.name.startswith(PROGRAM_PREFIX)]
            if spans:
                out[f"{plane.name}:{i}:{line.name}"] = spans
    return out


def span_kind(name: str) -> str:
    """A span's name up to the first ``:`` or ``#``
    (``quest_tpu.serve.dispatch:energy:b64:env`` ->
    ``quest_tpu.serve.dispatch``)."""
    return re.split(r"[:#]", name, 1)[0]


def program_segments(spans: list, w0: float, w1: float) -> list:
    """``[(start, end, kind)]`` cutting ``[w0, w1]`` at every edge of the
    program spans ``[(name, start, end)]``: each piece is labelled with
    the kind of the shortest span covering it, else ``"none"``."""
    inside = sorted((max(s, w0), min(e, w1), e - s, name)
                    for name, s, e in spans if e > w0 and s < w1)
    edges = sorted({w0, w1} | {x for s, e, _, _ in inside for x in (s, e)})
    out, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(inside) and inside[k][0] <= a:
            active.append(inside[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        best = min(active, key=lambda sp: sp[2], default=None)
        out.append((a, b, span_kind(best[3]) if best else "none"))
    return out


def charge_idle(idle: list, segments: list, into: dict,
                scale: float = 1.0) -> None:
    """Add to ``into[kind]`` the overlap of the sorted, disjoint idle
    intervals ``[(start, end)]`` with each of ``segments`` (sorted
    ``(start, end, kind)``), times ``scale``."""
    i = j = 0
    while i < len(idle) and j < len(segments):
        lo = max(idle[i][0], segments[j][0])
        hi = min(idle[i][1], segments[j][1])
        if hi > lo:
            kind = segments[j][2]
            into[kind] = into.get(kind, 0.0) + (hi - lo) * scale
        if idle[i][1] < segments[j][1]:
            i += 1
        else:
            j += 1


def idle_by_program(pd) -> dict:
    """``{span kind: idle seconds}`` of a trace, averaged over its
    devices. Raises ``trace_reduce.TraceError`` where ``reduce_trace``
    does: a trace without the window span or a device plane reads
    nothing."""
    windows = [s for s in trace_reduce.host_spans(pd)
               if s[0] == trace_reduce.WINDOW]
    if not windows:
        raise trace_reduce.TraceError(
            f"no {trace_reduce.WINDOW} span in the trace")
    w0 = min(s[1] for s in windows)
    w1 = max(s[2] for s in windows)
    per_device = trace_reduce.device_ops(pd)
    if not per_device:
        raise trace_reduce.TraceError("no device plane in the trace")
    segments = program_segments(
        [sp for line in program_spans(pd).values() for sp in line], w0, w1)
    out: dict = {}
    for ops in per_device.values():
        merged = trace_reduce._merge([[max(s, w0), min(e, w1)]
                                      for _, s, e in ops
                                      if e > w0 and s < w1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        charge_idle(idle, segments, out, 1e-9 / len(per_device))
    return out
