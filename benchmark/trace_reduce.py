"""Reduces a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

- Device planes are those named ``/device:TPU:<n>``; their op events are
  the events of the line ``XLA Ops``.
- The window is the benchmark's host span ``bench.window``; device time
  outside it is not counted.
- Busy time is the union of a device's op intervals inside the window;
  ``busy_s`` is its mean over the devices.
- An op is named by its HLO instruction without the instance number
  (``%pallas_layer_3gates.10 = (...) custom-call(...)`` reads
  ``pallas_layer_3gates``). An event is charged its self time: its
  duration less that of the events nested inside it (a ``while`` holds
  the ops of its body). Per-op time sums its events over all devices.
- Collective time sums the ops whose names start like an XLA collective.
- Idle gaps are the holes between busy intervals on each device, longest
  first, each labelled with the innermost ``bench.`` host span (other
  than the window) that covers its middle.

:func:`reduce_trace` raises :class:`TraceError` when the trace has no
device plane or no window span: such a trace reads nothing, never 0.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVES = ("all-to-all", "collective-permute", "all-gather",
               "all-reduce", "reduce-scatter", "send", "recv")


class TraceError(RuntimeError):
    pass


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _merge(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def is_collective(name: str) -> bool:
    return name.lower().startswith(COLLECTIVES)


def host_spans(pd) -> list:
    """``(name, start_ns, end_ns)`` of every ``bench.`` host span."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def device_ops(pd) -> dict:
    """Device plane name -> ``[(op name, start_ns, end_ns)]``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((op_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events)
        out[plane.name] = ops
    return out


def self_times(ops: list) -> list:
    """``(op name, self seconds)`` of each ``(name, start_ns, end_ns)``:
    its duration less the parts that events nested inside it cover."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(e, stack[-1][1]) - s
        out.append([name, e - s])
        stack.append((len(out) - 1, e))
    return [(name, ns * 1e-9) for name, ns in out]


def reduce_trace(pd, top: int = 10) -> dict:
    spans = host_spans(pd)
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise TraceError(f"no {WINDOW} span in the trace")
    w0 = min(s[1] for s in windows)
    w1 = max(s[2] for s in windows)
    per_device = device_ops(pd)
    if not per_device:
        raise TraceError("no device plane in the trace")
    inner = [s for s in spans if s[0] != WINDOW]
    op_s = defaultdict(float)
    op_events = defaultdict(list)
    busy, collective, gaps = [], [], []
    for plane, ops in sorted(per_device.items()):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        timed = self_times(clipped)
        for n, s in timed:
            op_s[n] += s
            op_events[n].append((plane, s))
        merged = _merge([[s, e] for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        collective.append(sum(s for n, s in timed if is_collective(n)))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_label(inner, (g0 + g1) / 2), (g1 - g0) * 1e-9))
    n_dev = len(per_device)
    ops_sorted = sorted(op_s.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[1])
    return {
        "devices": n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "busy_per_device_s": busy,
        "collective_per_device_s": collective,
        "op_s": dict(op_s),
        "op_events": dict(op_events),
        "device_ops": [[n, s] for n, s in ops_sorted[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }


def _label(spans: list, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside any bench span"


def events_matching(reduced: dict, fragments) -> list:
    """``(device plane, seconds)`` of every op event whose name holds one
    of ``fragments``."""
    return [ev for name, evs in reduced["op_events"].items()
            if any(f in name for f in fragments) for ev in evs]


def idle_share(reduced) -> float | None:
    """Percent of the window in which no op ran, averaged over devices."""
    if not reduced or reduced["window_s"] <= 0 or reduced["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
