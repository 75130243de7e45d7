"""Collective device time per chip, read from a reduced trace's op events
(``trace_reduce.reduce_trace``'s ``op_events``).

The TPU trace spells an all-to-all ``all_to_all`` where the HLO text
spells it ``all-to-all``, and ``trace_reduce``'s
``collective_per_device_s`` matches the HLO spelling only, so it reads 0
for a mesh program's relayouts. Here an op name counts as a collective
when, with ``_`` read as ``-``, it starts like one of
``trace_reduce.COLLECTIVES``.
"""

from __future__ import annotations

from benchmark.trace_reduce import COLLECTIVES


def is_collective(name: str) -> bool:
    return name.lower().replace("_", "-").startswith(COLLECTIVES)


def seconds_per_chip(reduced) -> float | None:
    """Mean over the trace's devices of each one's collective self time
    inside the window, or None where the trace holds no collective."""
    if not reduced or not reduced.get("devices"):
        return None
    seconds = sum(s for name, events in reduced.get("op_events", {}).items()
                  if is_collective(name) for _, s in events)
    if seconds <= 0:
        return None
    return seconds / reduced["devices"]
