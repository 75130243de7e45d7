"""Share of the HBM roofline the Pallas row-gate kernels reach.

Each row-gate event in the trace (``pallas_rowgate_<k>q``) is charged one
read and one write of its device's share of the state, counted from the
state's shape (``peaks.state_pass_bytes``); the bytes over the events'
summed device time, over the chip's HBM peak (``peaks.json``). A row
gate reads and writes every amplitude once, so HBM bandwidth is the
bound it is held to. Nothing is read where no such event exists.
"""

from benchmark.peaks import state_pass_bytes
from benchmark.trace_reduce import events_matching

# the row-gate kernel's name as the trace gives it
ROWGATE_KERNEL = ("pallas_rowgate_",)


def read(ctx):
    trace, n = ctx.get("trace"), ctx.get("num_qubits")
    if not trace or not n:
        return None
    events = events_matching(trace, ROWGATE_KERNEL)
    seconds = sum(s for _, s in events)
    if not events or seconds <= 0:
        return None
    moved = len(events) * state_pass_bytes(n, ctx["chips"])
    return 100.0 * moved / seconds / ctx["peaks"]["hbm_bytes_per_s"]
