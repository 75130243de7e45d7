"""Share of the HBM roofline the Pallas layer kernels reach.

Each layer-kernel event in the trace is charged one read and one write of
its device's share of the state, counted from the state's shape
(``peaks.state_pass_bytes``); the bytes over the events' summed device
time, over the chip's HBM peak (``peaks.json``). HBM bandwidth is the
bound a layer pass is held to: it reads and writes the whole state once.
"""

from benchmark.peaks import state_pass_bytes
from benchmark.trace_reduce import events_matching

# the Pallas layer kernel's name as the trace gives it
# (``pallas_layer_<k>gates``)
LAYER_KERNEL = ("pallas_layer_",)


def read(ctx):
    trace, n = ctx.get("trace"), ctx.get("num_qubits")
    if not trace or not n:
        return None
    events = events_matching(trace, LAYER_KERNEL)
    seconds = sum(s for _, s in events)
    if not events or seconds <= 0:
        return None
    moved = len(events) * state_pass_bytes(n, ctx["chips"])
    return 100.0 * moved / seconds / ctx["peaks"]["hbm_bytes_per_s"]
