"""Share of the interchip roofline the exchange reaches.

Each chip is charged the bytes the program's plan sends per circuit
(``comm_bytes_planned``, mesh-total, over the chips) for every circuit
of the window; those bytes over the chip's collective device time
(``benchmark/collectives.py``, averaged over the chips), over the chip's
interconnect peak (``ici_bits_per_s`` of ``peaks.json``). The
interconnect is the bound an all-to-all relayout is held to: it moves
three quarters of each chip's share of the state. On the chip a
relayout is one synchronous ``all_to_all`` op, whose time covers its
transfer. Nothing is read without collectives in the trace or bytes in
the plan.
"""

from benchmark import collectives


def bytes_per_chip(plan: dict, chips: int, circuits: int) -> float:
    """Bytes one chip sends in a window of ``circuits`` runs."""
    return plan["comm_bytes_planned"] / chips * circuits


def read(ctx):
    plan, circuits = ctx.get("plan"), ctx.get("circuits")
    if not plan or not circuits or not plan.get("comm_bytes_planned"):
        return None
    seconds = collectives.seconds_per_chip(ctx.get("trace"))
    if seconds is None:
        return None
    peak = ctx["peaks"]["ici_bits_per_s"] / 8
    sent = bytes_per_chip(plan, ctx["chips"], circuits)
    return 100.0 * sent / seconds / peak
