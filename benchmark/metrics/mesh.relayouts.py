"""Relayouts the timed program's plan makes per circuit: the exchanges
that move qubits between the chips (``CompiledCircuit.dispatch_stats()``
``relayouts``)."""


def read(ctx):
    plan = ctx.get("plan")
    if not plan or "relayouts" not in plan:
        return None
    return plan["relayouts"]
