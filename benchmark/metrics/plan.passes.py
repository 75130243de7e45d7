"""State passes the timed program makes: the planner's kernels out plus
its relayouts (``CompiledCircuit.dispatch_stats()``)."""


def read(ctx):
    plan = ctx.get("plan")
    if not plan:
        return None
    return plan["kernels_out"] + plan["relayouts"]
