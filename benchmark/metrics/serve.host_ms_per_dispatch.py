"""Mean host time the service's dispatcher spends per dispatch off the
device wait: coalescing, issuing (parameter packing and the program's
own preparation), completing less the device wait, and fanning out
(``ServiceMetrics.snapshot()["dispatch_host_s"]``), over the dispatches
of the window."""


def read(ctx):
    svc = ctx.get("service")
    if not svc or not svc.get("batches") or "dispatch_host_s" not in svc:
        return None
    return svc["dispatch_host_s"] / svc["batches"] * 1e3
