"""Median submit-to-dispatch wait of the window's requests, from the
service's own queue-wait histogram (``ServiceMetrics``)."""


def read(ctx):
    svc = ctx.get("service")
    if not svc or not svc.get("batches"):
        return None
    return svc["p50_queue_wait_s"] * 1e3
