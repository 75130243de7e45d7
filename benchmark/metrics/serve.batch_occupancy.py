"""Mean live requests per engine dispatch over the window, from the
service's counters (``ServiceMetrics.snapshot()["batch_occupancy"]``)."""


def read(ctx):
    svc = ctx.get("service")
    if not svc or not svc.get("batch_occupancy"):
        return None
    return svc["batch_occupancy"]
