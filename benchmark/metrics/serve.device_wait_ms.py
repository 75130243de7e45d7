"""Mean time a dispatch holds the service's dispatcher waiting on the
device: the sum of its ``quest_tpu.serve.ready`` spans
(``ServiceMetrics.snapshot()["dispatch_wait_s"]``) over the dispatches
of the window."""


def read(ctx):
    svc = ctx.get("service")
    if not svc or not svc.get("batches") or "dispatch_wait_s" not in svc:
        return None
    return svc["dispatch_wait_s"] / svc["batches"] * 1e3
