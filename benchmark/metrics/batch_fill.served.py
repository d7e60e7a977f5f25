"""Mean real requests per queue dispatch over the window: the
``serving.requests.ok`` and ``serving.batches`` counters of the program's
telemetry."""


def read(ctx):
    batches = ctx.counters.get("serving.batches", 0)
    if not batches:
        return None
    return ctx.counters.get("serving.requests.ok", 0) / batches
