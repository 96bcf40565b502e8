"""Device busy time per engine step: the mean over the window's
``bench.engine_step`` marks of the device time inside each."""
SOURCE = "device_trace"


def read(ctx):
    busy = ctx.trace.busy_in("bench.engine_step")
    if not busy:
        return None
    return sum(busy) / len(busy) / 1e6
