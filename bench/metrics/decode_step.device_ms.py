"""Device busy time per decode step: the mean over the window's
``bench.decode`` marks of the device time inside each."""
SOURCE = "device_trace"


def read(ctx):
    busy = ctx.trace.busy_in("bench.decode")
    if not busy:
        return None
    return sum(busy) / len(busy) / 1e6
