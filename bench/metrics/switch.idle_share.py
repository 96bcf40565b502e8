"""Share of each switch's wall time in which the device ran nothing: one
minus device busy time over wall time, summed over the window's
``bench.switch`` marks."""
SOURCE = "device_trace"


def read(ctx):
    spans = ctx.trace.spans("bench.switch")
    wall = sum(e - s for s, e in spans)
    if wall <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - sum(ctx.trace.busy_in("bench.switch")) / wall)
