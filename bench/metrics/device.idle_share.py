"""Share of the traced window in which no program ran on the device."""
SOURCE = "device_trace"


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
