"""The host's dispatch time per switch: the mean over the window's
``repro.switch`` spans of the summed ``repro.switch.unload`` and
``repro.switch.load`` spans inside each (the walk of the weights and the
dispatch of their scatters; the wait is ``repro.switch.wait``)."""
import progtrace as P

SOURCE = "device_trace"


def read(ctx):
    ev = P.events(ctx.trace)
    return None if ev is None else P.dispatch_ms(ev, ctx.trace)
