"""Device-idle time in the window that lies inside a Python garbage
collection (``repro.gc``) or a compile (``repro.compile``, JAX's
``backend_compile_and_load``), summed over the union of those spans."""
import progtrace as P

SOURCE = "device_trace"


def read(ctx):
    ev = P.events(ctx.trace)
    return None if ev is None else P.stall_ms(ev, ctx.trace)
