"""The request engine's own host time per engine step: the mean over the
window's ``repro.step`` spans of the step's length less the time it
waited on the device (its ``repro.sync`` spans and, in traced runs, the
``bench.prefill_chunk`` marks); scheduling, table lookups and uploads."""
import progtrace as P

SOURCE = "device_trace"


def read(ctx):
    ev = P.events(ctx.trace)
    return None if ev is None else P.host_ms(ev, ctx.trace)
