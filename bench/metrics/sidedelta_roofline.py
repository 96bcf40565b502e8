"""The ``sidedelta`` kernel's share of its roofline.

The least time the chip could take for the window's side-delta calls
(each call's larger bound, FLOPs over peak or bytes over bandwidth, as
``roofline.sidedelta_call`` counts the operation) over the summed device
time of the kernel's events (HLO custom calls named ``sidedelta``).
"""
import roofline as R
from tracereduce import op_short

SOURCE = "device_trace"


def _is_kernel(op) -> bool:
    return op_short(op.name) == "sidedelta" and "custom-call" in op.name


def read(ctx):
    calls = ctx.counters.get("sidedelta_calls")
    ns, n = ctx.trace.op_ns(_is_kernel)
    if not calls or not n or ns <= 0:
        return None
    return 100.0 * R.sum_bounds(calls, ctx.peaks) / (ns / 1e9)
