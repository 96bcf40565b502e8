"""The ``moe_gmm`` grouped expert matmul's share of its roofline.

The least time the chip could take for the window's expert matmuls (the
larger bound, FLOPs over peak or bytes over bandwidth, of the decode
calls and of the prefill calls of each prompt bucket, each set summed as
``roofline_mla_moe.moe_gmm_calls`` counts it from the routing counter)
over the summed device time of the kernel's events (HLO custom calls named
``moe_gmm``). The calls of one set have the same shape and are bound by
the same side; a set's bound is never above the sum of its calls' bounds,
so the share is never counted high.
"""
import roofline as R
from tracereduce import op_short

SOURCE = "device_trace"


def _is_kernel(op) -> bool:
    return op_short(op.name) == "moe_gmm" and "custom-call" in op.name


def read(ctx):
    calls = ctx.counters.get("moe_gmm_calls")
    ns, n = ctx.trace.op_ns(_is_kernel)
    if not calls or not n or ns <= 0:
        return None
    return 100.0 * R.sum_bounds(calls, ctx.peaks) / (ns / 1e9)
