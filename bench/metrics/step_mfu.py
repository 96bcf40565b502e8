"""The whole serving step's share of the chip's bf16 peak.

Model FLOPs of every token prefilled and decoded in the traced window
(``roofline.token_flops``: 2 per matmul weight, attention at each token's
context, the output head where logits are needed, 2 per adapter nonzero
where an adapter rides as a side delta) over the window's length times
the peak.
"""
SOURCE = "device_trace"


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if not flops or ctx.trace.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
