"""A rapid switch's share of its roofline.

The least time the window's switches could take (per nonzero of the
unloaded and of the loaded pack: one read and one write of the weight
element and one read of its value, over HBM bandwidth) over the device
busy time inside the ``bench.switch`` marks. The mark spans the whole
switch, so this share is the switch's whole-operation share: no kernel
inside it can leave it silent.
"""
SOURCE = "device_trace"


def read(ctx):
    moved = ctx.counters.get("switch_bytes")
    busy = sum(ctx.trace.busy_in("bench.switch"))
    if not moved or busy <= 0:
        return None
    return 100.0 * (moved / ctx.peaks["hbm_bytes_per_s"]) / (busy / 1e9)
