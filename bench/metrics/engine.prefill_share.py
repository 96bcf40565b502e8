"""Share of the engine's device time spent in chunked prefill.

Device busy time inside ``bench.prefill_chunk`` marks (each prefill chunk
the engine runs, waited for inside its mark in traced runs) over device
busy time inside ``bench.engine_step`` marks. 0 when the window's steps
ran no prefill chunk.
"""
SOURCE = "device_trace"


def read(ctx):
    steps = sum(ctx.trace.busy_in("bench.engine_step"))
    if steps <= 0:
        return None
    return 100.0 * sum(ctx.trace.busy_in("bench.prefill_chunk")) / steps
