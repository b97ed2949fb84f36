"""Decode's share of its roofline: the least time the chip could take for
the decode ticks of the traced slice (bench/flops.py: every weight once a
tick and the live keys and values, or the operations, at the chip's peak),
over the device time of jit__decode_fn there."""
from bench import flops


def read(ctx):
    dec = ctx.program_seconds("jit__decode_fn")
    if dec is None:
        return None
    least = 0.0
    for t in ctx.traced_ticks():
        for j in range(t.steps):
            ctxs = [c + j for c, k in t.decoded if k > j]
            if ctxs:
                least += flops.least_seconds(
                    *flops.decode_tick(ctx.model, ctxs), ctx.peak)
    return 100.0 * least / dec[0] if least else None
