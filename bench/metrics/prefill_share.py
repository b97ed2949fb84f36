"""Share of the step programs' device time that prefill takes:
jit__prefill_fn over jit__prefill_fn + jit__decode_fn, in the traced
slice (device trace)."""


def read(ctx):
    dec = ctx.program_seconds("jit__decode_fn")
    pre = ctx.program_seconds("jit__prefill_fn")
    if dec is None:
        return None
    p = pre[0] if pre else 0.0
    return 100.0 * p / (p + dec[0])
