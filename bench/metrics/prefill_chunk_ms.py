"""Device time of one prefill chunk call (jit__prefill_fn) in the traced
slice, in milliseconds (device trace)."""


def read(ctx):
    pre = ctx.program_seconds("jit__prefill_fn")
    return None if pre is None else 1e3 * pre[0] / pre[1]
