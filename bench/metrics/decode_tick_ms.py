"""Device time of jit__decode_fn in the traced slice over the decode ticks
it ran (the engine's decode_steps counter), in milliseconds."""


def read(ctx):
    dec = ctx.program_seconds("jit__decode_fn")
    steps = sum(t.steps for t in ctx.traced_ticks())
    return None if dec is None or not steps else 1e3 * dec[0] / steps
