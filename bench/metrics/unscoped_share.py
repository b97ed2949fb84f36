"""Share of jit__decode_fn's device time in the traced slice spent in ops
under none of the step programs' leaf scopes (models/transformer.py):
the layer scan's slicing and stacking of the page pool, and ops the
compiler added (device trace).  None where the program carries no leaf
scopes."""
from bench import engine_trace

engine_trace.install()


def read(ctx):
    return engine_trace.scope_share(ctx.trace, "jit__decode_fn",
                                    engine_trace.UNSCOPED)
