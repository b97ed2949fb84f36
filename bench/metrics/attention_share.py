"""Share of jit__decode_fn's device time in the traced slice spent in ops
under the ``attention`` scope (models/transformer.py: the paged attention
op), of all its innermost ops (device trace).  None where the program
carries no leaf scopes."""
from bench import engine_trace

engine_trace.install()


def read(ctx):
    return engine_trace.scope_share(ctx.trace, "jit__decode_fn", "attention")
