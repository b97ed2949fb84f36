"""Share of the weight stream's roofline in the decode step outside
attention: the least time to read every weight once per decode tick of
the traced slice (``bench/flops.py`` ``weight_bytes``, at the chip's
peak bytes per second), over the device seconds of jit__decode_fn's ops
under the scopes that hold the weight products (``attn_in``,
``attn_out``, ``mlp``, ``head``) and under none of the leaf scopes: on a
v5e the compiler moves part of the projections' weight reads into
prefetch and layout copies that carry only the layer scan's op names, so
those count with the products (device trace).  None where the program
carries no leaf scopes."""
from bench import engine_trace, flops

engine_trace.install()

PROGRAM = "jit__decode_fn"
WEIGHT_SCOPES = ("attn_in", "attn_out", "mlp", "head", engine_trace.UNSCOPED)


def read(ctx):
    if engine_trace.scope_share(ctx.trace, PROGRAM, "mlp") is None:
        return None
    times = ctx.trace["scopes"][PROGRAM]
    busy = sum(times.get(s, 0.0) for s in WEIGHT_SCOPES)
    steps = sum(t.steps for t in ctx.traced_ticks())
    if not busy or not steps:
        return None
    least = flops.weight_bytes(ctx.model) * steps / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / busy
