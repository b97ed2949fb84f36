"""Model FLOP/s utilization of the traced slice: the model's operations for
every token it processed there (prompt tokens and decoded tokens, at their
context lengths; bench/flops.py), over the traced window times the chip's
peak bf16 rate."""
from bench import flops


def read(ctx):
    m, total = ctx.model, 0
    for t in ctx.traced_ticks():
        for c, k in t.decoded:
            total += sum(flops.token_flops(m, c + j, head=True)
                         for j in range(k))
        for p in t.prefilled:
            total += sum(flops.prefill_chunk(m, s, min(ctx.chunk, p - s),
                                             s + ctx.chunk >= p)[0]
                         for s in range(0, p, ctx.chunk))
    if not total:
        return None
    return 100.0 * total / (ctx.trace["window_s"]
                            * ctx.peak["bf16_flops_per_s"])
