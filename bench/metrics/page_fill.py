"""Share of the mapped pages' positions that hold live cache
(serve/paging.py): after every tick in the window, the positions the
active requests hold over mapped pages x page size, averaged."""


def read(ctx):
    fills = [t.live_tokens / (t.mapped_pages * ctx.page)
             for t in ctx.ticks[:ctx.n_window_ticks] if t.mapped_pages]
    return 100.0 * sum(fills) / len(fills) if fills else None
