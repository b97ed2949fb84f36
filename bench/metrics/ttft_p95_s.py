"""95th percentile of time to first token over every request due inside
the window, from its due time (host clock).  A request that never got a
token counts at its time from due to the end of the drain."""
import numpy as np


def read(ctx):
    recs = ctx.window_recs()
    if not recs:
        return None
    return float(np.percentile(
        [(r.first if r.first is not None else ctx.t_end) - r.due
         for r in recs], 95))
