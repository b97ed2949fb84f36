"""95th percentile, over the requests due inside the window that emitted
two tokens or more, of (last token time - first token time) / (tokens - 1),
in milliseconds (host clock, times at which engine.tick() returned)."""
import numpy as np


def read(ctx):
    gaps = [(r.last - r.first) / (r.seen - 1) * 1e3
            for r in ctx.window_recs() if r.first is not None and r.seen >= 2]
    return float(np.percentile(gaps, 95)) if gaps else None
