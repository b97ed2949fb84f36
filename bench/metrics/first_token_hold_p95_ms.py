"""95th percentile, over the requests due inside the window, of how long
a first token already on the host waits for ``engine.tick()`` to return
(the rest of that tick's decode dispatch): the harness's first-token
time less ``Request.t_first`` (serve/engine.py), in milliseconds on the
host clock.  None where the engine keeps no such timestamp."""
import numpy as np


def read(ctx):
    holds = [(r.first - r.req.t_first) * 1e3 for r in ctx.window_recs()
             if r.first is not None
             and getattr(r.req, "t_first", None) is not None]
    return float(np.percentile(holds, 95)) if holds else None
