"""95th percentile, over the requests due inside the window, of the time
from a request's admission to its first token on the host
(serve/engine.py: ``Request.t_first - Request.t_admit``, the chunked
prefill and the batched first-token sync; host clock).  None where the
engine keeps no such timestamps."""
import numpy as np


def read(ctx):
    waits = [r.req.t_first - r.req.t_admit for r in ctx.window_recs()
             if getattr(r.req, "t_first", None) is not None]
    return float(np.percentile(waits, 95)) if waits else None
