"""95th percentile of the wait for a decode slot (serve/engine.py): from
``Request.t_submit`` to ``Request.t_admit``, the engine's own timestamps
on the host clock, over the requests due inside the window.  One never
admitted counts to the end of the drain.  None where the engine keeps no
such timestamps."""
import numpy as np


def read(ctx):
    waits = []
    for r in ctx.window_recs():
        submit = getattr(r.req, "t_submit", None)
        if submit is not None:
            admit = r.req.t_admit
            waits.append((ctx.t_end if admit is None else admit) - submit)
    return float(np.percentile(waits, 95)) if waits else None
