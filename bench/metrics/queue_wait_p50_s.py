"""Median wait in the scheduler's queue (serve/engine.py): from a request's
due time to the start of the engine.tick() that admitted it, over the
requests due inside the window (host clock; the harness reads the engine
after every tick).  One never admitted counts to the end of the drain."""
import numpy as np


def read(ctx):
    waits = [(r.admitted if r.admitted is not None else ctx.t_end) - r.due
             for r in ctx.window_recs()]
    return float(np.median(waits)) if waits else None
