"""Seconds from process start to the window's start: imports, weights,
engine, warm-up and compilation (or loading from the compile cache), and
for a closed loop the filling of every slot."""


def read(ctx):
    return ctx.setup_s
