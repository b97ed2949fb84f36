"""Output tokens emitted inside the window, over the window (host clock)."""


def read(ctx):
    return ctx.tokens_window / ctx.window_s
