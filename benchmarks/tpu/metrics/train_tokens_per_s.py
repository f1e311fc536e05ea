"""Tokens of real examples trained in the window's global-round local
steps, over the whole window (host clock). Profiling's warm-up and eval
take time and add no tokens."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.jobs:
        return None
    return ctx.tokens / ctx.window_s
