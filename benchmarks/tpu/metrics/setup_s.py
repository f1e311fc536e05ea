"""Process start to the start of the window: building the federation,
and one whole warm job that compiles or loads every program the window
runs (host clock)."""


def read(ctx):
    return ctx.setup_s
