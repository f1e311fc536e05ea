"""Host time of the local steps per global round of the traced job: the
``local_steps`` spans, each ending in the engine's one host fetch of the
round's losses."""


def read(ctx):
    spans = ctx.spans("local_steps")
    if not spans or not ctx.traced_rounds:
        return None
    return 1e3 * sum(spans) / ctx.traced_rounds
