"""Host time of eval per global round of the traced job: the ``eval``
span, which ends with the accuracy on the host."""


def read(ctx):
    spans = ctx.spans("eval")
    if not spans or not ctx.traced_rounds:
        return None
    return 1e3 * sum(spans) / ctx.traced_rounds
