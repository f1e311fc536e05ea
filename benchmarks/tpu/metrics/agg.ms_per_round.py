"""Host time of edge and cloud aggregation per global round of the
traced job: the ``edge_agg`` and ``cloud_agg`` spans. Edge aggregation
is eager and unsynced, so its device work lands in ``cloud_agg``, whose
``global_delta`` ends in a host float."""


def read(ctx):
    spans = ctx.spans("edge_agg") + ctx.spans("cloud_agg")
    if not spans or not ctx.traced_rounds:
        return None
    return 1e3 * sum(spans) / ctx.traced_rounds
