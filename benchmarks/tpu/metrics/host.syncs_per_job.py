"""Host reads of device values in the traced job: the ``host.syncs``
counter summed over its sites (probe norms, KL pairs, the engine's loss
fetch, the global delta's leaves, the eval accuracy)."""


def read(ctx):
    tel = ctx.telemetry
    syncs = tel.counters_by_name("host.syncs") if tel is not None else {}
    return float(sum(syncs.values())) if syncs else None
