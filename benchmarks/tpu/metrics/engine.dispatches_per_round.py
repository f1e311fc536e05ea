"""Round-program dispatches per global round of the traced job: the
count of ``engine.dispatch_s`` observations, less the profiling
warm-up's."""


def read(ctx):
    tel = ctx.telemetry
    if tel is None or not ctx.traced_rounds:
        return None
    total = sum(h.count for k, h in tel.histograms.items()
                if k.startswith("engine.dispatch_s"))
    warm = sum(1 for d in ctx.dispatches if d["profile"])
    return (total - warm) / ctx.traced_rounds
