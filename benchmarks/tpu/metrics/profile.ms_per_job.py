"""Host time of the traced job's profiling phase (warm-up round, probe
fingerprints, KL clustering, edge assignment): the ``profile`` span,
which ends in host numpy, so its device work is done."""


def read(ctx):
    spans = ctx.spans("profile")
    return 1e3 * sum(spans) if spans else None
