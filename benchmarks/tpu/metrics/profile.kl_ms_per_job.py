"""Self time of the traced job's divergence matrix: the ``profile.kl``
span (one jitted program over every client pair, and one host read of
the whole matrix) less the time its child spans cover."""
import program_spans as ps


def read(ctx):
    secs = ps.self_seconds(ps.records(ctx.telemetry), "profile.kl")
    return 1e3 * sum(secs) if secs else None
