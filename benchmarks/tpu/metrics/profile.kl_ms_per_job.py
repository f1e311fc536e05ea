"""Self time of the traced job's divergence matrix: the ``profile.kl``
span (one eager symmetric KL per client pair, each read on the host)
less the time its child spans cover."""
import program_spans as ps


def read(ctx):
    secs = ps.self_seconds(ps.records(ctx.telemetry), "profile.kl")
    return 1e3 * sum(secs) if secs else None
