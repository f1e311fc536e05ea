"""Host work of the local steps per global round of the traced job: the
batch draws (``data.draw``), the padding, stacking and copies to the
device (``engine.stack``) and the slicing of the results
(``engine.unstack``), where profiling's warm-up is not an ancestor."""
import program_spans as ps

NAMES = ("data.draw", "engine.stack", "engine.unstack")


def read(ctx):
    secs = ps.outside(ps.records(ctx.telemetry), NAMES, "profile")
    if not secs or not ctx.traced_rounds:
        return None
    return 1e3 * sum(secs) / ctx.traced_rounds
