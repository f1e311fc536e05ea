"""Share of the traced job in which no operation ran on the device:
one minus the union of the XLA Ops intervals over the job's span."""
import trace_reduce as tr


def read(ctx):
    if not ctx.events or ctx.trace_hi <= ctx.trace_lo:
        return None
    if not tr.device_planes(ctx.events):
        return None
    busy = tr.busy_seconds(ctx.events, ctx.trace_lo, ctx.trace_hi)
    return 100.0 * (1.0 - busy / ((ctx.trace_hi - ctx.trace_lo) / 1e9))
