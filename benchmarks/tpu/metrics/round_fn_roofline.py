"""Share of its roofline that the local-step program (the jitted
vmap/scan ``round_fn``) reaches in the traced job: the least time of
every dispatch, profiling's warm-up included, at the chip's peaks
(operations and bytes from ``flops.py``), over those programs' device
time in the trace. Float32 operands at default precision run one
bfloat16 pass, so the compute peak is the bfloat16 one."""
import flops
import trace_reduce as tr

MODULE = "jit_round_fn"


def bound(ctx):
    """"compute" or "memory": which peak the traced dispatches meet."""
    kinds = {least(ctx, d)[1] for d in ctx.dispatches}
    return "/".join(sorted(kinds)) or None


def least(ctx, d):
    f = ctx.cell.traffic["federation"]
    return flops.dispatch_least_seconds(
        ctx.cell.config, f, d["steps"], d["clients"], d["batch"], d["seq"],
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])


def read(ctx):
    if not ctx.events or not ctx.dispatches or not ctx.peaks:
        return None
    device = tr.module_seconds(ctx.events, MODULE, ctx.trace_lo,
                               ctx.trace_hi)
    if device <= 0:
        return None
    return 100.0 * sum(least(ctx, d)[0] for d in ctx.dispatches) / device
