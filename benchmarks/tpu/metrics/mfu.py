"""Model operations of the window's jobs (``flops.py``: local steps,
profiling's warm-up and probes, eval) over the window times the chip's
bfloat16 peak. Float32 operands at default precision run one bfloat16
pass, so the bfloat16 peak is the denominator."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.peaks:
        return None
    return 100.0 * ctx.flops / (ctx.window_s * ctx.peaks["bf16_flops"])
