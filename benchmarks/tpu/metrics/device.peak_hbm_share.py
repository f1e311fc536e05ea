"""``peak_bytes_in_use`` of the fullest chip after the window over the
chip's HBM in the peaks table."""


def read(ctx):
    if not ctx.memory_peak_bytes or not ctx.peaks:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.peaks["hbm_bytes"]
