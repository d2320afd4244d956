"""DiT: the window's share of the chip's bf16 peak spent on live rows.

FLOPs of every live (not padding) row of every block call of the window,
from the configuration's count of work (``kernels/<work>.py``), over the
traced window's seconds times the peak.  Float32 matmuls at the default
precision run as one bf16 pass on the TPU, so the bf16 peak is the
denominator."""


def read(ctx):
    work = ctx.work.flops_per_sample_step(ctx.model)
    rows = sum(c["rows"] for c in ctx.calls)
    if rows <= 0:
        return None
    flops = rows * ctx.steps_per_block * work
    return 100.0 * flops / (ctx.window_s * ctx.peak("bf16_flops_per_s"))
