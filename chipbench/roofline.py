"""A kernel's share of its roofline, for the ``metrics/*_roofline.py`` readers.

The least time the chip could take for the window's calls of the kernel,
each the larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (``kernels/<kernel>.py:cost``, at the bucket rows the kernel ran
on), over the device time of the kernel's operations in the trace.  The
kernel's shape, and with it its calls per denoise step, comes from the
configuration's work module, which the reader passes on.  Which of the two
bounds dominates is noted on standard error.  No operation of the kernel
in the trace: nothing to read.
"""

from chipbench import harness, trace


def share(ctx, kernel: str, shape: dict):
    mod = harness.kernel_module(kernel)
    measured, n = trace.kernel_s(ctx.chip_ops(), mod.NAMES, ctx.lo, ctx.hi)
    if n == 0 or measured <= 0:
        return None
    per_block = shape["calls_per_step"] * ctx.steps_per_block
    peak = ctx.peak("bf16_flops_per_s")
    bw = ctx.peak("hbm_bytes_per_s")
    costs = [mod.cost(ctx.model, shape, c["bucket"]) for c in ctx.calls]
    compute = memory = 0.0
    for flops, nbytes in costs:
        compute += per_block * flops / peak
        memory += per_block * nbytes / bw
    bound = sum(max(per_block * f / peak, per_block * b / bw)
                for f, b in costs)
    ctx.notes[f"{kernel}_roofline"] = (
        f"{n} ops, {measured:.6f} s on the device; bound {bound:.6f} s "
        f"(compute {compute:.6f} s, memory {memory:.6f} s: "
        f"{'compute' if compute >= memory else 'memory'}-bound)")
    return 100.0 * bound / measured
