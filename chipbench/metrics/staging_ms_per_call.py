"""Staging: host milliseconds of a ``run_batch`` call that the device
spends outside its block program, averaged over the window's calls.
Each ``run_batch`` span minus the device-busy time inside it."""

from chipbench import trace


def read(ctx):
    calls = ctx.spans("run_batch")
    if not calls:
        return None
    ops = ctx.chip_ops()
    total = sum(c.dur - trace.busy_s(ops, c.start, c.end) for c in calls)
    return 1e3 * total / len(calls)
