"""Fleet control plane: host milliseconds per quantum outside the block
calls.  The ``ClusterEngine.step`` span minus the ``run_batch`` spans it
holds, summed over the window's quanta, over the number of quanta."""


def read(ctx):
    steps = ctx.steps
    calls = ctx.spans("run_batch")
    total = sum(s.dur for s in steps) - sum(c.dur for c in calls)
    return 1e3 * total / len(steps)
