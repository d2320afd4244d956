"""What the readers of the program's wall-clock phases share.  A phase
``<name>`` (``repro.serving.tracing.phase``) observes the milliseconds of
each run into the program's histogram ``<name>_ms``; a program without
that phase has no such histogram, and its readers read None."""


def _delta(ctx, name):
    return (ctx.counter_delta(f"{name}_ms.total"),
            ctx.counter_delta(f"{name}_ms.count"))


def per_quantum(ctx, name):
    """The phase's milliseconds in the window over its quanta (its
    ``ClusterEngine.step`` spans), summed over the cells."""
    total, n = _delta(ctx, name)
    if n <= 0:
        return None
    return total / len(ctx.steps)


def per_call(ctx, name):
    """The phase's mean milliseconds in the window."""
    total, n = _delta(ctx, name)
    if n <= 0:
        return None
    return total / n
