"""Staging: the program's ``launch`` phase (the jitted block call's
dispatch, with the host-to-device copy of the staged buffers), mean
milliseconds per call."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_call(ctx, "launch")
