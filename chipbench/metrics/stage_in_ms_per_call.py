"""Staging: the program's ``stage_in`` phase (a block call's bucket lookup,
row copies into the staging buffers and index buffer), mean milliseconds
per call."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_call(ctx, "stage_in")
