"""Staging: the program's ``readback`` phase (device-to-host copy of the
new latents and x0, the output states, the quality gather), mean
milliseconds per call."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_call(ctx, "readback")
