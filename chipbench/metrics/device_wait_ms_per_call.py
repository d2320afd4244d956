"""Block call: the program's ``device_wait`` phase (the host blocked on
the block program after its launch), mean milliseconds per call."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_call(ctx, "device_wait")
