"""Fleet control plane: the program's ``accounting`` phase (delivery,
downlink, completion bookkeeping, telemetry event, frame advance),
milliseconds per quantum over the cells."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_quantum(ctx, "accounting")
