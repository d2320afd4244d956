"""Fleet control plane: the program's ``fleet`` phase (the cluster's
handovers, its grouping of the plans by service and the write-back of
block results), milliseconds per quantum."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_quantum(ctx, "fleet")
