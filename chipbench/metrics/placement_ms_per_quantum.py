"""Fleet control plane: the program's ``placement`` phase (``plan_step``
after the policy's decision: placement loop, transmission charging,
compute spans, join counting), milliseconds per quantum over the cells."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_quantum(ctx, "placement")
