"""Fleet control plane: the program's ``admission`` phase
(``ServingEngine.begin_quantum``: deadline shedding, failure handling,
admission, degradation), milliseconds per quantum over the cells."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_quantum(ctx, "admission")
