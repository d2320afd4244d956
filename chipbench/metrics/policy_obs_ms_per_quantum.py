"""Policy bridge: the program's ``policy_obs`` phase (the bridge builds
the observation, its history window and the slot view before the act),
milliseconds per quantum over the cells."""

from chipbench.metrics import _phase


def read(ctx):
    return _phase.per_quantum(ctx, "policy_obs")
