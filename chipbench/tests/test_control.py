"""The control comes out not correct: the reference computed in bfloat16
(the precision below the configuration's float32), put in the program's
place on the rows a window served, fails the configuration's limits, in
every cell of ``BENCHMARK.json``.

On the chip, at the cell's own size, ``calibrate.py`` reads the same
control on three seeds or more; here a small DiT on the CPU stands in.
"""
import pytest

from chipbench import harness
from chipbench.tests import small

WORKLOADS = [w["name"] for w in
             harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bfloat16_control_fails_the_limits(workload):
    c = small.cell(workload)
    line = small.run(c, 2**31 + 77, control=True)
    limits = {k: v["limit"] for k, v in line["check"].items()}
    control = line["control"][
        f"control@{c.config['check']['reference_precision']}"]
    assert line["correct"] is True
    assert any(control[k] > limits[k] for k in limits), (control, limits)
