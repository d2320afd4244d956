"""The readers of the program's wall-clock phases, on a stub context with
known counter deltas."""
import json

import pytest

from chipbench import harness

PER_QUANTUM = ("admission", "policy_obs", "placement", "accounting", "fleet")
PER_CALL = ("stage_in", "launch", "device_wait", "readback")


class Stub:
    """What the phase readers read of a ``harness.Context``: the window's
    quanta and the program's counter deltas."""

    def __init__(self, quanta, after, before=None):
        self.steps = [object()] * quanta
        self.after, self.before = after, before or {}

    counter_delta = harness.Context.counter_delta


@pytest.mark.parametrize("phase", PER_QUANTUM)
def test_per_quantum_reader(phase):
    ctx = Stub(40, {f"{phase}_ms.total": 130.0, f"{phase}_ms.count": 320.0},
               {f"{phase}_ms.total": 10.0, f"{phase}_ms.count": 8.0})
    value = harness.read_metric(f"{phase}_ms_per_quantum", ctx)
    assert value == pytest.approx(120.0 / 40)


@pytest.mark.parametrize("phase", PER_CALL)
def test_per_call_reader(phase):
    ctx = Stub(40, {f"{phase}_ms.total": 250.0, f"{phase}_ms.count": 130.0},
               {f"{phase}_ms.total": 10.0, f"{phase}_ms.count": 10.0})
    value = harness.read_metric(f"{phase}_ms_per_call", ctx)
    assert value == pytest.approx(240.0 / 120)


@pytest.mark.parametrize("name", [f"{p}_ms_per_quantum" for p in PER_QUANTUM]
                         + [f"{p}_ms_per_call" for p in PER_CALL])
def test_reader_of_a_program_without_the_phase_reads_none(name):
    # a program without the phase has no histogram (and an untraced
    # run no registry): the metric is left out, nothing raises
    ctx = Stub(40, {"policy_act_batch_ms.total": 50.0,
                    "policy_act_batch_ms.count": 40.0})
    assert harness.read_metric(name, ctx) is None


def test_every_phase_reader_is_declared():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in ([f"{p}_ms_per_quantum" for p in PER_QUANTUM]
                 + [f"{p}_ms_per_call" for p in PER_CALL]):
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_counter", "images_per_s")
        assert "workloads" not in m
