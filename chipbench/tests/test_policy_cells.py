"""The reader of the cells per policy act, on a stub context with known
counter deltas."""
import json

import pytest

from chipbench import harness


class Stub:
    """What the reader reads of a ``harness.Context``: the program's
    counter deltas."""

    def __init__(self, after, before=None):
        self.after, self.before = after, before or {}

    counter_delta = harness.Context.counter_delta


def test_cells_per_act_over_the_window():
    # 40 pooled acts of 8 cells in the window, after 2 acts of 2 in set-up
    ctx = Stub({"policy_act_cells": 324.0, "policy_act_batch_ms.count": 42.0},
               {"policy_act_cells": 4.0, "policy_act_batch_ms.count": 2.0})
    assert harness.read_metric("policy_cells_per_act", ctx) == \
        pytest.approx(8.0)


@pytest.mark.parametrize("after", [
    {"policy_act_batch_ms.count": 40.0},         # a program without it
    {},                                          # an untraced registry
], ids=["no-counter", "nothing"])
def test_a_program_without_the_counter_reads_none(after):
    assert harness.read_metric("policy_cells_per_act", Stub(after)) is None


def test_the_reader_is_declared():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"]
            if m["name"] == "policy_cells_per_act"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("cells", "higher", "program_counter", "policy bridge",
            "images_per_s")
    assert "workloads" not in m
