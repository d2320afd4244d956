"""The reduction from trace to metrics: on made-up events whose answers
are known, and on a small profile recorded on a v5e
(``data/tpu_small.xplane.pb``, written by ``record_profile.py``)."""
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.trace import SPAN, Event

DATA = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"


def ev(name, a, b):
    return Event(name, a, b)


OPS = [ev("fusion.1", 1.0, 2.0), ev("fusion.2", 1.5, 2.5),
       ev("flash_attention", 3.0, 3.5), ev("copy", 6.0, 6.25),
       ev("adaln_norm_epilogue", 9.5, 11.0)]


def test_busy_is_the_union_clipped_to_the_window():
    # [1, 2.5] + [3, 3.5] + [6, 6.25] + [9.5, 10] inside [0, 10]
    assert trace.busy_s(OPS, 0.0, 10.0) == pytest.approx(1.5 + 0.5 + 0.25
                                                         + 0.5)
    assert trace.busy_s(OPS, 1.75, 3.25) == pytest.approx(0.75 + 0.25)


def test_idle_gaps_and_busy_fill_the_window():
    gaps = trace.idle_gaps(OPS, 0.0, 10.0)
    assert gaps == [(0.0, 1.0), (2.5, 3.0), (3.5, 6.0), (6.25, 9.5)]
    assert sum(b - a for a, b in gaps) + trace.busy_s(OPS, 0.0, 10.0) \
        == pytest.approx(10.0)


def test_kernel_time_counts_named_ops_that_start_inside():
    assert trace.kernel_s(OPS, ("flash_attention",), 0, 10) == (0.5, 1)
    # names match whole: adaln_norm is not adaln_norm_epilogue
    assert trace.kernel_s(OPS, ("adaln_norm",), 0, 10) == (0.0, 0)
    both = ("adaln_norm", "adaln_norm_epilogue")
    assert trace.kernel_s(OPS, both, 0, 10) == (1.5, 1)
    assert trace.kernel_s(OPS, both, 0, 9) == (0.0, 0)


def test_gaps_go_to_the_innermost_span():
    spans = [ev(SPAN + "ClusterEngine.step", 0.5, 9.0),
             ev(SPAN + "begin_step", 0.5, 1.0),
             ev(SPAN + "run_batch", 2.4, 3.6),
             ev(SPAN + "end_step", 3.6, 6.1)]
    by = trace.attribute_gaps(trace.idle_gaps(OPS, 0.0, 10.0), spans,
                              outside="between")
    assert by == pytest.approx({"begin_step": 1.0, "run_batch": 0.5,
                                "end_step": 2.5, "ClusterEngine.step": 3.25})
    # the gap from 0 to 1 has its midpoint at 0.5: inside begin_step
    by = trace.attribute_gaps([(0.0, 0.4)], spans, outside="between")
    assert by == {"between": 0.4}


def test_merge_handles_nesting_and_touching():
    assert trace.merge([(0, 1), (1, 2), (0.5, 0.7), (3, 4)]) == [(0, 2),
                                                                 (3, 4)]


def test_op_name_is_the_instruction_without_its_number():
    e = ev("%flash_attention.5 = f32[4,4,64,16] custom-call(f32[4] "
           "%jit_adaln_norm_.12)", 0, 1)
    assert trace.op_name(e) == "flash_attention"
    assert trace.matches(e, ("flash_attention",))
    # an operand's name is not the operation's
    assert not trace.matches(e, ("adaln_norm",))
    assert trace.op_name(ev("%copy", 0, 1)) == "copy"


def test_host_lag_puts_the_earliest_program_at_its_launch():
    runs = [ev("jit_run(1)", 0.9, 1.0), ev("jit_run(1)", 2.7, 2.8)]
    spans = [ev(SPAN + "run_batch", 1.0, 1.5), ev(SPAN + "run_batch", 3.0,
                                                   3.5)]
    assert trace.host_lag(runs, spans) == pytest.approx(0.3)
    assert trace.host_lag(runs[:1], spans) is None


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.skip("no recorded profile")
    prof = trace.load(str(DATA))
    (chip, ops), = prof.ops.items()
    runs = [m for m in prof.modules[chip] if m.name.startswith("jit_run(")]
    launches = [s for s in prof.spans if s.name == SPAN + "run_batch"]
    lag = trace.host_lag(runs, launches)
    return prof, trace.shift(ops, lag), lag


def test_recorded_profile_has_one_chip_and_the_spans(recorded):
    prof, _, lag = recorded
    names = [s.name for s in prof.spans]
    assert names.count(SPAN + "run_batch") == 2
    assert names.count(SPAN + "sleep") == 2
    # the device clock lags the host's by a fraction of a millisecond
    assert 0 < lag < 2e-3


def test_recorded_kernels_run_inside_their_block_calls(recorded):
    prof, ops, _ = recorded
    for span in (s for s in prof.spans if s.name == SPAN + "run_batch"):
        for kernel in ("flash_attention", "adaln_norm",
                       "adaln_norm_epilogue"):
            seconds, n = trace.kernel_s(ops, (kernel,), span.start, span.end)
            # a 2-layer DiT: one call of each kernel per layer
            assert n == 2 and 0 < seconds < span.dur
        busy = trace.busy_s(ops, span.start, span.end)
        assert 0 < busy < span.dur


def test_recorded_sleep_is_idle(recorded):
    prof, ops, _ = recorded
    step = next(s for s in prof.spans
                if s.name == SPAN + "ClusterEngine.step")
    gaps = trace.idle_gaps(ops, step.start, step.end)
    by = trace.attribute_gaps(gaps, prof.spans)
    assert by["sleep"] >= 2 * 0.019
    assert sum(by.values()) + trace.busy_s(ops, step.start, step.end) \
        == pytest.approx(step.dur)


def test_recorded_top_ops_leave_out_the_loop(recorded):
    prof, ops, _ = recorded
    names = [n for n, _ in trace.top_ops(ops, 0, 1e9, n=50)]
    assert "while" not in names and "flash_attention" in names
