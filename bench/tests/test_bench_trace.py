"""The trace reduction on a small synthetic trace: device busy time as a
union of intervals, kernels found by what they are and not by name, idle
gaps named by the harness span the host was in."""
import pytest

from _tiny import BENCH  # noqa: F401 -- puts the benchmark on the path

import devtrace
from devtrace import Event

DEV = "/device:TPU:0"


def _op(name, start, dur, plane=DEV, **stats):
    return Event(plane, "XLA Ops", name, start, dur, stats)


def _span(name, start, dur):
    return Event("/host:CPU", "python", name, start, dur, {})


def test_union_counts_overlap_once():
    assert devtrace.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25


def test_summary_of_a_small_trace():
    events = [
        _span("bench.window", 0, 1000),
        _span("bench.query", 0, 400),
        _span("bench.query", 500, 500),
        _op("custom-call.1", 100, 100,
            long_name='custom_call_target="tpu_custom_call"'),
        _op("fusion.2", 150, 100),                  # overlaps the kernel
        _op("%my_kernel_renamed.3 = f32[3,128]{1,0} custom-call(f32[5,24]"
            " %axes.1), custom_call_target=\"tpu_custom_call\"", 600, 200),
        _op("copy.3", 950, 100),                    # runs past the window
        _op("noise", 100, 50, plane="/device:TPU_NON_CORE:0"),
    ]
    s = devtrace.summarize(events)
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100, 250) + [600, 800) + [950, 1000) inside the window
    assert s.busy_s == pytest.approx(400e-9)
    assert s.kernel_launches == 2
    assert s.kernel_s == pytest.approx(300e-9)
    assert s.device_ops[0][0] == "my_kernel_renamed.3"
    gaps = dict(s.idle_gaps)
    # Idle: [0, 100) in the first query; [250, 600) split at the query
    # boundaries into 150 + 100 (between the queries) + 100; [800, 950).
    assert gaps["query after window start"] == pytest.approx(100e-9)
    assert gaps["query after fusion.2"] == pytest.approx(250e-9)
    assert gaps["window after fusion.2"] == pytest.approx(100e-9)
    assert gaps["query after my_kernel_renamed.3"] == pytest.approx(150e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_device_op_or_no_span_gives_nothing():
    assert devtrace.summarize([_span("bench.window", 0, 10)]) is None
    assert devtrace.summarize([_op("fusion", 0, 10)]) is None
