"""The reduction from a profiler trace to device busy time, program and
kernel time and idle gaps: on hand-built intervals, and on a small trace
recorded on one TPU v5e (``testdata/record_trace.py``)."""
import os

import pytest

from bench import trace_reduce
from bench.trace_reduce import DeviceTrace

TPU_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "testdata", "tpu_window.xplane.pb")


def _hand_trace():
    # window 0..100 ns; ops overlap (10-30, 20-40) and one spills past it
    ops = [(10, 30, "fusion.1"), (20, 40, "fusion.2"),
           (60, 70, "gf256_rs_encode"), (95, 120, "fusion.1")]
    modules = [(10, 40, "jit_step(3)"), (60, 70, "jit__encode_tiles(1)"),
               (95, 120, "jit_step(3)")]
    return DeviceTrace(window=(0.0, 100.0), ops=[ops], modules=[modules])


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _hand_trace()
    assert t.busy_intervals(0) == [(10, 40), (60, 70), (95, 100)]
    assert t.busy_s == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_program_and_kernel_time_by_name():
    t = _hand_trace()
    assert t.program_time("jit_step") == (pytest.approx(35e-9), 2)
    assert t.op_time("gf256_rs_encode") == (pytest.approx(10e-9), 1)
    assert t.program_time("jit_other") == (0.0, 0)
    assert t.top_ops(2)[0] == ("jit_step/fusion.1", pytest.approx(25e-9))


def test_idle_gaps_longest_first_and_named_by_the_host():
    t = _hand_trace()
    gaps = t.idle_gaps(0)
    assert gaps == [(70, 95), (40, 60), (0, 10)]
    host = [(41.0, 58.0, "recovery.fetch"), (65.0, 65.0, "persist.commit"),
            (0.0, 5.0, "iteration.step")]
    assert trace_reduce.label_gaps(gaps, host) == [
        ("after persist.commit", pytest.approx(25e-9)),
        ("recovery.fetch", pytest.approx(20e-9)),
        ("iteration.step", pytest.approx(10e-9))]


def test_a_trace_without_devices_reads_nothing():
    t = DeviceTrace(window=(0.0, 10.0))
    assert t.ndevices == 0 and t.busy_s == 0.0


def test_recorded_tpu_trace():
    t = trace_reduce.load(TPU_TRACE)
    assert t.ndevices == 1
    assert 0.15 < t.window_s < 1.0  # four 50 ms sleeps and the calls
    assert 0.0 < t.busy_s < t.window_s
    secs, count = t.program_time("jit_step")
    # three steps ran; the first shows 0.9 ms before the window opens, as
    # the device clock leads the host's, and is clipped away
    assert count == 2 and secs > 0
    ksecs, kcount = t.op_time("gf256_rs_encode")
    assert kcount == 1 and 0 < ksecs < t.busy_s
    gaps = t.idle_gaps(0)
    assert sum(g1 - g0 for g0, g1 in gaps) * 1e-9 == pytest.approx(
        t.window_s - t.busy_s)
    assert (gaps[0][1] - gaps[0][0]) * 1e-9 > 0.04
