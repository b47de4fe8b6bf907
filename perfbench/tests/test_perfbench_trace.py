"""The trace reduction on a hand-built trace."""
import pytest

from perfbench.lib import trace
from perfbench.metrics import idle_share

DEVICES = {"/device:TPU:0": [
    ("fusion.1", 0, 100),
    ("_fused_padded.3", 150, 50),
    ("fusion.2", 180, 40),             # overlaps the kernel
    ("while.7", 140, 90),              # spans the two above
    ("fusion.3", 390, 30),             # runs past the window's end
    ("fusion.4", 500, 10),             # outside the window
]}
SPANS = [("bench.window", 0, 400), ("bench.engine_step", 0, 250),
         ("bench.submit", 120, 10)]


@pytest.fixture
def tr():
    return trace.from_events(DEVICES, SPANS)


def test_busy_is_the_union_of_operations_inside_the_window(tr):
    assert trace.busy_intervals(tr.devices["/device:TPU:0"], tr.window) \
        == [[0, 100], [140, 230], [390, 400]]
    assert trace.busy_s(tr) == pytest.approx(200e-9)
    assert tr.window_s == pytest.approx(400e-9)


def test_idle_gaps_are_attributed_to_the_innermost_open_span(tr):
    assert trace.idle_gaps(tr) == [
        ("no span", pytest.approx(160e-9)),
        ("bench.submit", pytest.approx(40e-9))]


def test_device_time_by_operation_name_is_clipped_to_the_window(tr):
    got = dict(trace.op_seconds(tr))
    assert got["fusion.1"] == pytest.approx(100e-9)
    assert got["fusion.3"] == pytest.approx(10e-9)
    assert "fusion.4" not in got and "while.7" not in got


def test_kernel_events_match_by_instruction_name_prefix(tr):
    assert [o.name for o in trace.matching(tr, ("_fused_padded",))] \
        == ["_fused_padded.3"]
    assert trace.op_name("%_fused_padded.17 = f32[32,1,18944]{2,1,0} "
                         "custom-call(bf16[2,32,1,1792] %pad.17)") \
        == "_fused_padded.17"


def test_idle_share_reads_the_trace(tr):
    class Run:
        trace = None
    assert idle_share.read(Run) is None
    Run.trace = tr
    assert idle_share.read(Run) == pytest.approx(100 * (1 - 200 / 400))


def test_a_trace_needs_exactly_one_window_span():
    with pytest.raises(ValueError):
        trace.from_events(DEVICES, [("bench.engine_step", 0, 10)])
