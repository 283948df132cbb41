"""The trace reduction on a small synthetic trace."""
import numpy as np
import pytest

from bench import xplane


def _events(rows):
    return xplane.Events.of(rows)


@pytest.fixture
def trace():
    # window [100, 200) ns; ops overlap, one sticks out of the window
    ops = _events([
        (90, 110, "fusion.1", "jit_step"),
        (105, 120, "fusion.2", "jit_step"),       # overlaps the first
        (130, 150, "synapse_attention", "jit_step"),
        (160, 170, "synapse_attention", "jit_step"),
        (190, 230, "copy.3", "jit_spawn"),        # clipped at 200
    ])
    spans = [("fe.step", 95, 155), ("fe.submit", 150, 185), ("gen.sleep", 185, 200)]
    return xplane.Trace((100.0, 200.0), [ops], [_events([])], spans)


def test_union_and_busy(trace):
    assert trace.busy_intervals(0).tolist() == [[100, 120], [130, 150], [160, 170], [190, 200]]
    assert trace.busy_s() == pytest.approx(60e-9)
    assert trace.window_s == pytest.approx(100e-9)


def test_idle_gaps_named_by_open_span(trace):
    gaps = trace.idle_gaps(0)
    assert [(n, round(s * 1e9)) for n, s in gaps] == [
        ("fe.step", 10),     # 120-130: inside fe.step
        ("fe.submit", 10),   # 150-160: fe.submit is the innermost open span
        ("fe.submit", 20),   # 170-190: midpoint 180 in fe.submit
    ]
    assert trace.top_gaps() == [["fe.submit", pytest.approx(30e-9)],
                                ["fe.step", pytest.approx(10e-9)]]


def test_kernel_time_by_name(trace):
    sec, n = trace.op_seconds(lambda name, mod: name == "synapse_attention")
    assert n == 2 and sec == pytest.approx(30e-9)
    sec, n = trace.op_seconds(lambda name, mod: mod == "jit_spawn")
    assert n == 1 and sec == pytest.approx(10e-9)


def test_top_ops_inside_the_window(trace):
    top = dict((k, v) for k, v in trace.top_ops())
    assert top["jit_step/synapse_attention"] == pytest.approx(30e-9)
    assert top["jit_step/fusion.2"] == pytest.approx(15e-9)
    assert "jit_step/fusion.1" not in top  # began before the window


def test_holes_cover_the_window():
    busy = xplane.union(np.array([[5.0, 7.0], [1.0, 3.0], [2.0, 4.0]]))
    assert busy.tolist() == [[1, 4], [5, 7]]
    assert xplane.holes(busy, 0.0, 10.0) == [(0.0, 1.0), (4.0, 5.0), (7.0, 10.0)]
    assert xplane.span_at([], 3.0) == "none"


def test_self_time_of_nested_ops_and_names():
    start = np.array([0.0, 10.0, 20.0, 60.0, 100.0])
    end = np.array([90.0, 40.0, 30.0, 80.0, 110.0])
    # 0 holds 1 and 3; 1 holds 2; 4 stands alone
    assert xplane.self_times(start, end).tolist() == [40.0, 20.0, 10.0, 20.0, 10.0]
    name = "%synapse_attention.8 = (bf16[256,2,7,128]) custom-call(bf16[256,2,7,128] %x)"
    assert xplane.short_name(name) == "synapse_attention.8"
    assert xplane.base_name(name) == "synapse_attention"
    assert xplane.base_name("%while.121 = (s32[]) while(...)") == "while"
    assert xplane.base_name("fusion") == "fusion"


def test_top_ops_count_self_time():
    ops = _events([(0, 90, "%while.1 = w", "m"), (10, 40, "%dot.2 = d", "m"),
                   (60, 80, "%dot.2 = d", "m")])
    tr = xplane.Trace((0.0, 100.0), [ops], [_events([])], [])
    top = dict((k, v) for k, v in tr.top_ops())
    assert top == {"m/dot.2": pytest.approx(50e-9), "m/while.1": pytest.approx(40e-9)}
