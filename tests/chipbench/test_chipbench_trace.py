"""The trace reduction on hand-built traces with known answers."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"


def build(ops, spans=()):
    window = [("chipbench.window", 0.0, 100.0)]
    return tr.Trace(ops={DEV: list(ops)}, spans=window + list(spans))


def test_op_name_from_hlo_text():
    text = "%fusion.12 = bf16[4,8]{1,0} fusion(bf16[4,8] %p), kind=kLoop"
    assert tr.op_name(text) == "fusion.12"
    assert tr.op_name("dot_general.1") == "dot_general.1"


def test_busy_is_the_union_of_op_intervals():
    s = tr.reduce(build([("a", 10, 30), ("b", 20, 40), ("c", 60, 70)]))
    assert s.window_ns == 100
    assert s.busy_ns == 40  # [10, 40) and [60, 70)
    d = s.devices[DEV]
    assert d.op_ns == {"a": 20, "b": 20, "c": 10}


def test_ops_are_clipped_to_the_window():
    s = tr.reduce(build([("a", -50, 10), ("b", 90, 150)]))
    assert s.busy_ns == 20
    assert s.devices[DEV].op_ns == {"a": 10, "b": 10}


def test_control_flow_containers_count_as_busy_not_as_ops():
    s = tr.reduce(build([("while.3", 10, 50), ("fusion.1", 12, 20),
                         ("fusion.2", 30, 45)]))
    d = s.devices[DEV]
    assert d.busy_ns == 40
    assert d.op_ns == {"fusion.1": 8, "fusion.2": 15}
    assert [n for n, _ in s.top_ops()] == ["fusion.2", "fusion.1"]


def test_collective_time_and_its_exposed_part():
    ops = [("collective-permute-start.1", 10, 30),
           ("fusion.1", 20, 25),
           ("all-reduce.2", 50, 60),
           ("fusion.2", 55, 80)]
    d = tr.reduce(build(ops)).devices[DEV]
    assert d.collective_ns == 30
    # [10, 20) and [25, 30) of the permute, [50, 55) of the all-reduce.
    assert d.collective_exposed_ns == 20


def test_idle_gaps_are_named_by_the_innermost_open_span():
    spans = [("chipbench.launch", 0.0, 50.0),
             ("chipbench.collect", 35.0, 50.0),
             ("chipbench.step", 50.0, 100.0)]
    s = tr.reduce(build([("a", 0, 30), ("b", 45, 90)], spans))
    assert s.top_gaps() == [["chipbench.collect", 15e-9],
                            ["chipbench.step", 10e-9]]


def test_busy_is_averaged_over_devices():
    t = tr.Trace(ops={DEV: [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]},
                 spans=[("chipbench.window", 0.0, 100.0)])
    s = tr.reduce(t)
    assert s.busy_ns == 75
    assert s.top_ops() == [["a", 75e-9]]


def test_subtract_and_union():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="chipbench.window"):
        tr.reduce(tr.Trace(ops={DEV: []}, spans=[]))
