"""The reduction from a trace to numbers: first on intervals made by hand,
then on the small trace recorded on a v5e chip (`record_small_trace.py`: three
calls of one small program, a 20 ms host sleep after each)."""

import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small_trace.xplane.pb")


def test_union_and_gaps():
    busy, gaps = tr.union_length([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)])
    assert busy == pytest.approx(3.0) and gaps == [(2.0, 3.0)]


def test_self_times_of_nested_events():
    events = [(0.0, 10.0, "while.1"), (1.0, 4.0, "fusion.2"), (4.0, 9.0, "fusion.3"), (12.0, 13.0, "copy.4")]
    got = tr.self_times(events)
    assert got["while.1"] == pytest.approx(2.0) and got["fusion.2"] == pytest.approx(3.0)
    assert got["copy.4"] == pytest.approx(1.0)
    assert tr.base_name("fusion.123") == "fusion" and tr.base_name("jit_f(42)") == "jit_f"
    assert tr.base_name("pallas_gru_gates") == "pallas_gru_gates"
    full = "%fusion.3 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} fusion(f32[1024,1024]{1,0} %copy-done), kind=kLoop"
    assert tr.op_label(full) == "fusion.3 bf16[1024,1024]" and tr.op_kind(full) == "fusion"


@pytest.mark.skipif(not os.path.isfile(SMALL), reason="no recorded trace in this tree")
def test_recorded_trace_gives_known_numbers():
    reduced = tr.reduce_planes(tr.read_planes(SMALL), chips=1)
    dev = reduced["devices"][0]
    # three executions of one program, nothing else on the device
    runs = dev["modules"]["jit_small_program"]
    assert len(runs) == 3
    assert reduced["busy_s"] == pytest.approx(sum(runs), rel=0.05)
    # two sleeps of 20 ms lie between the three calls
    long_gaps = [b - a for a, b in dev["gaps"] if b - a > 0.015]
    assert len(long_gaps) == 2 and all(0.02 <= g < 0.05 for g in long_gaps)
    assert reduced["window_s"] == pytest.approx(reduced["busy_s"] + sum(b - a for a, b in dev["gaps"]), rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # self times add up to the busy time; the three host annotations are found
    assert sum(dev["ops_self_s"].values()) == pytest.approx(reduced["busy_s"], rel=0.05)
    assert len([a for a in reduced["annotations"] if a[2] == "chipbench.flush"]) == 3
