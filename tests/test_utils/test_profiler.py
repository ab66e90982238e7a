"""The flight recorder of ``utils/profiler.py``: spans nest by parent id,
threads interleave safely, memory is bounded, ``snapshot`` cuts by time, and
every span is entered as a ``jax.profiler.TraceAnnotation``."""

import sys
import threading
import time

import pytest

from sheeprl_tpu.utils import profiler
from sheeprl_tpu.utils.profiler import ROOT, Recorder


def _by_name(snap):
    return {s["name"]: s for s in snap["spans"]}


def test_parent_ids_nest_and_counters_are_kept():
    rec = Recorder()
    with rec.span("iter", parent=ROOT, iter_num=7) as it:
        with rec.span("player.act"):
            pass
        with rec.span("burst.flush", burst=3) as fl:
            with rec.span("burst.pack"):
                pass
            fl.set(rows=17, blob_bytes=1024)
    spans = _by_name(rec.snapshot())
    assert spans["iter"]["parent"] == ROOT and spans["iter"]["counters"] == {"iter_num": 7}
    assert spans["player.act"]["parent"] == it.id == spans["burst.flush"]["parent"]
    assert spans["burst.pack"]["parent"] == fl.id
    assert spans["burst.flush"]["counters"] == {"burst": 3, "rows": 17, "blob_bytes": 1024}
    # a span enters the record when it ends: children before their parent
    assert [s["name"] for s in rec.snapshot()["spans"]] == ["player.act", "burst.pack", "burst.flush", "iter"]
    assert all(s["t_end"] >= s["t_start"] > 0 for s in spans.values())
    assert rec._stack() == []


def test_explicit_parent_crosses_threads_and_root_drops_what_was_left_open():
    rec = Recorder()
    flush = rec.span("burst.flush").start()
    seen = {}

    def trainer():
        with rec.span("burst.dispatch", parent=flush.id, bucket=19) as d:
            seen["thread"] = d.thread
        seen["own_stack_empty"] = rec._stack() == []

    t = threading.Thread(target=trainer)
    t.start()
    t.join(10)
    assert not t.is_alive()
    flush.stop()
    spans = _by_name(rec.snapshot())
    assert spans["burst.dispatch"]["parent"] == flush.id and spans["burst.dispatch"]["thread"] == seen["thread"]
    assert spans["burst.dispatch"]["thread"] != spans["burst.flush"]["thread"] and seen["own_stack_empty"]
    # an iteration that an exception cut short leaves its span open; the next root span drops it
    rec.span("iter", parent=ROOT).start()
    rec.span("env.step").start()
    with rec.span("iter", parent=ROOT) as fresh:
        with rec.span("stage") as child:
            assert child.parent == fresh.id
    assert rec._stack() == []


def test_spans_from_many_threads_interleave_safely():
    rec = Recorder(capacity=100_000)
    n_threads, per_thread = 16, 400  # more workers than cores
    errors = []

    def worker(k):
        try:
            for i in range(per_thread):
                with rec.span("outer", k=k, i=i) as o:
                    with rec.span("inner") as inner:
                        assert inner.parent == o.id
                if i % 50 == 0:
                    rec.snapshot()  # a reader while writers append
        except BaseException as e:  # surfaced below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    spans = rec.snapshot()["spans"]
    assert len(spans) == 2 * n_threads * per_thread
    assert len({s["id"] for s in spans}) == len(spans)  # no id handed out twice
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        if s["name"] == "inner":  # every child's parent is a span of its own thread
            assert by_id[s["parent"]]["thread"] == s["thread"] and by_id[s["parent"]]["name"] == "outer"


def test_memory_is_bounded_and_reset_clears():
    rec = Recorder(capacity=8)
    for i in range(100):
        with rec.span("iter", i=i):
            pass
    snap = rec.snapshot()
    assert [s["counters"]["i"] for s in snap["spans"]] == list(range(92, 100))
    assert snap["counters"] == {"recorded": 100, "held": 8, "capacity": 8}
    rec.reset()
    assert rec.snapshot()["spans"] == []
    assert profiler.RECORDER._ring.maxlen == profiler.CAPACITY == 65536


def test_snapshot_cuts_by_time():
    rec = Recorder()
    marks = []
    for i in range(3):
        marks.append(time.perf_counter())
        with rec.span("iter", i=i):
            time.sleep(0.002)
    marks.append(time.perf_counter())
    only = lambda t0, t1: [s["counters"]["i"] for s in rec.snapshot(t0, t1)["spans"]]
    assert only(None, None) == [0, 1, 2]
    assert only(marks[1], marks[2]) == [1]
    assert only(marks[2], None) == [2] and only(None, marks[1]) == [0]
    mid = rec.snapshot()["spans"][1]
    inside = (mid["t_start"] + mid["t_end"]) / 2
    assert only(inside, inside) == [1]  # a span that overlaps the cut is kept whole


def test_every_span_is_entered_as_a_trace_annotation(monkeypatch):
    entered = []

    class FakeAnnotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            entered.append(("enter", self.name, self.kwargs))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(profiler, "_TraceAnnotation", FakeAnnotation)
    rec = Recorder()
    with rec.span("burst.flush", burst=5):
        with rec.span("burst.submit"):
            pass
    assert entered == [
        ("enter", "burst.flush", {"burst": 5}), ("enter", "burst.submit", {}),
        ("exit", "burst.submit"), ("exit", "burst.flush"),
    ]
    monkeypatch.setattr(profiler, "_TraceAnnotation", None)
    with rec.span("iter"):  # the real one: a no-op while no profiler session runs
        pass
    import jax

    assert profiler._TraceAnnotation is jax.profiler.TraceAnnotation


def test_span_names_and_regions_are_listed_once():
    assert len(set(profiler.SPANS)) == len(profiler.SPANS) == 10
    assert len(set(profiler.REGIONS)) == len(profiler.REGIONS) == 24  # 12 of the bursts, 12 of the language-model block (PR 36: +2)
    assert len(profiler.BURST_REGIONS) == 12 and profiler.REGIONS == profiler.BURST_REGIONS + profiler.LM_BLOCK_REGIONS
    assert all(name == name.lower() for name in profiler.SPANS + profiler.REGIONS)
    assert profiler.scope_table("no such program") is None


@pytest.mark.parametrize("capacity", [1, 3])
def test_a_full_ring_keeps_the_newest(capacity):
    rec = Recorder(capacity=capacity)
    for i in range(5):
        with rec.span("iter", i=i):
            pass
    assert [s["counters"]["i"] for s in rec.snapshot()["spans"]] == list(range(5 - capacity, 5))
