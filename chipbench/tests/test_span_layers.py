"""The per-layer readers of the program's own record (`layers/_program_record.py`)
on a run made by hand: each gives the value worked out by hand here, and `None`
where its input is absent (no trace, no record in the program, no scope table)."""

import os
import sys

import pytest

from conftest import ROOT

sys.path.insert(0, ROOT)

import run as harness  # chipbench/run.py
from sheeprl_tpu.utils import profiler
from sheeprl_tpu.utils.profiler import Recorder, Span

PROGRAM = "packed_burst/245812"
T_OPEN, T_CLOSE = 100.0, 110.0

# instruction -> (outer region, innermost scope, backward); seconds of self time in the traced stretch
OPS = {
    "fusion.1 f32[16,512]": (0.10, "wm.encoder", "wm.encoder", False),
    "fusion.2 f32[16,512]": (0.06, "wm.decoder", "wm.decoder", True),
    "while.3 (tuple)": (0.02, "wm.dynamics", "wm.dynamics", False),
    "convolution_add_fusion.4 f32[5120,12288]": (0.30, "wm.dynamics", "wm.dynamics", True),
    "gru_gates.20 f32[16,512]": (0.04, "wm.dynamics", "kernel.gru_gates", False),
    "gru_gates.21 f32[1024,512]": (0.02, "behaviour.imagination", "kernel.gru_gates", False),
    "fusion.5 f32[1024,512]": (0.08, "behaviour.imagination", "behaviour.imagination", False),
    "fusion.6 f32[64,16]": (0.03, "wm.heads", "wm.heads", False),
    "fusion.7 f32[16,1024]": (0.02, "behaviour.returns", "behaviour.returns", False),
    "fusion.8 f32[15,1024]": (0.01, "behaviour.heads", "behaviour.heads", True),
    "fusion.9 f32[5120,12288]": (0.05, "wm.optim", "wm.optim", False),
    "fusion.10 f32[1024,1024]": (0.02, "behaviour.optim", "behaviour.optim", False),
    "fusion.11 f32[1024,255]": (0.01, "target.ema", "target.ema", False),
    "ragged_ring_scatter.6 u8[100000,1,64,64,3]": (0.03, "ring.append", "kernel.ragged_ring_scatter", False),
    "gather.12 u8[64,16,64,64,3]": (0.02, "ring.sample", "ring.sample", False),
    "copy.13 u8[100000,1,64,64,3]": (0.08, None, None, False),  # in the program, no op_name
    "fusion.99 bf16[1000]": (0.01, "absent", None, False),  # another program's: not in the table
}
GRANTS, BUSY = 4, 1.0
# by hand: milliseconds of a region = 1e3 x its seconds / 4 gradient steps
EXPECTED = {
    "enc_dec_ms": 1e3 * (0.10 + 0.06) / GRANTS,
    "dyn_scan_ms": 1e3 * (0.02 + 0.30 + 0.04) / GRANTS,
    "dyn_scan_bwd_ms": 1e3 * 0.30 / GRANTS,
    "imag_scan_ms": 1e3 * (0.02 + 0.08) / GRANTS,
    "heads_loss_ms": 1e3 * (0.03 + 0.02 + 0.01) / GRANTS,
    "optim_ms": 1e3 * (0.05 + 0.02 + 0.01) / GRANTS,
    "ring_ms": 1e3 * (0.03 + 0.02) / GRANTS,
    "unscoped_pct": 100.0 * (1.0 - 0.81 / BUSY),  # 0.81 s lie under some region
    "kernel_gru_gates_ms": 1e3 * (0.04 + 0.02) / GRANTS,
    "kernel_ragged_ring_scatter_ms": 1e3 * 0.03 / GRANTS,
    "host_env_ms": 6.5,  # median of 1..12 ms
    "host_player_ms": 2.0,
    "host_blocked_pct": 100.0 * (1.0 + 2.0 + 0.5) / 10.0,  # each span cut to the window
    "flush_kib": (204800 + 409600) / 2 / 1024.0,
}
DEVICE = [k for k in EXPECTED if not k.startswith(("host_", "flush_"))]
HOST = [k for k in EXPECTED if k.startswith(("host_", "flush_"))]
NO_EVENTS = ["kernel_two_hot_symlog_loss_ms", "kernel_two_hot_symexp_decode_ms"]


def _put(rec, name, t0, t1, thread=1, **counters):
    span = Span(rec, name, 0, counters)
    span.t_start, span.t_end, span.thread = t0, t1, thread
    rec._ring.append(span)


def _record():
    rec = Recorder()
    _put(rec, "env.step", 90.0, 90.1)  # before the window: not counted
    for i in range(1, 13):
        _put(rec, "env.step", 100.0 + i * 0.5, 100.0 + i * 0.5 + i * 1e-3)
    _put(rec, "env.step", 109.99, 110.5)  # straddles the close: not a whole sample
    for i in range(11):
        _put(rec, "player.act", 101.0 + i * 0.5, 101.0 + i * 0.5 + 2e-3)
    _put(rec, "burst.submit", 99.0, 101.0)
    _put(rec, "burst.submit", 103.0, 105.0)
    _put(rec, "burst.submit", 109.5, 111.0)
    _put(rec, "burst.submit", 120.0, 125.0)  # after the window
    _put(rec, "burst.flush", 98.0, 99.5, blob_bytes=999999)  # ended before the window opened
    _put(rec, "burst.flush", 102.9, 105.0, blob_bytes=204800)
    _put(rec, "burst.flush", 109.4, 111.0, blob_bytes=409600)
    _put(rec, "burst.dispatch", 104.0, 104.1, thread=2, program="packed_burst/999999", bucket=35)  # not traced
    _put(rec, "burst.dispatch", 111.2, 111.3, thread=2, program=PROGRAM, bucket=19)
    _put(rec, "burst.dispatch", 112.2, 112.3, thread=2, program=PROGRAM, bucket=19)
    return rec


def _run(trace=True):
    table_ops = {k: v for k, v in OPS.items() if v[1] != "absent"}
    return {
        "window": {"t_open": T_OPEN, "t_close": T_CLOSE, "seconds": T_CLOSE - T_OPEN},
        "trace_info": {"t_start": 111.0, "t_stop": 113.0} if trace else {},
        "traffic": {"burst_program": "packed_burst"},
        "trace": {"grants": GRANTS, "busy_s": BUSY, "devices": [{"ops_self_s": {k: v[0] for k, v in OPS.items()},
                                                               "custom_calls": {}}]} if trace else None,
    }, {PROGRAM: {k.split(" ")[0]: {"outer": v[1], "scope": v[2], "backward": v[3]} for k, v in table_ops.items()},
        "packed_burst/999999": {"fusion.1": {"outer": "ring.append", "scope": "ring.append", "backward": False}}}


@pytest.fixture()
def record(monkeypatch):
    rec = _record()
    monkeypatch.setattr(profiler, "snapshot", rec.snapshot)
    tables = {}
    monkeypatch.setattr(profiler, "scope_table", lambda name: tables.get(name))
    return tables


def _read(name, run):
    return harness.load_module("layers", name).read(run)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_value_worked_out_by_hand(name, record):
    run, tables = _run()
    record.update(tables)
    assert _read(name, run) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", NO_EVENTS)
def test_kernel_with_no_event_in_the_trace_reads_nothing(name, record):
    if not os.path.isfile(os.path.join(ROOT, "chipbench", "layers", name + ".py")):
        pytest.skip("reader not shipped: the chip's trace has no event of this kernel")
    run, tables = _run()
    record.update(tables)
    assert _read(name, run) is None


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_device_readers_read_nothing_without_trace_table_or_traced_dispatch(name, record, monkeypatch):
    run, tables = _run(trace=False)
    record.update(tables)
    assert _read(name, run) is None  # an untraced run
    record.clear()
    run, _ = _run()
    assert _read(name, run) is None  # the program registered no scope table
    run, tables = _run()
    record.update(tables)
    run["trace_info"] = {"t_start": 130.0, "t_stop": 131.0}  # no burst.dispatch in the traced stretch
    assert _read(name, run) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_every_reader_reads_nothing_from_a_program_without_the_record(name, monkeypatch):
    """The parent of the PR that added the record: `utils.profiler` has no
    `snapshot` or `scope_table`. No reader raises."""
    monkeypatch.delattr(profiler, "snapshot")
    monkeypatch.delattr(profiler, "scope_table")
    run, _ = _run()
    assert _read(name, run) is None


def test_every_new_metric_of_the_benchmark_has_a_case_here(bench):
    before = {"warm_compiles", "window_compiles", "host_step_ms", "device_idle_pct", "train_step_ms", "train_mfu_pct",
              "peak_hbm_gib"}  # PR 25's, read from the adapter's record
    added = {m["name"] for m in bench["per_layer"]} - before
    assert added and added <= set(EXPECTED) | set(NO_EVENTS)
