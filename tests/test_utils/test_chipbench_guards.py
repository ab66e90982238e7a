"""What every PR's test run guards of the benchmark under ``chipbench/``, loaded by path as the harness loads it:
the reader of ``moe_compact_share_pct`` over the recorder's ``iter`` spans, and the definitions that decide
``correct`` in the language-model cell (``chipbench/tests/test_ppo_lm.py:check_synthetic``)."""

import contextlib
import importlib.util
import json
import os
import sys

import pytest

from sheeprl_tpu.utils import profiler
from sheeprl_tpu.utils.profiler import Recorder, Span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIPBENCH = os.path.join(ROOT, "chipbench")
T_OPEN, T_CLOSE = 100.0, 120.0


@contextlib.contextmanager
def chipbench_on_path():
    """``chipbench/`` and its tests importable as the harness and its own test run have them, and gone again after
    (``conftest``, ``run`` and ``layers`` are names another test of this worker must not find taken)."""
    modules, path = set(sys.modules), list(sys.path)
    sys.path[:0] = [os.path.join(CHIPBENCH, "tests"), CHIPBENCH]
    try:
        yield
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - modules:
            del sys.modules[name]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(CHIPBENCH, path))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def recorded(*iters):
    """A recorder holding one ``iter`` span per entry: ``(t_start, t_end, counters)``."""
    rec = Recorder()
    for t0, t1, counters in iters:
        span = Span(rec, "iter", 0, dict(iter_num=1, policy_step=0, grad_steps=8, **counters))
        span.t_start, span.t_end = t0, t1
        rec._ring.append(span)
    return rec


def block(compacted, of=64):
    return {"moe_compact_calls": compacted, "moe_compactable_calls": of}


COMPACT_CASES = {
    # the window's blocks: 4 layers x (8 prompts + 8 update forwards) = 64 calls each
    "all_compacted": ([block(64), block(64), block(64)], 100.0),
    "some": ([block(64), block(48), block(32)], 100.0 * 144 / 192),
    "none": ([block(0), block(0)], 0.0),
    "nothing_could": ([block(0, of=0), block(0, of=0)], None),  # an uncut layer: every call moves every row
    "no_such_counter": ([{}, {}], None),  # the classic block's and the Dreamer loops' `iter` spans
    "no_spans": ([], None),
}


@pytest.mark.parametrize("case", list(COMPACT_CASES))
def test_moe_compact_share_reader(case, monkeypatch):
    blocks, expected = COMPACT_CASES[case]
    spans = [(T_OPEN + 4.0 * i, T_OPEN + 4.0 * i + 3.9, counters) for i, counters in enumerate(blocks)]
    # a block that ended before the window opened is not the window's, whatever it counted
    rec = recorded((T_OPEN - 5.0, T_OPEN - 1.0, block(0)), *spans)
    monkeypatch.setattr(profiler, "snapshot", rec.snapshot)
    run = {"window": {"t_open": T_OPEN, "t_close": T_CLOSE, "seconds": T_CLOSE - T_OPEN}}
    with chipbench_on_path():
        got = load("layers/moe_compact_share_pct.py", "chipbench_layers_moe_compact_share_pct").read(run)
    assert got == (None if expected is None else pytest.approx(expected))


def test_the_benchmark_declares_the_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == "moe_compact_share_pct")
    assert entry == {"name": "moe_compact_share_pct", "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "model blocks", "moves": "grad_steps_per_s", "workloads": ["smallthinker_ep4_longprompt_rl"]}


def test_the_language_model_cells_correct_holds_on_a_case_made_by_hand():
    """Noise of the recorded size on a small first step is ``correct``; half of every step's gradient and an entry
    that never moved are not: the definitions and the limits a claim in that cell is judged by."""
    with chipbench_on_path():
        theirs = load("tests/test_ppo_lm.py", "chipbench_tests_test_ppo_lm")
        correct = load("correct/ppo_lm.py", "chipbench_correct_ppo_lm")
        with open(os.path.join(CHIPBENCH, "configs", "smallthinker_21b_a3b_ep4.json")) as f:
            theirs.check_synthetic(correct, json.load(f)["correct_limits"])
