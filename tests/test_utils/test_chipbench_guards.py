"""What every PR's test run guards of the benchmark under ``chipbench/``, loaded by path as the harness loads it:
the reader of ``moe_compact_share_pct`` over the recorder's ``iter`` spans, the definitions that decide
``correct`` in the first language-model cell (``chipbench/tests/test_ppo_lm.py:check_synthetic``), and, for the
latent-attention policy's cell (PR 36), its four readers, its family's FLOP and kernel-work functions and every
chip run on record put through its ``correct`` again (``chipbench/tests/test_ppo_lm_mla.py``'s ``check_*``)."""

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
                     "layer": "model blocks", "moves": "grad_steps_per_s",
                     "workloads": ["smallthinker_ep4_longprompt_rl", "kanana2_ep8_longprompt_rl"]}


def test_the_language_model_cells_correct_holds_on_a_case_made_by_hand():
    """Noise of the recorded size on a small first step is ``correct``; half of every step's gradient and an entry
    that never moved are not: the definitions and the limits a claim in that cell is judged by."""
    with chipbench_on_path():
        theirs = load("tests/test_ppo_lm.py", "chipbench_tests_test_ppo_lm")
        correct = load("correct/ppo_lm.py", "chipbench_correct_ppo_lm")
        with open(os.path.join(CHIPBENCH, "configs", "smallthinker_21b_a3b_ep4.json")) as f:
            theirs.check_synthetic(correct, json.load(f)["correct_limits"])


# -- the latent-attention policy's cell (PR 36) ------------------------------------------------------------------
NEW_CELL, NEW_CONFIG = "kanana2_ep8_longprompt_rl", "kanana2_30b_a3b_ep8"
NEW_METRICS = {"attn_mla_ms": ("ms", "lower", "device_trace", "grad_steps_per_s"),
               "ffn_shared_ms": ("ms", "lower", "device_trace", "grad_steps_per_s"),
               "moe_bias_moved_pct": ("%", "higher", "program_counter", "grad_steps_per_s"),
               "rollout_cache_mib": ("MiB", "lower", "program_counter", "policy_steps_per_s")}


def theirs():
    """``chipbench/tests/test_ppo_lm_mla.py``, whose ``check_*`` functions hold the cases (call inside ``chipbench_on_path``)."""
    return load("tests/test_ppo_lm_mla.py", "chipbench_tests_test_ppo_lm_mla")


def new_config():
    with open(os.path.join(CHIPBENCH, "configs", NEW_CONFIG + ".json")) as f:
        return json.load(f)


with chipbench_on_path():
    _THEIRS = theirs()
    COUNTER_CASES, REGION_CASES = list(_THEIRS.COUNTER_CASES), sorted(_THEIRS.REGION_EXPECTED)
    RECORD_ENTRIES, RECORDED = _THEIRS.recorded_runs()


@pytest.mark.parametrize("case", COUNTER_CASES)
def test_the_new_cells_counter_readers(case, monkeypatch):
    with chipbench_on_path():
        theirs().check_counter_reader(case, monkeypatch)


@pytest.mark.parametrize("name", REGION_CASES)
def test_the_new_cells_region_readers(name, monkeypatch):
    with chipbench_on_path():
        theirs().check_region_reader(name, monkeypatch)


def test_the_new_familys_flops_and_kernel_work_against_a_hand_count():
    with open(os.path.join(CHIPBENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    with chipbench_on_path():
        theirs().check_flops(load("flops/ppo_lm_mla.py", "chipbench_flops_ppo_lm_mla"), new_config(), peaks)


def test_the_benchmark_declares_the_new_cell_its_configuration_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]][-1] == NEW_CELL and all(w["chips"] == 1 for w in bench["workloads"])
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"]) == (NEW_CONFIG, "longprompt_rl") and len(cell["why"]) <= 200
    entry = bench["configs"][-1]
    conf = new_config()
    assert entry["name"] == conf["name"] == NEW_CONFIG and entry["source"] == conf["source"] and len(entry["why"]) <= 200
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128, "vocab_size": 128256}
    assert conf["family"] == "ppo_lm_mla" and conf["parameters"] == 575_958_017
    for kind, name in (("flops", "ppo_lm_mla"), ("reference", "ppo_lm_mla_ref"), ("correct", "ppo_lm_mla")):
        assert os.path.isfile(os.path.join(CHIPBENCH, kind, name + ".py"))
    # every published width under its published key, unchanged
    widths = {"hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 768, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64,
              "num_attention_heads": 32, "num_key_value_heads": 32, "num_experts_per_tok": 6, "n_shared_experts": 2}
    assert {k: conf[k] for k in widths} == widths and all(conf["as_run"]["algo.lm." + k] == v for k, v in widths.items() if k != "head_dim")
    assert set(conf["correct_limits_why"]) >= {k for k, v in conf["correct_limits"].items() if v}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, moves) in NEW_METRICS.items():
        assert metrics[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": "model blocks",
                                 "moves": moves, "workloads": [NEW_CELL]}
    listed = {name for name, m in metrics.items() if NEW_CELL in m.get("workloads", [])}
    assert len(listed) == 22 and not listed & {"moe_local_share_pct", "moe_load_max_over_mean", "attn_global_ms", "attn_window_ms"}


@pytest.mark.parametrize("run", RECORDED, ids=[r["id"] for r in RECORDED])
def test_a_chip_run_on_record_is_correct_and_its_controls_are_not(run):
    with chipbench_on_path():
        theirs().check_recorded_run(load("correct/ppo_lm_mla.py", "chipbench_correct_ppo_lm_mla"),
                                    new_config()["correct_limits"], RECORD_ENTRIES, run)


def test_the_records_cover_the_seeds_the_new_cells_limits_were_set_from():
    # ISSUE 36 asked for four control seeds; the chip budget ended after three (PERF.md, finding 36)
    assert len({r["seed"] for r in RECORDED}) >= 24 and sum(len(r["controls"]) == 7 for r in RECORDED) >= 3
