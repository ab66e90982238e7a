"""What every PR's test run guards of the benchmark under ``chipbench/``, loaded by path as the harness loads it:
the reader of ``moe_compact_share_pct`` over the recorder's ``iter`` spans, the definitions that decide
``correct`` in the first language-model cell (``chipbench/tests/test_ppo_lm.py:check_synthetic``), and, for the
latent-attention policy's cell, the gated grouped-query policy's and the convolution policy's, their readers, their
families' FLOP and kernel-work functions and every chip run on record put through their ``correct`` again
(``chipbench/tests/test_ppo_lm_mla.py``'s, ``test_ppo_lm_afmoe.py``'s and ``test_ppo_lm_lfm2.py``'s ``check_*``); the
five ``setup_*``
readers of the entry-and-compiler layer over the recorder's set-up spans; and the reader of
``attn_fwd_recomputed_ms`` over rows of the scope table joined to a trace."""

import contextlib
import importlib.util
import json
import os
import sys
import threading

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
                     "workloads": ["smallthinker_ep4_longprompt_rl", "kanana2_ep8_longprompt_rl", "trinity_mini_ep8_longprompt_rl",
                                   "lfm2_ep8_longprompt_rl"]}


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
THIRD_CELL, THIRD_CONFIG = "trinity_mini_ep8_longprompt_rl", "trinity_mini_26b_a3b_ep8"
FOURTH_CELL, FOURTH_CONFIG = "lfm2_ep8_longprompt_rl", "lfm2_24b_a2b_ep8"
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
    names = [w["name"] for w in bench["workloads"]]
    assert names[-3:] == [NEW_CELL, THIRD_CELL, FOURTH_CELL] and all(w["chips"] == 1 for w in bench["workloads"])
    cell = bench["workloads"][-3]
    assert (cell["config"], cell["traffic"]) == (NEW_CONFIG, "longprompt_rl") and len(cell["why"]) <= 200
    entry = bench["configs"][-3]
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
        later = [THIRD_CELL, FOURTH_CELL] if name != "attn_mla_ms" else []
        assert metrics[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": "model blocks",
                                 "moves": moves, "workloads": [NEW_CELL] + later}
    listed = {name for name, m in metrics.items() if NEW_CELL in m.get("workloads", [])}
    assert len(listed) == 28 and "attn_fwd_recomputed_ms" in listed and not listed & {"moe_local_share_pct", "moe_load_max_over_mean", "attn_global_ms", "attn_window_ms"}


@pytest.mark.parametrize("run", RECORDED, ids=[r["id"] for r in RECORDED])
def test_a_chip_run_on_record_is_correct_and_its_controls_are_not(run):
    with chipbench_on_path():
        theirs().check_recorded_run(load("correct/ppo_lm_mla.py", "chipbench_correct_ppo_lm_mla"),
                                    new_config()["correct_limits"], RECORD_ENTRIES, run)


def test_the_records_cover_the_seeds_the_new_cells_limits_were_set_from():
    # ISSUE 36 asked for four control seeds; the chip budget ended after three (PERF.md, finding 36)
    assert len({r["seed"] for r in RECORDED}) >= 24 and sum(len(r["controls"]) == 7 for r in RECORDED) >= 3


# -- the set-up readers (PR 38) -----------------------------------------------------------------------------------
SETUP_METRICS = ("setup_trace_s", "setup_compile_s", "setup_cache_load_s", "setup_build_s", "setup_iters_s")
SETUP_RUN = {"window": {"t_open": 100.0, "t_close": 120.0, "seconds": 20.0, "setup_s": 60.0}}  # set-up began at 40
MAIN, OTHER = threading.main_thread().ident, -1
SETUP_SPANS = [  # (name, t_start, t_end, thread, counters)
    ("setup.build", 41.0, 50.0, MAIN, {}), ("setup.build", 51.0, 60.0, MAIN, {}),
    ("jax.trace", 45.0, 48.0, MAIN, {"fn": "outer"}), ("jax.trace", 46.0, 47.0, MAIN, {"fn": "inner"}),  # nested: once
    ("jax.lower", 48.0, 49.0, MAIN, {"fn": "jit(outer)"}), ("jax.compile", 49.0, 50.5, MAIN, {"fn": "jit(outer)", "cache": "miss"}),
    ("iter", 60.0, 70.0, MAIN, {}), ("iter", 70.5, 80.0, MAIN, {}), ("iter", 65.0, 66.0, OTHER, {}),
    ("jax.trace", 61.0, 62.0, MAIN, {"fn": "burst"}), ("jax.lower", 62.0, 62.5, MAIN, {"fn": "jit(burst)"}),
    ("jax.compile", 62.5, 64.0, MAIN, {"fn": "jit(burst)", "cache": "hit"}),
    ("jax.compile", 75.0, 76.0, OTHER, {"fn": "jit(step)", "cache": "off"}),  # another thread's compile holds the loop too
    ("jax.trace", 30.0, 30.5, MAIN, {"fn": "before"}),  # the harness's, before set-up began: not the program's
    ("iter", 100.5, 101.0, MAIN, {}), ("jax.compile", 101.0, 102.0, MAIN, {"fn": "jit(reference)", "cache": "miss"}),
]
SETUP_EXPECTED = {
    "setup_trace_s": 4.0 + 1.5,  # [45, 49] and [61, 62.5]
    "setup_compile_s": 1.5 + 1.0,  # miss [49, 50.5], off [75, 76]
    "setup_cache_load_s": 1.5,
    "setup_build_s": 18.0 - 5.0,  # less [45, 50]
    "setup_iters_s": 19.5 - 3.0 - 1.0,  # the main thread's, less [61, 64] and [75, 76]
}


def setup_recorder(entries, capacity=profiler.CAPACITY, first_id=1):
    rec = Recorder(capacity=capacity)
    for i, (name, t0, t1, thread, counters) in enumerate(sorted(entries, key=lambda e: e[2])):  # as they ended
        span = Span(rec, name, 0, dict(counters))
        span.id, span.thread, span.t_start, span.t_end = first_id + i, thread, t0, t1
        rec._ring.append(span)
    return rec


def read_setup(name, rec, monkeypatch):
    monkeypatch.setattr(profiler, "snapshot", rec.snapshot)
    with chipbench_on_path():
        return load(f"layers/{name}.py", f"chipbench_layers_{name}").read(json.loads(json.dumps(SETUP_RUN)))


SETUP_CASES = {
    "read": (SETUP_SPANS, profiler.CAPACITY, 1, SETUP_EXPECTED),
    # a full ring that still holds set-up's first span has dropped nothing of it
    "full_ring_holding_setup": (SETUP_SPANS, len(SETUP_SPANS), 1, SETUP_EXPECTED),
    # the parent: no `setup.build`, no `jax.*`; the loop's `iter` spans alone are no reading
    "no_setup_spans": ([e for e in SETUP_SPANS if e[0] == "iter"], profiler.CAPACITY, 1, dict.fromkeys(SETUP_METRICS)),
    # spans were dropped since set-up began: the oldest held starts after it, and more were recorded than held
    "evicted": (sorted(SETUP_SPANS, key=lambda e: e[2])[2:], len(SETUP_SPANS) - 2, 3, dict.fromkeys(SETUP_METRICS)),
    # a cold cache: nothing served; a warm one: nothing compiled
    "nothing_served": ([e for e in SETUP_SPANS if e[4].get("cache") != "hit"], profiler.CAPACITY, 1,
                       {**SETUP_EXPECTED, "setup_cache_load_s": 0.0, "setup_iters_s": 19.5 - 1.5 - 1.0}),
}


@pytest.mark.parametrize("metric", SETUP_METRICS)
@pytest.mark.parametrize("case", list(SETUP_CASES))
def test_the_setup_readers(case, metric, monkeypatch):
    entries, capacity, first_id, expected = SETUP_CASES[case]
    got = read_setup(metric, setup_recorder(entries, capacity, first_id), monkeypatch)
    assert got == (None if expected[metric] is None else pytest.approx(expected[metric]))


def test_a_nested_jits_trace_counts_once(monkeypatch):
    """The real listener: the inner function's trace lies inside the outer's, and `setup_trace_s` reads the union."""
    import time

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.utils.utils import compile_stats

    compile_stats.register()
    inner = jax.jit(lambda x: jnp.cos(x) * 2.0)

    def nested_probe(x):
        return inner(x) + inner(x + 1.0)

    x = jnp.arange(3, dtype=jnp.float32)
    t_start = time.perf_counter()
    with profiler.span("setup.build", parent=profiler.ROOT):
        jax.jit(nested_probe)(x).block_until_ready()
    t_open = time.perf_counter()
    found = [s for s in profiler.snapshot(t_start, t_open)["spans"] if s["name"] in ("jax.trace", "jax.lower")]
    outer = next(s for s in found if s["counters"]["fn"] == "nested_probe")
    inside = [s for s in found if s is not outer and outer["t_start"] <= s["t_start"] <= s["t_end"] <= outer["t_end"]]
    assert inside  # the inner jit, traced inside the outer trace
    run = {"window": {"t_open": t_open, "t_close": t_open + 1.0, "seconds": 1.0, "setup_s": t_open - t_start}}
    with chipbench_on_path():
        got = load("layers/setup_trace_s.py", "chipbench_layers_setup_trace_s").read(run)
    assert got == pytest.approx(sum(s["t_end"] - s["t_start"] for s in found if s not in inside))
    assert got < sum(s["t_end"] - s["t_start"] for s in found)


def test_the_benchmark_declares_the_setup_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(SETUP_METRICS[0])
    assert names[first : first + 5] == list(SETUP_METRICS)  # appended together, after every metric that was there
    for m in bench["per_layer"][first : first + 5]:
        assert m == {"name": m["name"], "unit": "s", "better": "lower", "source": "program_span",
                     "layer": "entry and compiler", "moves": "setup_s", "workloads": cells}
        assert os.path.isfile(os.path.join(CHIPBENCH, "layers", m["name"] + ".py"))


# -- the update's recomputed attention forwards -------------------------------------------------------------------
ATTN = "kernel.window_attention"
# (instruction, seconds over the traced block, outer region, innermost scope, backward): a layer's forward, its
# backward's dq and dkv, the megablox product's, an instruction with no scope
FORWARD = ("splash_mqa_fwd_residuals.4", 0.040, "lm.attn_mla", ATTN, False)
BACKWARD = [("splash_mqa_dq_no_residuals.2", 0.085, "lm.attn_mla", ATTN, True),
            ("splash_mqa_dkv_no_residuals.2", 0.096, "lm.attn_mla", ATTN, True)]
OTHERS = [("gmm.3", 0.02, "lm.moe", "kernel.moe_grouped_ffn", True), ("copy-done.1", 0.01, None, None, False)]
RECOMPUTED = [("splash_mqa_fwd_residuals.6", 0.036, "lm.attn_mla", ATTN, True),
              ("splash_mqa_fwd_residuals.7", 0.037, "lm.attn_mla", ATTN, True)]
RECOMPUTE_CASES = {
    # the parent: each layer's forward again in the backward, 73 ms over 8 gradient steps
    "recomputed": ([FORWARD, *RECOMPUTED, *BACKWARD, *OTHERS], 8, 1e3 * 0.073 / 8),
    # the residuals kept: splash kernels, none of them a forward in the backward
    "none_recomputed": ([FORWARD, *BACKWARD, *OTHERS], 8, 0.0),
    # a forward in the backward that is no splash kernel's (the reference tier's blocks) is not read
    "no_splash_kernel": ([("fusion.12", 0.03, "lm.attn_mla", ATTN, True), *OTHERS], 8, None),
    "no_trace": (None, 8, None),
}


@pytest.mark.parametrize("case", list(RECOMPUTE_CASES))
def test_attn_fwd_recomputed_reader(case):
    rows, grants, expected = RECOMPUTE_CASES[case]
    run = {"trace": {"grants": grants}, "_attributed": rows} if rows is not None else {"trace": None, "trace_info": {}}
    with chipbench_on_path():
        got = load("layers/attn_fwd_recomputed_ms.py", "chipbench_layers_attn_fwd_recomputed_ms").read(run)
    assert got == (None if expected is None else pytest.approx(expected))


def test_the_benchmark_declares_the_recompute_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-4] == {"name": "attn_fwd_recomputed_ms", "unit": "ms", "better": "lower",
                                      "source": "device_trace", "layer": "model blocks", "moves": "grad_steps_per_s",
                                      "workloads": ["smallthinker_ep4_longprompt_rl", NEW_CELL, THIRD_CELL, FOURTH_CELL]}


# -- the gated grouped-query policy's cell ----------------------------------------------------------------------
def third():
    """``chipbench/tests/test_ppo_lm_afmoe.py``, whose ``check_*`` functions hold the cases (call inside ``chipbench_on_path``)."""
    return load("tests/test_ppo_lm_afmoe.py", "chipbench_tests_test_ppo_lm_afmoe")


def third_config():
    with open(os.path.join(CHIPBENCH, "configs", THIRD_CONFIG + ".json")) as f:
        return json.load(f)


with chipbench_on_path():
    _THIRD = third()
    THIRD_COUNTER_CASES, THIRD_REGION_CASES = list(_THIRD.COUNTER_CASES), sorted(_THIRD.REGION_EXPECTED)
    THIRD_ENTRIES, THIRD_RECORDED = _THIRD.recorded_runs()


@pytest.mark.parametrize("case", THIRD_COUNTER_CASES)
def test_the_third_cells_counter_readers(case, monkeypatch):
    with chipbench_on_path():
        third().check_counter_reader(case, monkeypatch)


@pytest.mark.parametrize("name", THIRD_REGION_CASES)
def test_the_third_cells_region_readers(name, monkeypatch):
    with chipbench_on_path():
        third().check_region_reader(name, monkeypatch)


def test_the_third_familys_flops_and_kernel_work_against_a_hand_count():
    with open(os.path.join(CHIPBENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    with chipbench_on_path():
        third().check_flops(load("flops/ppo_lm_afmoe.py", "chipbench_flops_ppo_lm_afmoe"), third_config(), peaks)


def test_the_benchmark_declares_the_third_cell_its_configuration_and_its_metric():
    """One configuration, one cell and one per-layer metric appended; the cell in every language-model metric's
    list whose reader reads it, and in no other."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, entry, conf = bench["workloads"][-2], bench["configs"][-2], third_config()
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (THIRD_CELL, THIRD_CONFIG, "longprompt_rl", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["name"] == conf["name"] == THIRD_CONFIG and entry["source"] == conf["source"]
    assert entry["file"] == f"chipbench/configs/{THIRD_CONFIG}.json"
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128, "vocab_size": 200192}
    assert conf["family"] == "ppo_lm_afmoe" and conf["parameters"] == 705_476_353
    for kind, name in (("flops", "ppo_lm_afmoe"), ("reference", "ppo_lm_afmoe_ref"), ("correct", "ppo_lm_afmoe"),
                       ("layers", "attn_gate_ms")):
        assert os.path.isfile(os.path.join(CHIPBENCH, kind, name + ".py"))
    # every published width under its published key, unchanged
    widths = {"hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 1024, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 4, "num_experts_per_tok": 8, "num_shared_experts": 1,
              "sliding_window": 2048, "rope_theta": 10000, "route_scale": 2.826}
    assert {k: conf[k] for k in widths} == widths and all(conf["as_run"]["algo.lm." + k] == v for k, v in widths.items())
    assert set(conf["correct_limits_why"]) >= {k for k, v in conf["correct_limits"].items() if v}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert bench["per_layer"][-3] == {"name": "attn_gate_ms", "unit": "ms", "better": "lower", "source": "device_trace",
                                      "layer": "model blocks", "moves": "grad_steps_per_s", "workloads": [THIRD_CELL]}
    listed = {name for name, m in metrics.items() if THIRD_CELL in m.get("workloads", [])}
    kanana = {name for name, m in metrics.items() if NEW_CELL in m.get("workloads", [])}
    assert listed == (kanana - {"attn_mla_ms"}) | {"attn_window_ms", "attn_global_ms", "attn_gate_ms"}
    assert not listed & {"moe_local_share_pct", "moe_load_max_over_mean"}


@pytest.mark.parametrize("run", THIRD_RECORDED, ids=[r["id"] for r in THIRD_RECORDED])
def test_a_chip_run_of_the_third_cell_on_record_is_correct_and_its_controls_are_not(run):
    with chipbench_on_path():
        third().check_recorded_run(load("correct/ppo_lm_afmoe.py", "chipbench_correct_ppo_lm_afmoe"),
                                   third_config()["correct_limits"], THIRD_ENTRIES, run)


def test_the_records_cover_the_seeds_the_third_cells_limits_were_set_from():
    assert len({r["seed"] for r in THIRD_RECORDED}) >= 3 and sum(len(r["controls"]) == 10 for r in THIRD_RECORDED) >= 3


# -- the convolution policy's cell ------------------------------------------------------------------------------
def fourth():
    """``chipbench/tests/test_ppo_lm_lfm2.py``, whose ``check_*`` functions hold the cases (call inside ``chipbench_on_path``)."""
    return load("tests/test_ppo_lm_lfm2.py", "chipbench_tests_test_ppo_lm_lfm2")


def fourth_config():
    with open(os.path.join(CHIPBENCH, "configs", FOURTH_CONFIG + ".json")) as f:
        return json.load(f)


with chipbench_on_path():
    _FOURTH = fourth()
    FOURTH_COUNTER_CASES, FOURTH_REGION_CASES = list(_FOURTH.COUNTER_CASES), sorted(_FOURTH.REGION_EXPECTED)
    FOURTH_ENTRIES, FOURTH_RECORDED = _FOURTH.recorded_runs()


@pytest.mark.parametrize("case", FOURTH_COUNTER_CASES)
def test_the_fourth_cells_counter_readers(case, monkeypatch):
    with chipbench_on_path():
        fourth().check_counter_reader(case, monkeypatch)


@pytest.mark.parametrize("name", FOURTH_REGION_CASES)
def test_the_fourth_cells_region_readers(name, monkeypatch):
    with chipbench_on_path():
        fourth().check_region_reader(name, monkeypatch)


def test_the_fourth_familys_flops_and_kernel_work_against_a_hand_count():
    with open(os.path.join(CHIPBENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    with chipbench_on_path():
        fourth().check_flops(load("flops/ppo_lm_lfm2.py", "chipbench_flops_ppo_lm_lfm2"), fourth_config(), peaks)


def test_the_benchmark_declares_the_fourth_cell_its_configuration_and_its_metrics():
    """One configuration, one cell and two per-layer metrics appended; the cell in every language-model metric's
    list whose reader reads it, and in no other."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, entry, conf = bench["workloads"][-1], bench["configs"][-1], fourth_config()
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (FOURTH_CELL, FOURTH_CONFIG, "longprompt_rl", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["name"] == conf["name"] == FOURTH_CONFIG and entry["source"] == conf["source"]
    assert entry["file"] == f"chipbench/configs/{FOURTH_CONFIG}.json"
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64, "vocab_size": 65536}
    assert conf["family"] == "ppo_lm_lfm2" and conf["parameters"] == 469_287_297
    for kind, name in (("flops", "ppo_lm_lfm2"), ("reference", "ppo_lm_lfm2_ref"), ("correct", "ppo_lm_lfm2"),
                       ("layers", "conv_ms"), ("layers", "conv_mix_ms")):
        assert os.path.isfile(os.path.join(CHIPBENCH, kind, name + ".py"))
    # every published width under its published key, unchanged
    widths = {"hidden_size": 2048, "intermediate_size": 11776, "moe_intermediate_size": 1536, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_experts_per_tok": 4, "conv_L_cache": 3, "routed_scaling_factor": 1,
              "norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: conf[k] for k in widths} == widths and all(conf["as_run"]["algo.lm." + k] == v for k, v in widths.items())
    assert conf["layer_types"][:5] == ["conv", "conv", "full_attention", "conv", "conv"] and len(conf["layer_types"]) == 40
    assert set(conf["correct_limits_why"]) >= {k for k, v in conf["correct_limits"].items() if v}
    for i, name in enumerate(("conv_ms", "conv_mix_ms")):
        assert bench["per_layer"][-2 + i] == {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
                                              "layer": "model blocks", "moves": "grad_steps_per_s", "workloads": [FOURTH_CELL]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    listed = {name for name, m in metrics.items() if FOURTH_CELL in m.get("workloads", [])}
    trinity = {name for name, m in metrics.items() if THIRD_CELL in m.get("workloads", [])}
    assert listed == (trinity - {"attn_window_ms", "attn_gate_ms"}) | {"conv_ms", "conv_mix_ms"}
    assert not listed & {"moe_local_share_pct", "moe_load_max_over_mean", "attn_mla_ms"}


@pytest.mark.parametrize("run", FOURTH_RECORDED, ids=[r["id"] for r in FOURTH_RECORDED])
def test_a_chip_run_of_the_fourth_cell_on_record_is_correct_and_its_controls_are_not(run):
    with chipbench_on_path():
        fourth().check_recorded_run(load("correct/ppo_lm_lfm2.py", "chipbench_correct_ppo_lm_lfm2"),
                                    fourth_config()["correct_limits"], FOURTH_ENTRIES, run)


def test_the_records_cover_the_seeds_the_fourth_cells_limits_were_set_from():
    assert len({r["seed"] for r in FOURTH_RECORDED}) >= 7 and sum(len(r["controls"]) == 6 for r in FOURTH_RECORDED) >= 2
