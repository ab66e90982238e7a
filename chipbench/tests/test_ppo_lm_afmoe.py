"""The gated grouped-query token-PPO family (`ppo_lm_afmoe`) at a size a CPU test run can hold: a rehearsal of its
cell through `run.py` with the control and the planted faults, its FLOP and kernel-work functions against hand
counts, the readers it reports on runs made by hand (`attn_gate_ms` among them), and what decides `correct` put
through the chip's own readings again (`ppo_lm_afmoe_readings.jsonl`). The cases that need no subprocess also run
in every PR's test run (tests/test_utils/test_chipbench_guards.py calls the `check_*` functions below)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from run import load_module
from test_ppo_lm_mla import check_recorded_run, recorder_of

CELL = "trinity_mini_ep8_longprompt_rl"
CONFIG = os.path.join(ROOT, "chipbench", "configs", "trinity_mini_26b_a3b_ep8.json")
READINGS = os.path.join(ROOT, "chipbench", "tests", "ppo_lm_afmoe_readings.jsonl")
# a dense layer and four routed ones ([window x 3, full, window]) with a shared expert, 4 of 8 experts held, 32
# positions, four windows of 8; hidden 256, so that the embedding's scale (16 x 0.02) keeps tokens apart under the
# sandwich norms and every held expert is routed to
TOY = [
    "algo.lm.hidden_size=256", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2", "algo.lm.head_dim=16",
    "algo.lm.intermediate_size=96", "algo.lm.moe_intermediate_size=32", "algo.lm.num_experts=8",
    "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2", "algo.lm.num_hidden_layers=5",
    "algo.lm.num_dense_layers=1", "algo.lm.sliding_window=8", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64",
    "env.prompt_len=24", "algo.rollout_steps=8", "env.num_envs=4", "algo.per_rank_batch_size=1", "fabric.accelerator=cpu",
]
CONTROLS = ("control_bfloat16", "fault_gate_dropped", "fault_qk_norm_dropped", "fault_post_norms_dropped",
            "fault_embed_scale_dropped", "fault_rope_on_full", "fault_bias_dropped", "fault_shared_skipped",
            "fault_expert_skipped_in_update", "fault_half_batch")


def config():
    with open(CONFIG) as f:
        return json.load(f)


def rehearse(*extra: str, timeout: int = 1500):
    cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL, "--seed", "3600000019",
           "--seconds", "2", "--trace", "0", "--rehearsal", "1", *extra]
    for o in TOY:
        cmd += ["--override", o]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          cwd=ROOT)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def test_rehearsal_line_control_and_faults(bench, tmp_path):
    """The line; the agreement with the reference (float32 on both sides here, so the program's decode through the
    window ring and the full buffer and its update sit on the reference's full forward); the control and every
    planted fault through the file's limits, each failing at least two."""
    dump = tmp_path / "run.json"
    proc, line = rehearse("--control", "1", "--dump", str(dump))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True and list(line)[-1] == "compared"
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert line["attempted"] > 0 and line["run"]["policy_steps"] == line["attempted"] * 8
    for exact in ("moe_dropped", "rollout_repeats_differ", "router_bias_change", "moments_unmoved", "entries_unmoved",
                  "controls_passing"):
        assert line["compared"][exact] == {"value": 0.0, "limit": 0.0}
    assert all(v["value"] < 1e-4 for v in line["compared"].values()), line["compared"]
    verdict = json.load(open(dump))["verdict"]
    assert all(v < 1e-4 for v in verdict["read_only"].values()), verdict["read_only"]
    assert set(config()["correct_limits"]) == set(verdict["read_only"]) | (set(line["compared"]) - {"controls_passing"})
    assert tuple(k for k in verdict if k.startswith(("control_", "fault_"))) == CONTROLS
    assert all(len(verdict[k]["fails"]) >= 2 for k in CONTROLS), {k: verdict[k]["fails"] for k in CONTROLS}
    assert "param_change" in verdict["control_bfloat16"]["fails"]  # bfloat16 weights do not move by lr 1e-5
    for label in ("fault_gate_dropped", "fault_qk_norm_dropped", "fault_post_norms_dropped", "fault_embed_scale_dropped",
                  "fault_shared_skipped"):
        assert {"rollout_logprob_mean", "rollout_value_mean"} <= set(verdict[label]["fails"]), label
    for label in ("fault_expert_skipped_in_update", "fault_half_batch"):  # the rollout stays the reference's
        assert verdict[label]["rollout_logprob_mean"] == 0.0
    assert verdict["fault_expert_skipped_in_update"]["param_change_experts"] > 0.99
    assert verdict["fault_expert_skipped_in_update"]["entries_unmoved"] == 12  # its three leaves in each of the four routed layers
    assert all(verdict[k]["router_bias_change"] == 0.0 for k in CONTROLS)
    detail = verdict["detail"]
    assert len(detail["program_losses"]) == 3 and np.shape(detail["program_change"]) == (3, len(detail["entries"]))
    assert sum("router_bias" in e for e in detail["entries"]) == 4


def test_new_files_refuse_a_program_without_the_path():
    """The cell's command on a tree that lacks the program's half (the commit before the policy came) stops
    soon and cleanly."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(tmp, "chipbench"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        proc = subprocess.run([sys.executable, os.path.join(tmp, "chipbench", "run.py"), "--workload", CELL, "--seed", "1",
                               "--seconds", "1", "--trace", "0"], capture_output=True, text=True, timeout=120,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp)
    assert proc.returncode != 0 and not proc.stdout.strip().startswith("{")


# -- the FLOP and kernel-work functions against hand counts -----------------------------------------------------
def check_flops(flops, conf, peaks):
    w = flops.widths(conf)
    assert (w["hidden"], w["heads"], w["kv_heads"], w["head_dim"], w["q"], w["kv"]) == (2048, 32, 4, 128, 4096, 512)
    assert (w["layers"], w["dense_layers"], w["routed_layers"], w["shared_width"], w["vocab"]) == (5, 1, 4, 1024, 25024)
    assert w["windows"] == [2048, 2048, 2048, 0, 2048]
    per_token = flops.token_flops(w)
    # per token and layer: the projections with the gate 54.5 MFLOP; the routed, shared and
    # router work 25.7 MFLOP (one expert of 6 x 2048 x 1024 on average: 8 of 128 a token, 16 held)
    assert per_token["projections"] == 5 * 2 * 2048 * (3 * 4096 + 2 * 512) and per_token["projections"] / 5 == pytest.approx(54.5e6, rel=1e-3)
    routed = (per_token["experts"] + per_token["shared"] + per_token["router"]) / 4
    assert routed == pytest.approx(25.7e6, rel=2e-3) and per_token["dense"] == 3 * 2 * 2048 * 6144
    assert per_token["gate_and_norms"] == 5 * (4096 + 4 * (4 * 2048 + 4096 + 512))
    assert flops.pair_flops(w) == 4 * 128 * 32
    # scores a token, 8 192 positions: 29.4 MFLOP on a window layer, 67.1 on the full one
    assert flops.visible_pairs(8192, 2048) * flops.pair_flops(w) / 8192 == pytest.approx(29.4e6, rel=2e-3)
    assert flops.visible_pairs(8192, 0) * flops.pair_flops(w) / 8192 == pytest.approx(67.1e6, rel=2e-3)
    # the window lets 0.44 of the causal area through
    assert flops.visible_pairs(8192, 2048) / flops.visible_pairs(8192, 0) == pytest.approx(0.4375, abs=2e-3)
    pairs = 4 * flops.visible_pairs(8192, 2048) + flops.visible_pairs(8192, 0)
    assert flops.pairs(w, 8192) == pairs
    forward = 8192 * sum(per_token.values()) + pairs * 16_384 + 256 * 2 * 2048 * 25_025
    parts = flops.parts(conf)
    assert parts["update"] == pytest.approx(8 * 3 * forward)
    assert flops.grad_steps_per_iteration(conf) == 8
    assert flops.flops_per_grad_step(conf) == pytest.approx(sum(parts.values()) / 8)
    # the routed kernel: counted from assignments; a decode call is charged one expert's weights, not sixteen
    one = flops.moe_kernel_work(conf, 1000.0, 0.0)["update"]
    assert one[0] == 4 * 1000 * 3 * 2 * 2048 * 1024 and flops.moe_kernel_work(conf, 2000.0, 0.0)["update"][0] == 2 * one[0]
    expert = 2 * 3 * 2048 * 1024
    rollout = flops.moe_kernel_work(conf, 0.0, 500.0)["rollout"]
    assert rollout == (500 * 3 * 2 * 2048 * 1024, 500 * 4 * (2 * 2048 + 3 * 1024) + 4 * 8 * 16 * expert + 4 * 256 * expert)
    attention = flops.attention_kernel_work(conf)
    # the update's attention: one forward (the remat keeps the kernel's residuals) and the backward's five products
    assert attention["update"][0] == pytest.approx(8 * pairs * 16_384 * 3.5)
    assert attention["prefill"] == (8 * flops.pairs(w, 7936) * 16_384, 8 * 5 * 4 * 7936 * (2 * 4096 + 2 * 512))
    assert flops.roofline_seconds({"a": (197e12, 0.0), "b": (0.0, 819e9)}, peaks) == pytest.approx(2.0)


def test_flops_against_a_hand_count():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))["devices"]["TPU v5 lite"]
    check_flops(load_module("flops", "ppo_lm_afmoe"), config(), peaks)


# -- the readers this cell reports, on runs made by hand ---------------------------------------------------------
T_OPEN, T_CLOSE = 100.0, 124.0
COUNTER_CASES = {
    # name: (reader, the `iter` spans' counters, expected)
    "bias_moved": ("moe_bias_moved_pct", [{"moe_bias_moved": 50, "moe_bias_movable": 1000}, {"moe_bias_moved": 150, "moe_bias_movable": 1000}], 10.0),
    # four window rings of 2 048 slots and one full buffer of 8 192, keys and values of 4 heads x 128, 8 sequences, float32
    "cache": ("rollout_cache_mib", [{"rollout_cache_bytes": 8 * (4 * 2048 + 8192) * 2 * 4 * 128 * 4}], 512.0),
    "no_cache_counter": ("rollout_cache_mib", [{"moe_bias_moved": 1, "moe_bias_movable": 2}], None),
}


def check_counter_reader(case, monkeypatch):
    from sheeprl_tpu.utils import profiler

    reader, blocks, expected = COUNTER_CASES[case]
    spans = [(T_OPEN + 6.0 * i, T_OPEN + 6.0 * i + 5.9, counters) for i, counters in enumerate(blocks)]
    before = (T_OPEN - 7.0, T_OPEN - 1.0, {"moe_bias_moved": 999, "moe_bias_movable": 999, "rollout_cache_bytes": 2**40})
    monkeypatch.setattr(profiler, "snapshot", recorder_of(before, *spans).snapshot)
    run = {"window": {"t_open": T_OPEN, "t_close": T_CLOSE, "seconds": T_CLOSE - T_OPEN}}
    got = load_module("layers", reader).read(run)
    assert got == (None if expected is None else pytest.approx(expected))


LM_PROGRAM = "ppo_anakin_lm_block/1"
# instruction: (seconds of self time in the traced block, outermost region, innermost scope, backward)
LM_OPS = {
    "fusion.1": (0.40, "rollout.prefill", "lm.attn_gate", False),  # the gate in prefill: the phase's, and the gate's
    "splash_mqa_fwd.2": (0.90, "lm.attn_window", "kernel.window_attention", False),
    "fusion.3": (0.30, "lm.attn_window", "lm.attn_window", False),
    "fusion.4": (0.12, "lm.attn_window", "lm.attn_gate", False),
    "fusion.5": (0.20, "lm.attn_global", "lm.attn_gate", True),
    "fusion.6": (0.08, "lm.attn_global", "lm.attn_global", True),
    "gmm.7": (0.20, "lm.moe", "kernel.moe_grouped_ffn", True),
    "copy.8": (0.30, None, None, False),
}
REGION_EXPECTED = {"attn_gate_ms": 1e3 * (0.40 + 0.12 + 0.20) / 8, "attn_window_ms": 1e3 * (0.90 + 0.30 + 0.12) / 8,
                   "attn_global_ms": 1e3 * (0.20 + 0.08) / 8}


def check_region_reader(name, monkeypatch):
    from sheeprl_tpu.utils import profiler
    from sheeprl_tpu.utils.profiler import Recorder, Span

    rec = Recorder()
    span = Span(rec, "burst.dispatch", 0, {"program": LM_PROGRAM})
    span.t_start, span.t_end, span.thread = 111.2, 115.2, 1
    rec._ring.append(span)
    table = {k: {"outer": v[1], "scope": v[2], "backward": v[3]} for k, v in LM_OPS.items() if v[1]}
    monkeypatch.setattr(profiler, "snapshot", rec.snapshot)
    monkeypatch.setattr(profiler, "scope_table", lambda program: table if program == LM_PROGRAM else None)
    run = {"trace_info": {"t_start": 111.0, "t_stop": 116.0}, "traffic": {"burst_program": "ppo_anakin_lm_block"},
           "trace": {"grants": 8, "busy_s": 4.0, "devices": [{
               "ops_self_s": {f"{k} f32[8,8192]": v[0] for k, v in LM_OPS.items() if not k.startswith(("splash", "gmm"))},
               "custom_calls": {f"{k} (tuple)": {"seconds": v[0]} for k, v in LM_OPS.items() if k.startswith(("splash", "gmm"))}}]}}
    reader = load_module("layers", name)
    assert reader.read(run) == pytest.approx(REGION_EXPECTED[name], rel=1e-9)
    assert reader.read({**{k: v for k, v in run.items() if k != "_attributed"}, "trace": None}) is None
    # a program whose table has no such scope (the parent, another policy): nothing at all
    monkeypatch.setattr(profiler, "scope_table", lambda program: {k: v for k, v in table.items() if v["scope"] != "lm.attn_gate"})
    if name == "attn_gate_ms":
        assert reader.read({k: v for k, v in run.items() if k != "_attributed"}) is None
    monkeypatch.setattr(profiler, "scope_table", lambda program: None)
    assert reader.read({k: v for k, v in run.items() if k != "_attributed"}) is None


@pytest.mark.parametrize("case", list(COUNTER_CASES))
def test_counter_reader(case, monkeypatch):
    check_counter_reader(case, monkeypatch)


@pytest.mark.parametrize("name", sorted(REGION_EXPECTED))
def test_region_reader(name, monkeypatch):
    check_region_reader(name, monkeypatch)


# -- what decides `correct`, on the chip's own readings -----------------------------------------------------------
def recorded_runs():
    if not os.path.isfile(READINGS):
        return [], []
    with open(READINGS) as f:
        entries = json.loads(f.readline())["entries"]
        return entries, [json.loads(line) for line in f]


ENTRIES, RUNS = recorded_runs()


@pytest.mark.parametrize("run", RUNS, ids=[r["id"] for r in RUNS])
def test_recorded_run_is_correct_and_its_controls_are_not(run):
    check_recorded_run(load_module("correct", "ppo_lm_afmoe"), config()["correct_limits"], ENTRIES, run)


def test_the_records_cover_the_seeds_the_limits_were_set_from():
    assert len({r["seed"] for r in RUNS}) >= 3 and sum(bool(r["controls"]) for r in RUNS) >= 3
    assert all(len(r["controls"]) == len(CONTROLS) for r in RUNS if r["controls"])
