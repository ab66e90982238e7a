"""The token-PPO family (`ppo_lm`) at a size a CPU test run can hold: a
rehearsal of its cell through `run.py`, its FLOP function against a hand
count, the bfloat16 control and the planted faults failing the comparison, and
its counter and roofline readers on a run made by hand."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from run import load_module

CELL = "smallthinker_ep4_longprompt_rl"
# a four-layer [0,1,1,1] stack, 4 of 8 experts held, window 8, 32 positions
TOY = [
    "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2", "algo.lm.head_dim=16",
    "algo.lm.moe_ffn_hidden_size=32", "algo.lm.moe_num_primary_experts=8", "algo.lm.moe_num_active_primary_experts=2",
    "algo.lm.experts_held=4", "algo.lm.expert_offset=2", "algo.lm.num_hidden_layers=4", "algo.lm.sliding_window_size=8",
    "algo.lm.vocab_size=96", "algo.lm.vocab_held=64", "env.prompt_len=24", "algo.rollout_steps=8", "env.num_envs=4",
    "algo.per_rank_batch_size=1", "fabric.accelerator=cpu",
]


def rehearse(*extra: str, timeout: int = 900):
    cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL, "--seed", "3000000019",
           "--seconds", "2", "--trace", "0", "--rehearsal", "1", *extra]
    for o in TOY:
        cmd += ["--override", o]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          cwd=ROOT)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def test_rehearsal_line_control_and_faults(bench, tmp_path):
    """One run for three things: the line, the agreement with the reference
    (float32 on both sides here, so the program sits on it), and the control
    and the planted faults, each put through the file's limits and each
    failing at least one: a run with `--control 1` is `correct` only then."""
    dump = tmp_path / "run.json"
    proc, line = rehearse("--control", "1", "--dump", str(dump))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True and list(line)[-1] == "compared"
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(v["value"] is None for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["run"]["policy_steps"] == line["attempted"] * 8  # 4 steps, 4 envs x 8 tokens an iteration
    assert line["compared"]["moe_dropped"] == {"value": 0.0, "limit": 0.0}
    assert line["compared"]["rollout_repeats_differ"] == {"value": 0.0, "limit": 0.0}
    assert line["compared"]["controls_passing"] == {"value": 0.0, "limit": 0.0}
    verdict = json.load(open(dump))["verdict"]
    assert all(v < 1e-4 for v in verdict["read_only"].values()), verdict["read_only"]
    assert all(v["value"] < 1e-4 for v in line["compared"].values()), line["compared"]
    limits = json.load(open(os.path.join(ROOT, "chipbench", "configs", "smallthinker_21b_a3b_ep4.json")))["correct_limits"]
    assert set(limits) == set(verdict["read_only"]) | (set(line["compared"]) - {"controls_passing"})
    control, window, expert, in_update, half = (verdict[k] for k in (
        "control_bfloat16", "fault_window_dropped", "fault_expert_skipped", "fault_expert_skipped_in_update",
        "fault_half_batch"))
    assert all(v["fails"] for v in (control, window, expert, in_update, half))
    assert "param_change" in control["fails"]  # bfloat16 weights do not move by lr 1e-5
    assert {"rollout_logprob_mean", "rollout_value_mean"} <= set(window["fails"])
    # a fault in the update alone leaves the rollout on the reference and is still caught, by the update's numbers
    for fault in (in_update, half):
        assert fault["rollout_logprob_mean"] == 0.0 and not any(name.startswith("rollout") for name in fault["fails"])
    assert in_update["param_change_experts"] > 0.99  # the skipped expert's weights do not move at all
    assert half["grad_direction"] > 0.5 and {"loss_value_first", "param_change"} <= set(half["fails"])
    # three steps were granted, one dispatch each, and followed
    detail = verdict["detail"]
    assert len(detail["program_losses"]) == len(detail["reference_losses"]) == 3
    assert np.shape(detail["program_change"]) == (3, len(detail["entries"]))


def test_state_left_unchanged_is_not_correct():
    proc, line = rehearse("--fault", "state_unchanged")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["compared"]["param_change"]["value"] > 0.99  # nothing moved reads 1
    assert line["compared"]["param_change_experts"]["value"] > 0.99
    assert line["compared"]["rollout_logprob_mean"]["value"] < 1e-5  # the rest still sits on the reference


def test_new_files_refuse_a_program_without_the_path():
    """What the driver tries on the parent: the cell's command on a tree that
    lacks the program's half has to stop soon and cleanly, not hang."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(tmp, "chipbench"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        proc = subprocess.run([sys.executable, os.path.join(tmp, "chipbench", "run.py"), "--workload", CELL, "--seed", "1",
                               "--seconds", "1", "--trace", "0"], capture_output=True, text=True, timeout=120,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp)
    assert proc.returncode != 0 and not proc.stdout.strip().startswith("{")


# -- the FLOP function against a hand count ------------------------------------
def test_flops_against_a_hand_count():
    flops = load_module("flops", "ppo_lm")
    conf = json.load(open(os.path.join(ROOT, "chipbench", "configs", "smallthinker_21b_a3b_ep4.json")))
    w = flops.widths(conf)
    assert (w["hidden"], w["q"], w["kv"], w["expert_width"], w["vocab"]) == (2560, 3584, 512, 768, 37984)
    per_token = flops.token_flops(w)
    assert per_token["projections"] == 2 * 20_971_520  # ISSUE 32's table: 20 971 520 attention parameters a layer
    assert per_token["experts"] == 1.5 * 3 * 2 * 2560 * 768  # 6 x 16 / 64 experts a token
    # a causal mask over 8 positions lets 36 pairs through, a window of 3 lets 1 + 2 + 6 x 3
    assert flops.visible_pairs(8, 0) == 36 and flops.visible_pairs(8, 3) == 21 and flops.visible_pairs(8, 3, first=6) == 6
    full, window = flops.visible_pairs(8192, 0), flops.visible_pairs(8192, 4096)
    assert full == 8192 * 8193 // 2 and window == 4096 * 4097 // 2 + 4096 * 4096
    parts = flops.parts(conf)
    # the update: 8 sequences x 3 x (8192 tokens x 4 layers + the scores of 1 full and 3 window layers + 256 head rows)
    forward = 8192 * 4 * sum(per_token.values()) + (full + 3 * window) * 4 * 128 * 28 + 256 * 2 * 2560 * 37985
    assert parts["update"] == pytest.approx(8 * 3 * forward)
    assert 80e12 < parts["update"] < 90e12 and 20e12 < parts["prefill"] < 30e12  # ISSUE 32's sizing: ~85 and ~27 TFLOP
    assert flops.grad_steps_per_iteration(conf) == 8
    assert flops.flops_per_grad_step(conf) == pytest.approx(sum(parts.values()) / 8)


def test_kernel_work_is_counted_from_assignments():
    flops = load_module("flops", "ppo_lm")
    conf = json.load(open(os.path.join(ROOT, "chipbench", "configs", "smallthinker_21b_a3b_ep4.json")))
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))["devices"]["TPU v5 lite"]
    one = flops.moe_kernel_work(conf, 1000.0, 0.0)["update"]
    two = flops.moe_kernel_work(conf, 2000.0, 0.0)["update"]
    assert one[0] == 4 * 1000 * 3 * 2 * 2560 * 768 and two[0] == 2 * one[0]  # forward twice, backward twice a forward
    rollout = flops.moe_kernel_work(conf, 0.0, 500.0)["rollout"]
    assert rollout[0] == 500 * 3 * 2 * 2560 * 768 and rollout[1] > 4 * (8 + 256) * 2 * 16 * 3 * 2560 * 768 * 0.99
    attention = flops.attention_kernel_work(conf)
    pairs = flops.visible_pairs(8192, 0) + 3 * flops.visible_pairs(8192, 4096)
    assert attention["update"][0] == pytest.approx(8 * pairs * 4 * 128 * 28 * 4.5)
    assert flops.roofline_seconds({"a": (197e12, 0.0), "b": (0.0, 819e9)}, peaks) == pytest.approx(2.0)


# -- the counter and roofline readers on a run made by hand --------------------
def test_counter_and_roofline_readers(monkeypatch):
    conf = json.load(open(os.path.join(ROOT, "chipbench", "configs", "smallthinker_21b_a3b_ep4.json")))
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))["devices"]["TPU v5 lite"]
    made = 8 * 8192 * 6  # assignments a layer in one iteration's update
    block = {"iters": 1, "counters": {"moe_local_assignments": [[made // 4] * 4], "moe_rollout_assignments": [[100000] * 4],
                                      "moe_max_expert_load": [[900, 800, 1000, 1536]], "moe_dropped": [0]}}
    run = {"config": conf, "peaks": peaks, "flushes": [block, block], "trace_info": {"flush_start": 1, "flush_stop": 2},
           "trace": {"grants": 8}}
    assert load_module("layers", "moe_local_share_pct").read(run) == pytest.approx(25.0)
    assert load_module("layers", "moe_load_max_over_mean").read(run) == pytest.approx(1536 / 768)
    import layers._program_record as record

    flops = load_module("flops", "ppo_lm")
    work = flops.moe_kernel_work(conf, float(made), 400000.0)
    ideal_ms = 1e3 * flops.roofline_seconds(work, peaks) / 8
    monkeypatch.setattr(record, "kernel_ms", lambda run, kernel: 2 * ideal_ms if kernel == "moe_grouped_ffn" else None)
    assert load_module("layers", "kernel_moe_grouped_ffn_roofline_pct").read(run) == pytest.approx(50.0)
    assert load_module("layers", "kernel_window_attention_roofline_pct").read(run) is None  # no event of it in the trace
    # a program without the counters (the parent of the PR that added them): nothing to read, nothing raised
    bare = {**run, "flushes": [{"t0": 0.0, "t1": 1.0, "chunk": 16}]}
    for name in ("moe_local_share_pct", "moe_load_max_over_mean", "kernel_moe_grouped_ffn_roofline_pct"):
        assert load_module("layers", name).read(bare) is None
    dreamer = {**run, "config": json.load(open(os.path.join(ROOT, "chipbench", "configs", "dreamer_v3_S.json")))}
    assert load_module("layers", "moe_local_share_pct").read(dreamer) is None
