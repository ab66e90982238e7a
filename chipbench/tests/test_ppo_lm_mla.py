"""The latent-attention token-PPO family (`ppo_lm_mla`) at a size a CPU test run can hold: a rehearsal of its
cell through `run.py` with the control and the planted faults, its FLOP and kernel-work functions against hand
counts, its four readers on runs made by hand, and what decides `correct` put through the chip's own readings
again (`ppo_lm_mla_readings.jsonl`). The cases that need no subprocess also run in every PR's test run
(tests/test_utils/test_chipbench_guards.py calls the `check_*` functions below)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from run import load_module

CELL = "kanana2_ep8_longprompt_rl"
CONFIG = os.path.join(ROOT, "chipbench", "configs", "kanana2_30b_a3b_ep8.json")
READINGS = os.path.join(ROOT, "chipbench", "tests", "ppo_lm_mla_readings.jsonl")
# a dense layer and two routed ones with shared experts, 4 of 8 experts held, latent 32, 32 positions
TOY = [
    "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=4",
    "algo.lm.qk_nope_head_dim=16", "algo.lm.qk_rope_head_dim=8", "algo.lm.qk_head_dim=24", "algo.lm.v_head_dim=16",
    "algo.lm.kv_lora_rank=32", "algo.lm.intermediate_size=96", "algo.lm.moe_intermediate_size=32",
    "algo.lm.n_routed_experts=8", "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2",
    "algo.lm.num_hidden_layers=3", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64", "env.prompt_len=24",
    "algo.rollout_steps=8", "env.num_envs=4", "algo.per_rank_batch_size=1", "fabric.accelerator=cpu",
]


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
    """The line; the agreement with the reference (float32 on both sides here, so the program's absorbed decode
    and expanded update sit on the reference's expanded forward); the control and the planted faults through the
    file's limits. At toy widths every score is near 0 and attention near uniform, so the rotary part dropped is
    invisible here; on the chip it fails the rollout's means (the records below)."""
    dump = tmp_path / "run.json"
    proc, line = rehearse("--control", "1", "--dump", str(dump))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["rehearsal"] is True and list(line)[-1] == "compared"
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert line["attempted"] > 0 and line["run"]["policy_steps"] == line["attempted"] * 8
    for exact in ("moe_dropped", "rollout_repeats_differ", "router_bias_change"):
        assert line["compared"][exact] == {"value": 0.0, "limit": 0.0}
    assert all(v["value"] < 1e-4 for k, v in line["compared"].items() if k != "controls_passing"), line["compared"]
    verdict = json.load(open(dump))["verdict"]
    assert all(v < 1e-4 for v in verdict["read_only"].values()), verdict["read_only"]
    assert set(config()["correct_limits"]) == set(verdict["read_only"]) | (set(line["compared"]) - {"controls_passing"})
    labels = [k for k in verdict if k.startswith(("control_", "fault_"))]
    assert len(labels) == 7
    passing = [k for k in labels if not verdict[k]["fails"]]
    assert passing in ([], ["fault_rope_dropped"]) and line["compared"]["controls_passing"]["value"] == len(passing)
    assert "param_change" in verdict["control_bfloat16"]["fails"]  # bfloat16 weights do not move by lr 1e-5
    assert {"rollout_logprob_mean", "rollout_value_mean"} <= set(verdict["fault_latent_norm_dropped"]["fails"])
    for label in ("fault_expert_skipped_in_update", "fault_half_batch"):  # the rollout stays the reference's
        assert verdict[label]["rollout_logprob_mean"] == 0.0
    assert verdict["fault_expert_skipped_in_update"]["param_change_experts"] > 0.99
    assert verdict["fault_expert_skipped_in_update"]["entries_unmoved"] == 6  # its three leaves in each of the two routed layers
    assert all(verdict[k]["router_bias_change"] == 0.0 for k in labels)
    detail = verdict["detail"]
    assert len(detail["program_losses"]) == 3 and np.shape(detail["program_change"]) == (3, len(detail["entries"]))
    assert sum("router_bias" in e for e in detail["entries"]) == 2


def test_new_files_refuse_a_program_without_the_path():
    """What the driver tries on the parent: the cell's command on a tree that lacks the program's half stops
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
    assert (w["hidden"], w["heads"], w["nope"], w["rope"], w["v"], w["latent"]) == (2048, 32, 128, 64, 128, 512)
    assert (w["layers"], w["dense_layers"], w["routed_layers"], w["shared_width"], w["vocab"]) == (5, 1, 4, 1536, 16032)
    per_token = flops.token_flops(w)
    # ISSUE 36's arithmetic: 26 345 984 attention parameters a layer, 512 of them the latent norm's
    assert per_token["projections"] == 2 * (26_345_984 - 512) * 5
    assert per_token["dense"] == 2 * 37_748_736 and per_token["shared"] == 2 * 9_437_184 * 4
    assert per_token["experts"] == 0.75 * 2 * 4_718_592 * 4 and per_token["router"] == 2 * 262_144 * 4
    assert flops.pair_flops(w) == 20_480  # (192 + 128) multiply-adds a pair and head, 32 heads
    assert flops.visible_pairs(8) == 36 and flops.visible_pairs(8, first=6) == 15
    full = 8192 * 8193 // 2
    parts = flops.parts(conf)
    forward = 8192 * sum(per_token.values()) + 5 * full * 20_480 + 256 * 2 * 2048 * 16_033
    assert parts["update"] == pytest.approx(8 * 3 * forward)
    # the scores are about 45% of a forward's operations at these widths (ISSUE 36)
    assert 0.40 < 5 * full * 20_480 / forward < 0.50
    assert flops.grad_steps_per_iteration(conf) == 8
    assert flops.flops_per_grad_step(conf) == pytest.approx(sum(parts.values()) / 8)
    # the routed kernel: counted from assignments; a decode call is charged one expert's weights, not sixteen
    one = flops.moe_kernel_work(conf, 1000.0, 0.0)["update"]
    two = flops.moe_kernel_work(conf, 2000.0, 0.0)["update"]
    assert one[0] == 4 * 1000 * 3 * 2 * 2048 * 768 and two[0] == 2 * one[0]
    rollout = flops.moe_kernel_work(conf, 0.0, 500.0)["rollout"]
    expert = 2 * 3 * 2048 * 768
    assert rollout[0] == 500 * 3 * 2 * 2048 * 768
    assert rollout[1] == 500 * 4 * (2 * 2048 + 3 * 768) + 4 * 8 * 16 * expert + 4 * 256 * expert
    attention = flops.attention_kernel_work(conf)
    assert attention["update"][0] == pytest.approx(8 * 5 * full * 20_480 * 4.5)
    assert attention["prefill"][1] == 8 * 5 * 4 * 7936 * 32 * (2 * 192 + 2 * 128)
    assert flops.roofline_seconds({"a": (197e12, 0.0), "b": (0.0, 819e9)}, peaks) == pytest.approx(2.0)


def test_flops_against_a_hand_count():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))["devices"]["TPU v5 lite"]
    check_flops(load_module("flops", "ppo_lm_mla"), config(), peaks)


# -- the four readers on runs made by hand -----------------------------------------------------------------------
T_OPEN, T_CLOSE = 100.0, 124.0
COUNTER_CASES = {
    # name: (reader, the `iter` spans' counters, expected)
    "bias_moved": ("moe_bias_moved_pct", [{"moe_bias_moved": 100, "moe_bias_movable": 1000}, {"moe_bias_moved": 300, "moe_bias_movable": 1000}], 20.0),
    "bias_idle": ("moe_bias_moved_pct", [{"moe_bias_moved": 0, "moe_bias_movable": 1000}], 0.0),
    "no_bias_counter": ("moe_bias_moved_pct", [{"moe_compact_calls": 64, "moe_compactable_calls": 64}], None),  # the other policy
    "no_spans": ("moe_bias_moved_pct", [], None),
    "cache": ("rollout_cache_mib", [{"rollout_cache_bytes": 754_974_720}, {"rollout_cache_bytes": 754_974_720}], 720.0),
    "no_cache_counter": ("rollout_cache_mib", [{"moe_bias_moved": 1, "moe_bias_movable": 2}], None),  # the parent's spans
    "no_spans_cache": ("rollout_cache_mib", [], None),
}


def recorder_of(*iters):
    """A recorder holding one `iter` span per `(t_start, t_end, counters)`."""
    from sheeprl_tpu.utils.profiler import Recorder, Span

    rec = Recorder()
    for t0, t1, counters in iters:
        span = Span(rec, "iter", 0, dict(iter_num=1, policy_step=0, grad_steps=8, **counters))
        span.t_start, span.t_end = t0, t1
        rec._ring.append(span)
    return rec


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
# instruction: (seconds of self time in the traced block, outermost region, innermost scope)
LM_OPS = {
    "fusion.1": (0.40, "rollout.prefill", "lm.attn_mla"),  # the region inside the rollout counts to the phase
    "splash_mqa_fwd.2": (0.90, "lm.attn_mla", "kernel.window_attention"),
    "fusion.3": (0.30, "lm.attn_mla", "lm.attn_mla"),
    "fusion.4": (0.16, "lm.ffn_shared", "lm.ffn_shared"),
    "fusion.5": (0.08, "rollout.decode", "lm.ffn_shared"),
    "gmm.6": (0.20, "lm.moe", "kernel.moe_grouped_ffn"),
    "copy.7": (0.30, None, None),
}
REGION_EXPECTED = {"attn_mla_ms": 1e3 * (0.90 + 0.30) / 8, "ffn_shared_ms": 1e3 * 0.16 / 8}


def check_region_reader(name, monkeypatch):
    from sheeprl_tpu.utils import profiler
    from sheeprl_tpu.utils.profiler import Recorder, Span

    rec = Recorder()
    span = Span(rec, "burst.dispatch", 0, {"program": LM_PROGRAM})
    span.t_start, span.t_end, span.thread = 111.2, 115.2, 1
    rec._ring.append(span)
    table = {k: {"outer": v[1], "scope": v[2], "backward": False} for k, v in LM_OPS.items() if v[1]}
    monkeypatch.setattr(profiler, "snapshot", rec.snapshot)
    monkeypatch.setattr(profiler, "scope_table", lambda program: table if program == LM_PROGRAM else None)
    run = {"trace_info": {"t_start": 111.0, "t_stop": 116.0}, "traffic": {"burst_program": "ppo_anakin_lm_block"},
           "trace": {"grants": 8, "busy_s": 4.0, "devices": [{
               "ops_self_s": {f"{k} f32[8,8192]": v[0] for k, v in LM_OPS.items() if not k.startswith(("splash", "gmm"))},
               "custom_calls": {f"{k} (tuple)": {"seconds": v[0]} for k, v in LM_OPS.items() if k.startswith(("splash", "gmm"))}}]}}
    reader = load_module("layers", name)
    assert reader.read(run) == pytest.approx(REGION_EXPECTED[name], rel=1e-9)
    assert reader.read({**{k: v for k, v in run.items() if k != "_attributed"}, "trace": None}) is None
    # a program whose table has no such region (the parent, the other policy): 0 of it, or nothing at all
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


def _readings(r):
    return {"losses": np.asarray(r["losses"], np.float64), "mu_norm": np.asarray(r["moment"], np.float64),
            "dp_norm": np.asarray(r["change"], np.float64)}


def through_limits(correct, limits, got, want, entries, recorded):
    """`(numbers, failing)` of one set of readings: the update's numbers by today's definitions, the rollout's
    (and the exact ones) as the run compared them, each beside the file's limit."""
    values = {**recorded, **correct.update_numbers(got, want, entries)}
    numbers, _ = correct.through_limits(values, limits)
    return numbers, correct.failing(numbers)


def check_recorded_run(correct, limits, entries, run):
    """A run of the program on record is `correct` with every compared number at or under half its limit; every
    control and planted fault on record fails at least two limits, one of them by 1.5x."""
    want = _readings(run["reference"])
    numbers, fails = through_limits(correct, limits, _readings(run["program"]), want, entries, run["program"]["recorded"])
    assert not fails and set(numbers) >= {"grad_norm", "loss_value", "loss_policy", "adam_moment", "param_change",
                                          "param_change_expert_leaves", "moments_unmoved", "entries_unmoved",
                                          "rollout_logprob_mean", "rollout_value_mean", "router_bias_change"}
    worst = max((v["value"] / v["limit"], k) for k, v in numbers.items() if v["limit"] > 0)
    assert worst[0] <= 0.5, worst
    assert numbers["router_bias_change"]["value"] == 0.0 and numbers["moe_dropped"]["value"] == 0.0
    for label, other in run["controls"].items():
        numbers, fails = through_limits(correct, limits, _readings(other), want, entries, other["recorded"])
        over = [numbers[k]["value"] / numbers[k]["limit"] if numbers[k]["limit"] > 0 else float("inf") for k in fails]
        assert len(fails) >= 2 and max(over) >= 1.5, (label, fails)
        if label == "fault_expert_skipped_in_update":  # that expert's three leaves in each of the four routed layers never move
            assert numbers["moments_unmoved"]["value"] == numbers["entries_unmoved"]["value"] == 12


ENTRIES, RUNS = recorded_runs()


@pytest.mark.parametrize("run", RUNS, ids=[r["id"] for r in RUNS])
def test_recorded_run_is_correct_and_its_controls_are_not(run):
    check_recorded_run(load_module("correct", "ppo_lm_mla"), config()["correct_limits"], ENTRIES, run)


def test_the_records_cover_the_seeds_the_limits_were_set_from():
    assert len({r["seed"] for r in RUNS}) >= 24 and sum(bool(r["controls"]) for r in RUNS) >= 3
    assert all(len(r["controls"]) == 7 for r in RUNS if r["controls"])
