"""The convolution token-PPO family (`ppo_lm_lfm2`) at a size a CPU test run can hold: a rehearsal of its cell
through `run.py` with the control and the planted faults, its FLOP and kernel-work functions against hand counts,
the readers it reports on runs made by hand (`conv_ms` and `conv_mix_ms` among them), and what decides `correct`
put through the chip's own readings again (`ppo_lm_lfm2_readings.jsonl`). The cases that need no subprocess also
run in every PR's test run (tests/test_utils/test_chipbench_guards.py calls the `check_*` functions below)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from run import load_module
from test_ppo_lm_mla import check_recorded_run, recorder_of

CELL = "lfm2_ep8_longprompt_rl"
CONFIG = os.path.join(ROOT, "chipbench", "configs", "lfm2_24b_a2b_ep8.json")
READINGS = os.path.join(ROOT, "chipbench", "tests", "ppo_lm_lfm2_readings.jsonl")
# a dense layer and four routed ones ([conv, conv, full, conv, conv]) without a shared expert, 4 of 8 experts held,
# 32 positions
TOY = [
    "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2",
    "algo.lm.intermediate_size=96", "algo.lm.moe_intermediate_size=32", "algo.lm.num_experts=8",
    "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2", "algo.lm.num_hidden_layers=5",
    "algo.lm.num_dense_layers=1", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64", "env.prompt_len=24",
    "algo.rollout_steps=8", "env.num_envs=4", "algo.per_rank_batch_size=1", "fabric.accelerator=cpu",
]
CONTROLS = ("control_bfloat16", "fault_conv_gate_dropped", "fault_taps_reversed", "fault_conv_state_dropped",
            "fault_qk_norm_dropped", "fault_head_untied")
# at toy widths (hidden 64, matrices of 0.02) the convolution's output is a thousandth of the residual stream's, so
# the three faults of the convolution move nothing a limit reads; at the cell's widths it is as large as the stream
# (PERF.md, section 2): there they are caught
QUIET_AT_TOY_WIDTHS = ("fault_conv_gate_dropped", "fault_taps_reversed", "fault_conv_state_dropped")


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
    convolution states and the full buffer and its update sit on the reference's full forward); the control and the
    planted faults through the file's limits, each that toy widths can show failing at least two."""
    dump = tmp_path / "run.json"
    proc, line = rehearse("--control", "1", "--dump", str(dump))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["rehearsal"] is True and list(line)[-1] == "compared"
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert line["attempted"] > 0 and line["run"]["policy_steps"] == line["attempted"] * 8
    for exact in ("moe_dropped", "rollout_repeats_differ", "router_bias_change", "moments_unmoved", "entries_unmoved"):
        assert line["compared"][exact] == {"value": 0.0, "limit": 0.0}
    assert all(v["value"] < 1e-4 for k, v in line["compared"].items() if k != "controls_passing"), line["compared"]
    verdict = json.load(open(dump))["verdict"]
    assert all(v < 1e-4 for v in verdict["read_only"].values()), verdict["read_only"]
    assert set(config()["correct_limits"]) == set(verdict["read_only"]) | (set(line["compared"]) - {"controls_passing"})
    assert tuple(k for k in verdict if k.startswith(("control_", "fault_"))) == CONTROLS
    loud = [k for k in CONTROLS if k not in QUIET_AT_TOY_WIDTHS]
    assert all(len(verdict[k]["fails"]) >= 2 for k in loud), {k: verdict[k]["fails"] for k in loud}
    assert set(verdict["numbers"]["controls_passing"]["note"].split(", ")) <= set(QUIET_AT_TOY_WIDTHS)
    assert "param_change" in verdict["control_bfloat16"]["fails"]  # bfloat16 weights do not move by lr 1e-5
    assert {"param_change", "param_change_tied"} <= set(verdict["fault_head_untied"]["fails"])
    assert verdict["fault_head_untied"]["rollout_logprob_mean"] == 0.0  # the rollout stays the reference's
    assert verdict["fault_qk_norm_dropped"]["entries_unmoved"] == 2  # the attention layer's two norm scales
    assert all(verdict[k]["router_bias_change"] == 0.0 for k in CONTROLS)
    detail = verdict["detail"]
    assert len(detail["program_losses"]) == 3 and np.shape(detail["program_change"]) == (3, len(detail["entries"]))
    assert sum("router_bias" in e for e in detail["entries"]) == 4 and "['head']" not in detail["entries"]


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
    assert (w["hidden"], w["heads"], w["kv_heads"], w["head_dim"], w["q"], w["kv"]) == (2048, 32, 8, 64, 2048, 512)
    assert (w["layers"], w["dense_layers"], w["routed_layers"], w["conv_layers"], w["attention_layers"]) == (5, 1, 4, 4, 1)
    assert (w["conv_kernel"], w["dense_width"], w["expert_width"], w["vocab"]) == (3, 11776, 1536, 8192)
    per_token = flops.token_flops(w)
    # per token: the four convolutions' projections 134.2 MFLOP (a third of a token's forward), attention's
    # projections 21.0, the dense layer 144.7, the held experts and the router 38.8 (half an expert of 6 x 2048 x
    # 1536 a routed layer: 4 of 64 a token, 8 held)
    assert per_token["conv"] == 4 * 2 * 2048 * 4 * 2048 and per_token["conv"] == pytest.approx(134.2e6, rel=1e-3)
    assert per_token["conv_mix"] == 4 * 2048 * (1 + 5 + 1)
    assert per_token["attention"] == 2 * 2048 * (2 * 2048 + 2 * 512) == pytest.approx(21.0e6, rel=2e-3)
    assert per_token["dense"] == 3 * 2 * 2048 * 11776
    assert per_token["experts"] + per_token["router"] == pytest.approx(38.8e6, rel=1e-3)
    assert per_token["norms"] == 4 * (2 * 2048 * 5 + 2048 + 512)
    assert flops.pair_flops(w) == 4 * 64 * 32
    # scores a token at the mean context of 8 192 positions: 33.6 MFLOP; the head 33.6 a position it is applied to
    assert flops.visible_pairs(8192, 0) * flops.pair_flops(w) / 8192 == pytest.approx(33.6e6, rel=2e-3)
    assert flops.head_flops(w) == 2 * 2048 * 8193
    pairs = flops.visible_pairs(8192, 0)
    assert flops.pairs(w, 8192) == pairs  # the one attention layer; the convolutions attend to nothing
    # a token's forward with its scores and the head: ~406 MFLOP, of which the convolutions a third
    total = sum(per_token.values()) + pairs * 8192 / 8192 + flops.head_flops(w)
    assert total == pytest.approx(406.2e6, rel=2e-3) and per_token["conv"] / total == pytest.approx(0.33, abs=0.01)
    forward = 8192 * sum(per_token.values()) + pairs * 8192 + 256 * 2 * 2048 * 8193
    parts = flops.parts(conf)
    assert parts["update"] == pytest.approx(8 * 3 * forward)
    assert flops.grad_steps_per_iteration(conf) == 8
    assert flops.flops_per_grad_step(conf) == pytest.approx(sum(parts.values()) / 8)
    # the routed kernel: counted from assignments; a decode call is charged one expert's weights, not eight
    one = flops.moe_kernel_work(conf, 1000.0, 0.0)["update"]
    assert one[0] == 4 * 1000 * 3 * 2 * 2048 * 1536 and flops.moe_kernel_work(conf, 2000.0, 0.0)["update"][0] == 2 * one[0]
    expert = 2 * 3 * 2048 * 1536
    rollout = flops.moe_kernel_work(conf, 0.0, 500.0)["rollout"]
    assert rollout == (500 * 3 * 2 * 2048 * 1536, 500 * 4 * (2 * 2048 + 3 * 1536) + 4 * 8 * 8 * expert + 4 * 256 * expert)
    attention = flops.attention_kernel_work(conf)
    # the update's attention: one forward (the remat keeps the kernel's residuals) and the backward's five products,
    # on the one attention layer
    assert attention["update"][0] == pytest.approx(8 * pairs * 8192 * 3.5)
    assert attention["prefill"] == (8 * flops.visible_pairs(7936, 0) * 8192, 8 * 1 * 4 * 7936 * (2 * 2048 + 2 * 512))
    assert flops.roofline_seconds({"a": (197e12, 0.0), "b": (0.0, 819e9)}, peaks) == pytest.approx(2.0)


def test_flops_against_a_hand_count():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))["devices"]["TPU v5 lite"]
    check_flops(load_module("flops", "ppo_lm_lfm2"), config(), peaks)


# -- the readers this cell reports, on runs made by hand ---------------------------------------------------------
T_OPEN, T_CLOSE = 100.0, 124.0
COUNTER_CASES = {
    # name: (reader, the `iter` spans' counters, expected)
    # four convolution states of 3 gated inputs of 2 048 and one full buffer of 8 192 slots, keys and values of 8
    # heads x 64, 8 sequences, float32
    "cache": ("rollout_cache_mib", [{"rollout_cache_bytes": 8 * (4 * 3 * 2048 + 8192 * 2 * 8 * 64) * 4}], 256.75),
    "bias_moved": ("moe_bias_moved_pct", [{"moe_bias_moved": 30, "moe_bias_movable": 1000}, {"moe_bias_moved": 10, "moe_bias_movable": 1000}], 2.0),
}


def check_counter_reader(case, monkeypatch):
    from sheeprl_tpu.utils import profiler

    reader, blocks, expected = COUNTER_CASES[case]
    spans = [(T_OPEN + 6.0 * i, T_OPEN + 6.0 * i + 5.9, counters) for i, counters in enumerate(blocks)]
    before = (T_OPEN - 7.0, T_OPEN - 1.0, {"moe_bias_moved": 999, "moe_bias_movable": 999, "rollout_cache_bytes": 2**40})
    monkeypatch.setattr(profiler, "snapshot", recorder_of(before, *spans).snapshot)
    run = {"window": {"t_open": T_OPEN, "t_close": T_CLOSE, "seconds": T_CLOSE - T_OPEN}}
    assert load_module("layers", reader).read(run) == pytest.approx(expected)


LM_PROGRAM = "ppo_anakin_lm_block/1"
# instruction: (seconds of self time in the traced block, outermost region, innermost scope, backward)
LM_OPS = {
    "fusion.1": (0.40, "rollout.prefill", "lm.conv_mix", False),  # the gates and taps in prefill: the phase's, and the mix's
    "fusion.2": (0.10, "rollout.decode", "lm.conv", False),  # a decoded token's projections
    "convolution.3": (0.90, "lm.conv", "lm.conv", False),  # the update's input projection
    "fusion.4": (0.12, "lm.conv", "lm.conv_mix", False),
    "fusion.5": (0.20, "lm.conv", "lm.conv_mix", True),
    "fusion.6": (0.08, "lm.conv", "lm.conv", True),
    "splash_mqa_fwd.7": (0.50, "lm.attn_global", "kernel.window_attention", False),
    "gmm.8": (0.20, "lm.moe", "kernel.moe_grouped_ffn", True),
    "copy.9": (0.30, None, None, False),
}
REGION_EXPECTED = {"conv_ms": 1e3 * (0.40 + 0.10 + 0.90 + 0.12 + 0.20 + 0.08) / 8,
                   "conv_mix_ms": 1e3 * (0.40 + 0.12 + 0.20) / 8}


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
    monkeypatch.setattr(profiler, "scope_table", lambda program: {k: v for k, v in table.items() if not v["scope"].startswith("lm.conv")})
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
    check_recorded_run(load_module("correct", "ppo_lm_lfm2"), config()["correct_limits"], ENTRIES, run)


def test_the_records_cover_the_seeds_the_limits_were_set_from():
    assert len({r["seed"] for r in RUNS}) >= 7 and sum(bool(r["controls"]) for r in RUNS) >= 2
    assert all(len(r["controls"]) == len(CONTROLS) for r in RUNS if r["controls"])
