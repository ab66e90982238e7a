#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--devices N]        # N = 1 (default) or 4

Drives the main path once through the entry points a user calls, each as its
own process because a chip belongs to one process at a time (this parent never
imports JAX):

1. ``kernels``   — this file again, in-process: names the device, then runs
   every registered kernel's Pallas variant compiled by Mosaic (never the
   interpreter; the entries of ``COMPILED_BY_XLA`` as XLA compiles them) at
   the call-site shapes of the two runs below and at the
   shapes of ``sheeprl_tpu/ops/kernels/audit.py``, against its lax reference
   (integer outputs exactly); kernels the registry routes to lax on TPU by
   name are checked to be routed. With ``--devices N > 1`` also checks that
   ``Fabric``'s placement puts parameters on all N chips and splits a batch
   and an env axis across them.
2. ``python -m sheeprl_tpu run exp=dreamer_v3_100k_atari_dummy`` — DreamerV3-S
   at the widths of ``configs/algo/dreamer_v3_S.yaml``, batch 16 x sequence
   64, ``buffer.size=100000``, at least 256 gradient steps past
   ``learning_starts``, a last checkpoint.
3. ``python -m sheeprl_tpu eval checkpoint_path=<that checkpoint>``.
4. ``python -m sheeprl_tpu run exp=ppo_anakin`` on the pure-JAX CartPole, twice:
   the second process must read from the persistent compile cache what the
   first one wrote.
5. ``python -m sheeprl_tpu run exp=ppo_anakin_lm`` at toy widths: the same
   trainer with a decoder language model as the policy on the token MDP (both
   of its kernels through their TPU tier), four iterations.

It fails unless JAX's platform is ``tpu`` with N devices, every child exits 0,
the runs log finite ``Loss/*`` after training began, no update was skipped by
the divergence sentinel, no supervised worker was restarted or degraded, no
kernel resolved to interpret mode, nothing forked the JAX process, the
checkpoint is in its manifest and eval printed a test reward. Child logs and
``result.json`` (compile seconds, cache hits, kernel errors, losses) go to
``chiprun_out/chip_smoke/``, run directories to ``logs/chip_smoke/``. The last
line of standard output is one JSON object, ``{"ok": true, "device": {"platform":
"tpu", "kind": ..., "count": ...}}``; on failure there is no such line and the
exit code is not 0.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import struct
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, "chiprun_out", "chip_smoke")  # small: what a chip run brings back
RUNS = os.path.join(HERE, "logs", "chip_smoke")  # run directories: a Dreamer-S checkpoint is ~250 MB

DREAMER_EXP = "dreamer_v3_100k_atari_dummy"
DREAMER_LEARNING_STARTS = 1024  # the exp's own value, restated so the check below can use it
DREAMER_TOTAL_STEPS = DREAMER_LEARNING_STARTS + 384  # replay_ratio 1: one gradient step per policy step
DREAMER_MIN_GRAD_STEPS = 256
PPO_TOTAL_STEPS = 4096  # 8 iterations of 4 envs x 128 rollout steps
# the decoder policy at toy widths (heads of 128, 128-token prompts and 256-token sequences: what both kernels' TPU
# tiers take)
LM_OVERRIDES = (
    "exp=ppo_anakin_lm", "algo.lm.hidden_size=256", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2",
    "algo.lm.head_dim=128", "algo.lm.moe_ffn_hidden_size=128", "algo.lm.moe_num_primary_experts=8",
    "algo.lm.moe_num_active_primary_experts=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2",
    "algo.lm.num_hidden_layers=4", "algo.lm.sliding_window_size=128", "algo.lm.vocab_size=1024",
    "algo.lm.vocab_held=512", "env.prompt_len=128", "algo.rollout_steps=128",
)
LM_ITERATIONS = 4
# kernels that round their operands to bfloat16 by design: their float32 outputs are held to the bfloat16 tolerance
BF16_OPERAND_KERNELS = ("moe_grouped_ffn", "window_attention")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------- #
# parent side: children, logs, event files
# --------------------------------------------------------------------------- #


DEADLINE_S = 1150.0  # the contract's 1200 s, less what it takes to report
_START = time.monotonic()


def run_child(name: str, argv: list, timeout: float) -> tuple:
    """Run one child to its end in its own process group (a time-out kills the
    whole group, env workers included); returns ``(output, seconds)``."""
    timeout = max(1.0, min(timeout, DEADLINE_S - (time.monotonic() - _START)))
    log_path = os.path.join(WORK, f"{name}.log")
    print(f"[chip_smoke] {name}: {' '.join(argv)}", flush=True)
    tic = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=HERE, stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:  # the child's group: forkserver env workers die with it
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    seconds = time.monotonic() - tic
    with open(log_path, errors="replace") as f:
        out = f.read()
    if rc != 0:
        sys.stdout.write(out[-6000:] + "\n")
        raise SmokeFailure(f"{name}: " + ("timed out after %.0f s" % timeout if rc is None else f"exit code {rc}"))
    print(f"[chip_smoke] {name}: ok in {seconds:.1f} s", flush=True)
    return out, seconds


def read_scalars(event_file: str) -> dict:
    """``{tag: [(step, value), ...]}`` from a tensorboardX event file (TFRecord
    framing: u64 length, u32 crc, payload, u32 crc)."""
    from tensorboardX.proto import event_pb2

    scalars: dict = {}
    with open(event_file, "rb") as f:
        while True:
            header = f.read(12)
            if len(header) < 12:
                break
            (length,) = struct.unpack("<Q", header[:8])
            payload = f.read(length)
            f.read(4)
            event = event_pb2.Event.FromString(payload)
            for value in event.summary.value:
                if value.HasField("simple_value"):
                    scalars.setdefault(value.tag, []).append((event.step, value.simple_value))
    return scalars


def run_dir_of(out: str) -> str:
    m = re.search(r"^Log dir: (.+)$", out, re.M)
    check(m is not None, "the run printed no 'Log dir:' line")
    return m.group(1).strip()


def check_common(name: str, out: str, devices: int) -> dict:
    """What every CLI child must show: the launch banner with a TPU, the
    device count asked for and no interpret-mode kernel; no fork of the JAX
    process; no supervised worker restarted or degraded."""
    m = re.search(r"^fabric: platform=(\S+) device_kind='([^']*)' devices=(\d+) mesh=\(([^)]*)\) "
                  r"kernels=\[([^\]]*)\] hybrid_player=(\S+)$", out, re.M)
    check(m is not None, f"{name}: no 'fabric:' launch line")
    platform, kind, n, mesh, kernels, hybrid = m.groups()
    check(platform == "tpu", f"{name}: ran on platform '{platform}', not tpu")
    check(int(n) == devices, f"{name}: ran on {n} devices, {devices} asked")
    check("interpret" not in kernels, f"{name}: a kernel resolved to interpret mode: {kernels}")
    check("os.fork() was called" not in out, f"{name}: something forked the process that holds JAX")
    for needle in ("restarting in", "DEGRADED"):
        check(needle not in out, f"{name}: a supervised worker was restarted or degraded ('{needle}' in its output)")
    m = re.search(r"^compile: programs=(\d+) seconds=([\d.]+) cache_hits=(\d+) cache_writes=(\d+) "
                  r"trace_seconds=([\d.]+) cache_load_seconds=([\d.]+)\S* cache_dir=(.*)$", out, re.M)
    check(m is not None, f"{name}: no 'compile:' exit line")
    return {
        "kernels": kernels, "hybrid_player": hybrid, "mesh": mesh, "device_kind": kind,
        "compile_programs": int(m.group(1)), "compile_seconds": float(m.group(2)),
        "cache_hits": int(m.group(3)), "cache_writes": int(m.group(4)), "trace_seconds": float(m.group(5)),
        "cache_load_seconds": float(m.group(6)), "cache_dir": m.group(7),
    }


def check_losses(name: str, run_dir: str, tags: tuple, after_step: int) -> dict:
    """Finite ``tags`` logged after ``after_step`` and no sentinel skip."""
    files = glob.glob(os.path.join(run_dir, "events.out.tfevents.*"))
    check(len(files) == 1, f"{name}: expected one event file in {run_dir}, found {len(files)}")
    scalars = read_scalars(files[0])
    last = {}
    for tag in tags:
        points = [(s, v) for s, v in scalars.get(tag, []) if s > after_step]
        check(bool(points), f"{name}: no '{tag}' logged after policy step {after_step}")
        bad = [(s, v) for s, v in points if not math.isfinite(v)]
        check(not bad, f"{name}: non-finite '{tag}': {bad[:3]}")
        last[tag] = points[-1][1]
    skipped = scalars.get("Fault/skipped_updates", [])
    check(not skipped, f"{name}: the divergence sentinel skipped updates: {skipped[-1:]}")
    return {"last": last, "scalars": scalars}


def check_manifest(name: str, run_dir: str) -> str:
    manifest = os.path.join(run_dir, "checkpoint", "manifest.json")
    check(os.path.isfile(manifest), f"{name}: no {manifest}")
    with open(manifest) as f:
        entries = json.load(f)["entries"]
    check(bool(entries), f"{name}: checkpoint manifest has no entry")
    ckpt = os.path.join(run_dir, "checkpoint", entries[-1]["file"])
    check(os.path.exists(ckpt), f"{name}: manifest names {ckpt}, which is not there")
    return ckpt


def cli(*args: str) -> list:
    return [sys.executable, "-m", "sheeprl_tpu", *args]


def dreamer_overrides(devices: int) -> list:
    return [
        f"exp={DREAMER_EXP}",
        f"fabric.devices={devices}",
        f"algo.total_steps={DREAMER_TOTAL_STEPS}",
        "checkpoint.every=1000000",
        "checkpoint.save_last=True",
        "buffer.checkpoint=False",  # the 100000-row host mirror is not what this run is about
        "metric.log_every=64",
        "env.capture_video=False",
        "algo.run_test=False",  # eval is the next child
        f"log_root={RUNS}",
    ]


def ppo_overrides(devices: int) -> list:
    return [
        "exp=ppo_anakin",
        f"fabric.devices={devices}",
        f"algo.total_steps={PPO_TOTAL_STEPS}",
        "metric.log_every=512",
        "checkpoint.every=1000000",
        "checkpoint.save_last=True",
        f"log_root={RUNS}",
    ]


def lm_overrides(devices: int) -> list:
    envs = 2 * devices
    return [
        *LM_OVERRIDES, f"env.num_envs={envs}", "algo.per_rank_batch_size=1", f"fabric.devices={devices}",
        f"algo.total_steps={LM_ITERATIONS * envs * 128}", "metric.log_level=1", f"metric.log_every={envs * 128}",
        "checkpoint.every=1000000", "checkpoint.save_last=True", f"log_root={RUNS}",
    ]


def smoke(devices: int) -> dict:
    check(os.path.isdir(os.path.join(HERE, "sheeprl_tpu")), f"no sheeprl_tpu package next to {__file__}")
    for directory in (WORK, RUNS):
        shutil.rmtree(directory, ignore_errors=True)
        os.makedirs(directory)
    result: dict = {"devices_asked": devices}

    # 1. the device, the kernels, the placement (exits non-zero off-TPU)
    out, _ = run_child("kernels", [sys.executable, os.path.abspath(__file__), "--phase", "kernels",
                                   "--devices", str(devices)], timeout=600)
    device = json.loads(re.search(r"^DEVICE (.+)$", out, re.M).group(1))
    check(device["platform"] == "tpu" and device["count"] >= devices, f"kernels: device is {device}")
    result["device"] = device
    result["kernels"] = json.loads(re.search(r"^KERNELS (.+)$", out, re.M).group(1))
    print(f"[chip_smoke] device {device}; kernels {json.dumps(result['kernels'])}", flush=True)

    # 2. DreamerV3-S train
    out, secs = run_child("dreamer_train", cli("run", *dreamer_overrides(devices)), timeout=900)
    info = check_common("dreamer_train", out, devices)
    run_dir = run_dir_of(out)
    losses = check_losses(
        "dreamer_train", run_dir,
        ("Loss/world_model_loss", "Loss/policy_loss", "Loss/value_loss"), DREAMER_LEARNING_STARTS,
    )
    step, ratio = losses["scalars"]["Params/replay_ratio"][-1]
    grad_steps = round(ratio * step)
    check(grad_steps >= DREAMER_MIN_GRAD_STEPS,
          f"dreamer_train: {grad_steps} gradient steps by policy step {step}, {DREAMER_MIN_GRAD_STEPS} required")
    ckpt = check_manifest("dreamer_train", run_dir)
    result["dreamer_train"] = {**info, "seconds": round(secs, 1), "gradient_steps": grad_steps,
                               "losses": losses["last"], "checkpoint": os.path.relpath(ckpt, HERE)}
    print(f"[chip_smoke] dreamer_train {json.dumps(result['dreamer_train'])}", flush=True)

    # 3. eval of that checkpoint
    out, secs = run_child("dreamer_eval", cli("eval", f"checkpoint_path={ckpt}", "env.capture_video=False"),
                          timeout=600)
    info = check_common("dreamer_eval", out, 1)  # eval is a one-device verb
    m = re.search(r"^Test - Reward: (\S+)$", out, re.M)
    check(m is not None and math.isfinite(float(m.group(1))), "dreamer_eval: no finite 'Test - Reward:' line")
    result["dreamer_eval"] = {**info, "seconds": round(secs, 1), "test_reward": float(m.group(1))}
    print(f"[chip_smoke] dreamer_eval {json.dumps(result['dreamer_eval'])}", flush=True)

    # 4. PPO-Anakin, twice: the second process reads the first one's cache
    for name in ("ppo_anakin", "ppo_anakin_again"):
        out, secs = run_child(name, cli("run", *ppo_overrides(devices)), timeout=600)
        info = check_common(name, out, devices)
        run_dir = run_dir_of(out)
        losses = check_losses(name, run_dir, ("Loss/policy_loss", "Loss/value_loss", "Loss/entropy_loss"), 0)
        check_manifest(name, run_dir)
        m = re.search(r"^Test - Reward: (\S+)$", out, re.M)
        check(m is not None and math.isfinite(float(m.group(1))), f"{name}: no finite 'Test - Reward:' line")
        result[name] = {**info, "seconds": round(secs, 1), "losses": losses["last"],
                        "test_reward": float(m.group(1))}
        print(f"[chip_smoke] {name} {json.dumps(result[name])}", flush=True)
    check(result["ppo_anakin"]["cache_dir"] == result["ppo_anakin_again"]["cache_dir"],
          "the two PPO-Anakin processes used different compile cache directories")
    check(result["ppo_anakin_again"]["cache_hits"] > 0,
          "the second PPO-Anakin process read nothing from the persistent compile cache")

    # 5. the same trainer with a language-model policy on the token MDP, toy widths
    out, secs = run_child("ppo_anakin_lm", cli("run", *lm_overrides(devices)), timeout=600)
    info = check_common("ppo_anakin_lm", out, devices)
    for kernel in BF16_OPERAND_KERNELS:
        check(f"{kernel}=pallas" in info["kernels"], f"ppo_anakin_lm: {kernel} did not take its TPU tier: {info['kernels']}")
    run_dir = run_dir_of(out)
    losses = check_losses("ppo_anakin_lm", run_dir, ("Loss/policy_loss", "Loss/value_loss", "Loss/entropy_loss"), 0)
    check_manifest("ppo_anakin_lm", run_dir)
    result["ppo_anakin_lm"] = {**info, "seconds": round(secs, 1), "losses": losses["last"]}
    print(f"[chip_smoke] ppo_anakin_lm {json.dumps(result['ppo_anakin_lm'])}", flush=True)
    return result


# --------------------------------------------------------------------------- #
# child side, in-process: device, kernels, placement
# --------------------------------------------------------------------------- #


def phase_kernels(devices: int) -> None:
    import jax

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": len(jax.devices())}
    print("DEVICE " + json.dumps(device), flush=True)
    check(first.platform == "tpu", f"JAX found no TPU: platform '{first.platform}'")
    check(len(jax.devices()) >= devices, f"{devices} devices asked, {len(jax.devices())} visible")

    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()
    report = check_kernels(devices)
    print("KERNELS " + json.dumps(report), flush=True)
    if devices > 1:
        check_placement(devices)


def check_kernels(devices: int) -> dict:
    """Each registered kernel on the chip against its lax reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.ops import kernels as K

    rng = np.random.default_rng(0)

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale, dtype=dtype)

    def log_softmax(x):
        return x - jax.scipy.special.logsumexp(x.astype(jnp.float32), axis=-1, keepdims=True).astype(x.dtype)

    B, T, H = 16 // devices, 64, 15  # per-device Dreamer-S batch, sequence, imagination horizon
    cases: dict = {name: [] for name in K.names()}
    # gru_gates: dynamic scan, imagination scan, the on-device eval player; then every configured width
    for rows, hidden in ((B, 512), (B * T, 512), (1, 512), (256, 512), (1024, 1024), (1024, 2048), (1024, 4096)):
        cases["gru_gates"].append(((normal((rows, 3 * hidden)), normal((rows, hidden))), f"{rows}x{hidden} f32"))
    cases["gru_gates"].append(
        ((normal((1024, 3 * 4096), jnp.bfloat16), normal((1024, 4096), jnp.bfloat16)), "1024x4096 bf16")
    )
    # two-hot pair: reward head over (T, B), critic over (H + 1, T * B); the audit's (16, 64)
    for lead in ((T, B), (H + 1, T * B), (16, 64)):
        logits = log_softmax(normal(lead + (255,)))
        cases["two_hot_symlog_loss"].append(((logits, normal(lead + (1,), scale=5.0)), f"{lead} x 255"))
        cases["two_hot_symexp_decode"].append(((logits,), f"{lead} x 255"))
    # gae: the PPO-Anakin rollout (128 steps x envs per device); the audit's (128, 16)
    for shape in ((128, max(1, 4 // devices), 1), (128, 16)):
        dones = jnp.asarray(rng.uniform(size=shape) < 0.1, jnp.float32)
        args = (normal(shape), normal(shape), dones, normal(shape[1:]), 0.99, 0.95)
        cases["gae"].append((args, f"{shape}"))
    # ragged_ring_scatter: the flagship ring's keys at capacity 100000 x 1 env and the first flush
    # bucket (19 rows); a 4-env pixel ring; the audit's (64, 8, 32); each as the ring stores it,
    # (capacity, envs) + data.ring.ring_cell(feat)
    def pixels(shape, salt):
        # hashed iota, made on the chip in one fused pass: the flagship ring is 1.2 GB and a random
        # draw of that size needs several times as much in 32-bit temporaries
        n = math.prod(shape)
        make = jax.jit(lambda: ((jnp.arange(n, dtype=jnp.uint32) + salt) * jnp.uint32(2654435761) >> 24)
                       .astype(jnp.uint8).reshape(shape))
        return make()

    def ring_case(capacity, envs, feat, dtype, slots):
        from sheeprl_tpu.data.ring import ring_append_rows, ring_cell

        cell = ring_cell(feat)
        if dtype == jnp.uint8:
            storage, staged = pixels((capacity, envs) + cell, 1), pixels((slots, envs) + cell, 2)
        else:
            storage, staged = normal((capacity, envs) + cell), normal((slots, envs) + cell)
        pos = jnp.asarray(rng.integers(0, capacity, size=(envs,)), jnp.int32)
        pos = pos.at[0].set(capacity - 3)  # a wrapping head
        mask = jnp.asarray(rng.uniform(size=(slots, envs)) < 0.8, jnp.int32)  # ragged: some slots dropped
        row, _, _ = ring_append_rows(pos, jnp.full((envs,), capacity // 2, jnp.int32), mask, capacity)
        return ((storage, staged, row, pos), f"{(capacity, envs) + cell} {jnp.dtype(dtype).name} <- {slots} rows")

    for capacity, envs, feat, dtype, slots in (
        (100000, 1, (64, 64, 3), jnp.uint8, 19), (100000, 1, (18,), jnp.float32, 19),
        (100000, 1, (1,), jnp.float32, 19), (1024, 4, (64, 64, 3), jnp.uint8, 22), (64, 8, (32,), jnp.float32, 4),
    ):
        cases["ragged_ring_scatter"].append(ring_case(capacity, envs, feat, dtype, slots))
    # sumtree_sample: the audit's 4096-leaf tree and 256 draws
    from sheeprl_tpu.replay import sumtree as st

    tree = st.update(st.init(4096), jnp.arange(3000), jnp.asarray(rng.uniform(0.1, 2.0, size=(3000,)), jnp.float32))
    cases["sumtree_sample"].append(
        ((tree, jnp.asarray(rng.uniform(size=(256,)), jnp.float32), jnp.int32(3000), jnp.float32(0.4)), "8192 x 256")
    )

    # the decoder policy's kernels at the shapes of the language-model cell: one 8192-token sequence's
    # 49152 sorted assignments over 16 held experts at uneven loads (one empty, a quarter held), hidden 2560,
    # expert width 768; 28 query heads over 4 key-value heads of 128, whole and over a 4096-token window
    loads = rng.multinomial(12288, rng.dirichlet(np.full(15, 2.0))).tolist() + [0]
    cases["moe_grouped_ffn"].append(
        ((normal((49152, 2560)), normal((16, 2560, 768), scale=0.02), normal((16, 2560, 768), scale=0.02),
          normal((16, 768, 2560), scale=0.02), jnp.asarray(loads, jnp.int32)), "49152 x 2560 over 16 x 768"))
    for window in (0, 4096):
        cases["window_attention"].append(
            ((normal((1, 8192, 28, 128), scale=0.3), normal((1, 8192, 4, 128), scale=0.3), normal((1, 8192, 4, 128)), window),
             f"8192 x 28/4 x 128 window {window}"))

    report = {}
    for name in K.names():
        tier = K.tier(name)
        check(tier != "pallas-interpret", f"kernel {name} resolves to interpret mode on this machine")
        if name in K.AUTO_LAX_ON_TPU:
            check(tier == "lax" and K.dispatch(name).__wrapped__ is K.get(name).reference,  # under its scope
                  f"kernel {name} is listed in AUTO_LAX_ON_TPU but resolves to {tier}")
            for args, label in cases[name]:  # what the call site gets still has to run here
                out = jax.jit(K.dispatch(name))(*args)
                check(all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in jax.tree.leaves(out)),
                      f"kernel {name} [{label}]: the lax reference gave a non-finite output")
            report[name] = {"tier": "lax", "routed_by_name": K.AUTO_LAX_ON_TPU[name]}
            continue
        by_xla = name in K.COMPILED_BY_XLA  # the kernel tier's entry is plain jax.numpy: no Mosaic call to find
        check(tier == ("xla" if by_xla else "pallas"), f"kernel {name} resolves to {tier} on a TPU, not to its kernel-tier entry")
        kernel = K.get(name)
        worst: dict = {}  # max |got - want| / (1 + |want|) per output dtype
        for args, label in cases[name]:
            arrays = tuple(a for a in args if isinstance(a, jax.Array))
            statics = tuple(a for a in args if not isinstance(a, jax.Array))
            pallas = jax.jit(lambda *xs, _f=kernel.pallas, _s=statics: _f(*xs, *_s))
            reference = jax.jit(lambda *xs, _f=kernel.reference, _s=statics: _f(*xs, *_s))
            check(("tpu_custom_call" in pallas.lower(*arrays).as_text()) != by_xla,
                  f"kernel {name} [{label}]: the TPU lowering holds {'a' if by_xla else 'no'} Mosaic custom call")
            for g, w in zip(jax.tree.leaves(pallas(*arrays)), jax.tree.leaves(reference(*arrays))):
                check(g.shape == w.shape and g.dtype == w.dtype, f"kernel {name} [{label}]: {g.shape} {g.dtype} vs "
                      f"reference {w.shape} {w.dtype}")
                if jnp.issubdtype(g.dtype, jnp.integer) or name == "ragged_ring_scatter":  # compared on the chip
                    check(bool(jnp.array_equal(g, w)), f"kernel {name} [{label}]: output differs from the reference")
                    worst["exact"] = 0.0
                    continue
                g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
                check(bool(jnp.isfinite(g32).all()), f"kernel {name} [{label}]: non-finite output")
                err = float(jnp.max(jnp.abs(g32 - w32) / (1.0 + jnp.abs(w32))))
                tol = 2e-2 if g.dtype.itemsize == 2 or name in BF16_OPERAND_KERNELS else 1e-4
                check(err <= tol, f"kernel {name} [{label}]: error {err:.3g} against the reference exceeds {tol}")
                worst[g.dtype.name] = max(worst.get(g.dtype.name, 0.0), err)
                print(f"kernel {name} [{label}]: scaled error {err:.3g}", flush=True)
            print(f"kernel {name} [{label}]: ok", flush=True)
        report[name] = {"tier": tier, "cases": len(cases[name]), "max_scaled_error": worst}
    return report


def check_placement(devices: int) -> None:
    """``Fabric``'s placement on N chips: parameters on all of them, a batch
    and an env axis split across them, and a collective that crosses them."""
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel.fabric import Fabric

    fabric = Fabric(devices=devices, accelerator="tpu")
    chips = set(fabric.devices)
    check(len(chips) == devices, f"the mesh holds {len(chips)} distinct devices, {devices} asked")
    cfg = compose([f"exp={DREAMER_EXP}", f"fabric.devices={devices}"])
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    params = build_agent(fabric, (18,), False, cfg, obs_space)[3]
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        where = {s.device for s in leaf.addressable_shards}
        check(where == chips and all(s.data.shape == leaf.shape for s in leaf.addressable_shards),
              f"parameter {jax.tree_util.keystr(path)} is not whole on every chip: on {sorted(d.id for d in where)}")
    batch = fabric.shard_data({"x": np.zeros((16, 8), np.float32)})["x"]  # the batch axis of the train steps
    envs = jax.device_put(jnp.zeros((4 * devices, 4)), fabric.data_sharding)  # the PPO-Anakin env axis
    for name, arr in (("batch", batch), ("env axis", envs)):
        shards = arr.addressable_shards
        check({s.device for s in shards} == chips and all(s.data.shape[0] == arr.shape[0] // devices for s in shards),
              f"the {name} is not split over the {devices} chips: {[(s.device.id, s.data.shape) for s in shards]}")
    total = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=fabric.mesh, in_specs=P("dp"), out_specs=P()))(
        jax.device_put(jnp.arange(devices, dtype=jnp.float32), fabric.data_sharding)
    )
    check(float(total[0]) == devices * (devices - 1) / 2, f"psum over dp gave {total}")
    print(f"placement on {devices} chips: ok", flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--devices", type=int, default=1, help="chips to use (1 or 4)")
    parser.add_argument("--phase", choices=("kernels",), help=argparse.SUPPRESS)
    args = parser.parse_args()
    try:
        if args.phase == "kernels":
            phase_kernels(args.devices)
            return 0
        tic = time.monotonic()
        result = smoke(args.devices)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", flush=True)
        return 1
    result["seconds"] = round(time.monotonic() - tic, 1)
    with open(os.path.join(WORK, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[chip_smoke] passed on {args.devices} device(s) in {result['seconds']} s; details in "
          f"{os.path.relpath(WORK, HERE)}/result.json", flush=True)
    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
