"""End-to-end device-resident replay runs through the real CLI: SAC dry runs
(uniform + PER, 1/2 devices, env-sharded), checkpoint → resume round trips,
(the coupled Dreamer mains keep their replay on the device through the burst
topology: ``tests/test_algos/test_wm_loop.py``)."""

import glob
import os

import pytest

from sheeprl_tpu.cli import run


def _sac_args(tmp_path, devices=1, extra=()):
    args = [
        "exp=sac",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "buffer.memmap=False",
        "buffer.size=64",
        "buffer.device_resident=true",
        f"fabric.devices={devices}",
        "algo.per_rank_batch_size=8",
        "algo.hidden_size=16",
        "algo.mlp_keys.encoder=[state]",
        "algo.learning_starts=4",
        "algo.total_steps=16",
        "algo.run_test=False",
        "metric.log_level=0",
        "checkpoint.save_last=False",
        "checkpoint.every=0",
        f"log_root={tmp_path}/logs",
    ]
    args.extend(extra)
    return args


@pytest.mark.parametrize("devices", [1, 2])
def test_sac_resident_run(tmp_path, devices):
    """devices=2 with num_envs=2 exercises the env-sharded storage path."""
    run(_sac_args(tmp_path, devices=devices))


def test_sac_resident_prioritized(tmp_path):
    run(_sac_args(tmp_path, extra=["buffer.priority.enabled=true"]))


def test_sac_resident_checkpoint_resume(tmp_path):
    """Resident ring state (storage + heads + key + PER tree) survives a
    checkpoint → resume round trip through the real checkpoint machinery."""
    run(
        _sac_args(
            tmp_path,
            extra=[
                "buffer.priority.enabled=true",
                "checkpoint.every=8",
                "checkpoint.save_last=True",
                "algo.total_steps=16",
            ],
        )
    )
    ckpts = sorted(
        glob.glob(f"{tmp_path}/logs/**/*.ckpt", recursive=True), key=os.path.getmtime
    )
    assert ckpts, "resident run must produce a checkpoint"
    run(
        _sac_args(
            tmp_path,
            extra=[
                "buffer.priority.enabled=true",
                "algo.total_steps=24",
                f"checkpoint.resume_from={ckpts[-1]}",
            ],
        )
    )


def test_sac_resident_resume_onto_host_tier(tmp_path):
    """Crossover: a resident checkpoint resumed with the knob OFF lands on
    the host-sampling path and keeps the replay data."""
    run(
        _sac_args(
            tmp_path,
            extra=["checkpoint.every=8", "checkpoint.save_last=True", "algo.total_steps=16"],
        )
    )
    ckpts = sorted(
        glob.glob(f"{tmp_path}/logs/**/*.ckpt", recursive=True), key=os.path.getmtime
    )
    assert ckpts
    args = _sac_args(
        tmp_path, extra=["algo.total_steps=24", f"checkpoint.resume_from={ckpts[-1]}"]
    )
    args[args.index("buffer.device_resident=true")] = "buffer.device_resident=false"
    run(args)


def test_sac_spillover_falls_back_to_host(tmp_path):
    """buffer.device_resident=auto with a tiny HBM budget must run the host
    path (graceful spillover), not fail."""
    args = _sac_args(
        tmp_path,
        extra=["buffer.hbm_budget_gb=1e-9", "algo.total_steps=8"],
    )
    args[args.index("buffer.device_resident=true")] = "buffer.device_resident=auto"
    run(args)
