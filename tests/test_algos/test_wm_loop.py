"""The contract of the world-model host loop (``algos/world_model_loop.py``),
in the tier-1 lane: each main that stands on the loop runs on the CPU to its
last iteration and checkpoint on the host-sampled topology, the loop makes the
same sequence of seam calls whichever of the two trainers stands behind it,
the topology that was deleted is refused by name on a fresh run, and a run it
checkpointed still resumes through the CLI.

The cases are called ``dv3``, ``dv3_explore`` and ``dv3_finetune`` and the file
``test_wm_loop.py`` on purpose: ``tests/conftest.py`` marks slow every test
whose id contains ``dreamer`` or ``p2e`` (a rule by substring), which keeps
every other end-to-end run of these mains out of the lane the driver runs, and
that lane has some 1 000 s of its limit to spare.
"""

import glob
import os

import numpy as np
import pytest
import yaml

import sheeprl_tpu.algos.world_model_loop as loop_mod
import sheeprl_tpu.utils.profiler as profiler_mod
from sheeprl_tpu.cli import run

XS = [
    "env=dummy", "env.num_envs=2", "env.sync_env=True", "env.capture_video=False", "buffer.memmap=False",
    "fabric.devices=1", "metric.log_level=0", "algo.run_test=False", "algo.per_rank_batch_size=2", "algo.horizon=4",
    "algo.dense_units=8", "algo.mlp_layers=1", "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16", "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8", "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4", "algo.world_model.reward_model.bins=17", "algo.critic.bins=17",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]", "env.screen_size=64",
]
DRY = XS + ["dry_run=True", "checkpoint.save_last=True", "algo.per_rank_sequence_length=1", "algo.hybrid_player.enabled=false"]
# a few iterations past the prefill, with episode ends (a 5-step time limit) and one env restart
N_ENVS, TOTAL_STEPS, RESTART_AT = 2, 28, 9
FEW = XS + [
    "exp=dreamer_v3", "algo=dreamer_v3_XS", "dry_run=False", f"algo.total_steps={TOTAL_STEPS}", "algo.learning_starts=8",
    "algo.per_rank_sequence_length=2", "buffer.size=64", "env.max_episode_steps=5", "checkpoint.save_last=True",
    "checkpoint.every=1000", "algo.hybrid_player.train_every=2", "algo.hybrid_player.snapshot_every=2",
]
SEAM = ("stage_step", "stage_reset", "patch_last", "train")


def _checkpoints(root):
    return sorted(glob.glob(f"{root}/**/ckpt_*.ckpt", recursive=True))


class _RestartingEnvs:
    """The vector env, reporting at its ``at``-th step that env 0 was restarted
    after an exception (what ``RestartOnException`` reports)."""

    def __init__(self, envs, at):
        self._envs, self._at, self._steps = envs, at, 0

    def __getattr__(self, name):
        return getattr(self._envs, name)

    def step(self, actions):
        out = self._envs.step(actions)
        self._steps += 1
        if self._steps == self._at:
            out[4]["restart_on_exception"] = np.array([True] + [False] * (N_ENVS - 1))
        return out


def _recorded_run(tmp_path, hybrid, more=()):
    """``FEW`` (and ``more``) on one topology: the seam calls in order, each with
    what the loop handed it that does not depend on who acted."""
    calls, trainers = [], []
    mp = pytest.MonkeyPatch()

    def record(cls, name):
        orig = getattr(cls, name)

        def wrapper(self, *args):
            if name == "stage_step":
                seen = (args[0]["is_first"].ravel().tolist(), args[0]["terminated"].ravel().tolist())
            elif name == "stage_reset":
                seen = (list(args[1]), args[0]["truncated"].ravel().tolist())
            else:  # patch_last(i, updates), train(grants)
                seen = args
            calls.append((name, seen))
            if self not in trainers:
                trainers.append(self)
            return orig(self, *args)

        mp.setattr(cls, name, wrapper)

    for cls in (loop_mod.HostSampledTrainer, loop_mod.BurstTrainer):
        for name in SEAM:
            record(cls, name)
    orig_vectorize = loop_mod.vectorize_env
    mp.setattr(loop_mod, "vectorize_env", lambda *a, **kw: _RestartingEnvs(orig_vectorize(*a, **kw), RESTART_AT))
    # the recorder's compiled programs are the process's: leave them as found, for whichever file this worker
    # runs next (tests/test_utils/test_span_contract.py reads the registry as its own run left it)
    mp.setattr(profiler_mod.RECORDER, "_programs", dict(profiler_mod.RECORDER._programs))
    try:
        run(FEW + [f"algo.hybrid_player.enabled={hybrid}", f"log_root={tmp_path}/logs", *more])
    finally:
        mp.undo()
    (trainer,) = trainers
    return calls, trainer


@pytest.fixture(scope="module")
def host_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("dv3_host")
    calls, trainer = _recorded_run(root, "false")
    return {"root": root, "calls": calls, "trainer": trainer}


@pytest.fixture(scope="module")
def explore_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("dv3_explore")
    run(DRY + ["exp=p2e_dv3_exploration", "algo.ensembles.n=3", f"log_root={root}/logs"])
    return root


def test_dv3_runs_host_sampled_to_its_last_iteration_and_checkpoint(host_run):
    trainer, calls = host_run["trainer"], host_run["calls"]
    assert type(trainer) is loop_mod.HostSampledTrainer
    n_iters = TOTAL_STEPS // N_ENVS
    assert [n for n, _ in calls].count("stage_step") == [n for n, _ in calls].count("train") == n_iters
    assert trainer.gradient_steps > 0 and trainer.train_steps > 0
    (ckpt,) = _checkpoints(host_run["root"])
    assert ckpt.endswith(f"ckpt_{TOTAL_STEPS}_0.ckpt")


def test_the_divergence_sentinel_rolls_the_carry_back_to_a_checkpoint(host_run):
    import jax

    from sheeprl_tpu.utils.checkpoint import load_state

    trainer = host_run["trainer"]
    assert trainer.guard  # `dv3` hands over a guarded step, and the sentinel is on by default
    good = load_state(_checkpoints(host_run["root"])[0])
    trainer.params = jax.tree.map(lambda x: x + 1, trainer.params)
    trainer._rollback(good)
    assert set(trainer.params) == {"world_model", "actor", "critic", "target_critic"}
    for got, want in zip(jax.tree.leaves(trainer.params), jax.tree.leaves({k: good[k] for k in trainer.params})):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(trainer.rng), np.asarray(good["rng"]))


def test_both_topologies_make_the_same_seam_calls(host_run, tmp_path):
    burst_calls, burst = _recorded_run(tmp_path, "true")
    assert type(burst) is loop_mod.BurstTrainer
    assert burst_calls == host_run["calls"]
    names = [n for n, _ in burst_calls]
    assert names.count("stage_reset") >= 2 and names.count("patch_last") == 1
    # the restart closes the row just staged as truncated, and the next row opens an episode for that env only
    at = names.index("patch_last")
    assert burst_calls[at] == ("patch_last", (0, {"terminated": 0.0, "truncated": 1.0, "is_first": 0.0}))
    assert names[at - 1] == "stage_step"
    next_step = next(seen for n, seen in burst_calls[at + 1 :] if n == "stage_step")
    assert next_step[0] == [1.0] + [0.0] * (N_ENVS - 1)
    # every grant the Ratio made was handed over, none before learning starts
    grants = [seen[0] for n, seen in burst_calls if n == "train"]
    assert grants[:3] == [0, 0, 0] and sum(grants) == host_run["trainer"].gradient_steps
    assert burst.gradient_steps + burst.grant_backlog == sum(grants)
    assert _checkpoints(tmp_path)[-1].endswith(f"ckpt_{TOTAL_STEPS}_0.ckpt")


def test_dv3_explore_runs_host_sampled_to_its_checkpoint(explore_run):
    (ckpt,) = _checkpoints(explore_run)
    assert ckpt.endswith("ckpt_2_0.ckpt")


def test_dv3_finetune_runs_host_sampled_from_the_exploration_checkpoint(explore_run, tmp_path):
    (explored,) = _checkpoints(explore_run)
    run(DRY + ["exp=p2e_dv3_finetuning", f"checkpoint.exploration_ckpt_path={explored}", f"log_root={tmp_path}/logs"])
    (ckpt,) = _checkpoints(tmp_path)
    assert ckpt.endswith("ckpt_2_0.ckpt")


@pytest.mark.parametrize("kind", ["buffer", "list", "device_ring", "other"])
def test_a_checkpointed_replay_of_any_age_resumes_onto_the_host_buffer(kind):
    """``device_ring``: the snapshot the deleted coupled-resident topology wrote."""
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
    from sheeprl_tpu.replay import DeviceReplayState

    cap = 4
    fresh, saved = (
        EnvIndependentReplayBuffer(cap, n_envs=N_ENVS, obs_keys=("state",), buffer_cls=SequentialReplayBuffer)
        for _ in range(2)
    )
    storage = np.arange(cap * N_ENVS * 3, dtype=np.float32).reshape(cap, N_ENVS, 3)
    snap = DeviceReplayState(
        "sequence",
        {"storage/state": storage, "pos": np.array([3, 0]), "valid": np.array([3, cap]), "key": np.zeros(2, np.uint32)},
        {"capacity": cap, "n_envs": N_ENVS, "seq_len": 2},
    )
    if kind == "other":
        with pytest.raises(RuntimeError, match="Cannot restore the replay buffer"):
            loop_mod._restore_replay(fresh, object())
        return
    rb = loop_mod._restore_replay(fresh, {"buffer": saved, "list": [saved], "device_ring": snap}[kind])
    assert rb is (fresh if kind == "device_ring" else saved)
    if kind == "device_ring":
        assert [sub._pos for sub in rb.buffer] == [3, 0] and [sub.full for sub in rb.buffer] == [False, True]
        np.testing.assert_array_equal(np.asarray(rb.buffer[1].buffer["state"])[:, 0], storage[:, 1])
        assert np.asarray(rb.buffer[0].buffer["truncated"]).shape == (cap, 1, 1)  # the key a ring never stored


@pytest.mark.parametrize("hybrid", [False, True], ids=["host_sampled", "burst"])
def test_a_run_the_deleted_topology_checkpointed_resumes_through_the_cli(host_run, tmp_path, hybrid):
    """A run dir as the coupled-resident topology left it: ``buffer.device_resident: true`` in its ``config.yaml``
    (which wins over the command line on resume) and the device ring's own snapshot in the checkpoint's ``.rb``."""
    from sheeprl_tpu.replay import DeviceReplayState
    from sheeprl_tpu.utils.checkpoint import load_state, save_state

    (written,) = _checkpoints(host_run["root"])
    state = load_state(written)
    subs = state.pop("rb").buffer
    cap, pos = subs[0].buffer_size, [sub._pos for sub in subs]
    assert not any(sub.full for sub in subs) and min(pos) > 2
    arrays = {  # a ring stores every key but `truncated`, env-shaped on the host, and its per-env heads
        f"storage/{k}": np.concatenate([np.asarray(sub.buffer[k]) for sub in subs], axis=1)
        for k in subs[0].buffer if k != "truncated"
    }
    arrays.update(pos=np.array(pos), valid=np.array(pos), key=np.zeros(2, np.uint32))
    old_run = tmp_path / "old" / "version_0"
    ckpt = old_run / "checkpoint" / os.path.basename(written)
    os.makedirs(ckpt.parent)
    save_state(ckpt, {**state, "rb": DeviceReplayState("sequence", arrays, {"capacity": cap, "n_envs": N_ENVS, "seq_len": 2})})
    with open(os.path.join(os.path.dirname(os.path.dirname(written)), "config.yaml")) as f:
        old_cfg = yaml.safe_load(f)
    old_cfg["buffer"]["device_resident"] = True
    old_cfg["algo"]["hybrid_player"]["enabled"] = hybrid
    old_cfg["algo"]["total_steps"] = TOTAL_STEPS + 8
    with open(old_run / "config.yaml", "w") as f:
        yaml.safe_dump(old_cfg, f)

    with pytest.warns(UserWarning, match="buffer.device_resident=True, a topology"):
        calls, trainer = _recorded_run(tmp_path, hybrid, [f"checkpoint.resume_from={ckpt}", "algo.learning_starts=0"])
    assert type(trainer) is (loop_mod.BurstTrainer if hybrid else loop_mod.HostSampledTrainer)
    for e, sub in enumerate(trainer.rb.buffer):  # the ring's rows, and the resumed iterations after them
        assert sub._pos >= pos[e] + 4 and np.asarray(sub.buffer["truncated"]).shape == (cap, 1, 1)
        np.testing.assert_array_equal(np.asarray(sub.buffer["state"])[: pos[e], 0], arrays["storage/state"][: pos[e], e])
    # training goes on at the Ratio's rate from the first resumed iteration (the Ratio's first answer after a resume
    # is minus all it granted before: the loop hands over none of it, on either topology)
    grants = [seen[0] for n, seen in calls if n == "train"]
    assert grants[0] == 0 and min(grants[1:]) > 0 and trainer.gradient_steps + trainer.grant_backlog == sum(grants)
    (resumed,) = _checkpoints(tmp_path / "logs")
    assert resumed.endswith(f"ckpt_{TOTAL_STEPS + 8}_0.ckpt")
    with open(os.path.join(os.path.dirname(os.path.dirname(resumed)), "config.yaml")) as f:
        assert yaml.safe_load(f)["buffer"]["device_resident"] is False


@pytest.mark.parametrize("setting", ["True", "auto"])
def test_coupled_resident_replay_is_refused_by_name(tmp_path, setting):
    args = DRY + ["exp=dreamer_v3", "algo=dreamer_v3_XS", f"buffer.device_resident={setting}", f"log_root={tmp_path}/logs"]
    with pytest.raises(ValueError, match="algo.hybrid_player"):
        run(args)
    assert not _checkpoints(tmp_path)
