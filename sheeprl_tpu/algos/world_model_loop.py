"""The host loop of the world-model families (Dreamer, Plan2Explore): one loop
behind one replay/trainer seam.

:func:`run` is everything a coupled world-model main does that is not the
family's own: env construction and first observation, logger and aggregator,
buffer creation and restore, resume counters and ``Ratio``, the per-iteration
body (random prefill actions, acting, staging, ``envs.step``, the
``restart_on_exception`` patch, episode logging, ``final_obs``, reset rows),
the logging and checkpoint cadence, the tail flush, the test and the model
registration. A family's file builds its agent, optimizers and train step and
hands them over as a :class:`Family` of plain values and callables.

Whoever owns the replay and the gradient steps stands behind one seam, chosen
once before the loop (:func:`platform_trainer`):

- ``act(obs) -> actions`` / ``reset_player(idxs)``: who acts, on which
  parameters;
- ``poll()``, ``stage_step(step_data)``, ``stage_reset(reset_data, idxs)``,
  ``patch_last(i, updates)``, ``train(grants)``;
- the counters the ``iter`` span and the logger read: ``gradient_steps``,
  ``train_steps``, ``grant_backlog``, ``staged_rows``;
- ``checkpoint_state() -> (carry, rng, replay)`` and ``finish() -> carry``,
  ``carry`` being ``(params, opts, moments_state)``;
- built as ``trainer(fabric, cfg, family, setup)``, ``setup`` being the
  :class:`Setup` the loop has made by then.

:class:`HostSampledTrainer` samples the host buffer, uploads one packed batch
and runs the family's train step on the loop's thread, the device player
acting on the live parameters. :class:`BurstTrainer` holds the
``utils.burst.HybridPlayerHarness`` (device ring, trainer thread) beside the
host player that acts on its snapshot.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer, put_packed
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.utils import profiler as recorder
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, resolve_hybrid_player, save_configs

__all__ = ["BurstTrainer", "Family", "HostSampledTrainer", "Setup", "platform_trainer", "run"]


@dataclass
class Family:
    """What a family's ``build(observation_space, actions_dim, is_continuous)``
    hands the loop."""

    carry: Tuple[Any, Any, Any]  # (params, opts, moments_state), replicated
    # () -> the jitted G-step update on an uploaded batch; (ring=<spec>) -> the burst program over the device ring
    make_train_step: Callable[..., Callable]
    player: Any  # the device player
    prepare_obs: Callable[..., Dict[str, Any]]
    # (params, trained) -> the parameters the player acts on; `trained`: a gradient step was taken
    player_params: Callable[[Any, bool], Any]
    models: Callable[[Any], Dict[str, Any]]  # params -> the checkpoint's model entries
    test: Callable[[Any, str, Any], None]  # (params, log_dir, logger): the evaluation episode after training
    registered_models: Callable[[Any, Any], Dict[str, Any]]  # (params, moments_state) -> the registry's entries
    # names of the train step's metrics tuple (None: the step returns a dict)
    metric_names: Optional[Tuple[str, ...]] = None
    # `make_train_step(guard=True)` is the step under the divergence guard (its last metric the skipped
    # fraction), and the checkpoint's model entries are the params tree's own keys, for the rollback
    guarded: bool = False
    # the burst topology's own (None: the family trains host-sampled only)
    make_host_player: Optional[Callable[[Any], Any]] = None  # host device -> the host player
    player_subset: Optional[Callable[[Any], Any]] = None  # params -> the snapshot's leaves


@dataclass
class Setup:
    """What :func:`run` has made by the time it builds the trainer."""

    rb: EnvIndependentReplayBuffer  # the host buffer, one sequential buffer an env
    restored: bool  # `rb` holds a checkpoint's rows
    buffer_size: int  # rows an env
    rng: jax.Array
    aggregator: Optional[MetricAggregator]
    log_dir: str
    observation_space: gym.spaces.Dict
    actions_dim: Tuple[int, ...]


def _update_metrics(aggregator, names, metrics) -> None:
    if aggregator and not aggregator.disabled:
        pairs = metrics.items() if isinstance(metrics, dict) else zip(names, metrics)
        for name, value in pairs:
            if name in aggregator:
                aggregator.update(name, value)


def _patch_last_row(rb, env_idx: int, updates: Dict[str, float]) -> None:
    sub_rb = rb.buffer[env_idx]
    last_inserted_idx = (sub_rb._pos - 1) % sub_rb.buffer_size
    for k, v in updates.items():
        sub_rb[k][last_inserted_idx] = v


class HostSampledTrainer:
    """The host-sampled topology: ``rb.sample`` → one packed upload → the
    family's train step, with the divergence sentinel's rollback."""

    grant_backlog = 0
    staged_rows = 0

    def __init__(self, fabric, cfg, family: Family, setup: Setup):
        from sheeprl_tpu.fault import DivergenceSentinel

        self.fabric, self.cfg, self.family, self.rb = fabric, cfg, family, setup.rb
        self.rng = setup.rng
        self.aggregator = setup.aggregator
        self.ckpt_dir = os.path.join(setup.log_dir, "checkpoint")
        self.params, self.opts, self.moments_state = family.carry
        self.batch_size = int(cfg.algo.per_rank_batch_size)
        self.seq_len = int(cfg.algo.per_rank_sequence_length)
        self.n_envs = int(cfg.env.num_envs)
        self.cnn_keys = cfg.algo.cnn_keys.encoder
        self.gradient_steps = 0  # cumulative per-rank gradient steps
        self.train_steps = 0

        sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
        self.guard = family.guarded and bool(sentinel_cfg.get("enabled", True))
        self.sentinel = DivergenceSentinel(sentinel_cfg)
        self.train_fn = family.make_train_step(guard=True) if self.guard else family.make_train_step()
        self.data_sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))

    # -- player ---------------------------------------------------------------
    def _player_params(self):
        return self.family.player_params(self.params, self.gradient_steps > 0)

    def act(self, obs) -> Sequence[jax.Array]:
        jobs = self.family.prepare_obs(self.fabric, obs, cnn_keys=self.cnn_keys, num_envs=self.n_envs)
        self.rng, subkey = jax.random.split(self.rng)
        return self.family.player.get_actions(self._player_params(), jobs, subkey)

    def reset_player(self, idxs=None) -> None:
        self.family.player.init_states(self._player_params(), idxs)

    def poll(self) -> None:
        pass

    # -- replay ---------------------------------------------------------------
    def stage_step(self, step_data) -> None:
        self.rb.add(step_data, validate_args=self.cfg.buffer.validate_args)

    def stage_reset(self, reset_data, idxs) -> None:
        self.rb.add(reset_data, idxs, validate_args=self.cfg.buffer.validate_args)

    def patch_last(self, env_idx: int, updates: Dict[str, float]) -> None:
        _patch_last_row(self.rb, env_idx, updates)

    # -- training -------------------------------------------------------------
    def train(self, grants: int) -> None:
        if grants <= 0:
            return
        # the host-side replay path on the env-step critical path —
        # numpy window sampling + the f32 staging transfer — timed
        # for parity with the async tier's append-only segment
        # (BENCH_METRIC=dreamer_sebulba reads both)
        with timer("Time/replay_path_time", SumMetric):
            sample = self.rb.sample(
                self.batch_size,
                sequence_length=self.seq_len,
                n_samples=grants,
            )  # (G, T, B, ...)
            # ONE packed sharded transfer for the whole sample dict
            # (the PR-3 stager trick) instead of K per-key device_put
            # dispatches
            data = put_packed(sample, self.data_sharding, dtype=np.float32)
        with timer("Time/train_time", SumMetric):
            self.rng, train_key = jax.random.split(self.rng)
            self.params, self.opts, self.moments_state, metrics = self.train_fn(
                self.params, self.opts, self.moments_state, data, train_key,
                jnp.int32(self.gradient_steps),
            )
            _update_metrics(self.aggregator, self.family.metric_names, metrics)
        self.gradient_steps += grants
        self.train_steps += 1
        # metrics[-1] is the mean skipped fraction over the G steps
        if self.guard and self.sentinel.observe(float(metrics[-1]) * grants):
            self.sentinel.recover(self.ckpt_dir, self._rollback)

    def _rollback(self, good) -> None:
        fabric = self.fabric
        self.params = fabric.put_replicated(
            jax.tree.map(lambda t, s: jnp.asarray(s), self.params, {k: good[k] for k in self.params})
        )
        cast = lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s
        self.opts = fabric.put_replicated(jax.tree.map(cast, self.opts, good["optimizers"]))
        self.moments_state = fabric.put_replicated(jax.tree.map(cast, self.moments_state, good["moments"]))
        if good.get("rng") is not None:
            self.rng = jnp.asarray(good["rng"])

    # -- checkpoint and end ---------------------------------------------------
    def checkpoint_state(self):
        return (self.params, self.opts, self.moments_state), self.rng, self.rb

    def finish(self):
        return self.params, self.opts, self.moments_state


class BurstTrainer:
    """The burst topology (``algo.hybrid_player``): the policy runs on the
    host CPU from a packed bf16 params snapshot, replay lives in a
    device-resident uint8 sequence ring, and Ratio grants are dispatched in
    bursts on a trainer thread. This removes the per-step action pull (one
    device→host sync per env step) and the per-grant replay-batch upload
    (batch 16 x seq 64 of 64x64 pixels is ~12.6 MB per gradient step)."""

    def __init__(self, fabric, cfg, family: Family, setup: Setup):
        from sheeprl_tpu.utils.burst import HybridPlayerHarness

        self.fabric, self.cfg, self.family, self.rb = fabric, cfg, family, setup.rb
        self.rng = setup.rng  # kept for the checkpoint: the harness owns the streams it draws from
        self.n_envs = int(cfg.env.num_envs)
        self.cnn_keys = cfg.algo.cnn_keys.encoder
        # The host replay mirror only matters for checkpoints once the device
        # ring owns sampling; without it every pixel transition would be stored
        # twice (HBM ring + host RAM/memmap).
        self.host_mirror = bool(cfg.buffer.checkpoint)
        self.hp = HybridPlayerHarness(
            fabric, cfg,
            observation_space=setup.observation_space, cnn_keys=self.cnn_keys,
            mlp_keys=cfg.algo.mlp_keys.encoder, actions_dim=setup.actions_dim, capacity=setup.buffer_size,
            seq_len=int(cfg.algo.per_rank_sequence_length), batch_size=int(cfg.algo.per_rank_batch_size),
            policy_steps_per_iter=self.n_envs,
            make_burst_fn=lambda ring: family.make_train_step(ring=ring),
            player_subset=family.player_subset,
            carry=(*family.carry, jnp.int32(0)),
            rb=setup.rb if setup.restored else None,  # a checkpoint's rows, mirrored into the ring
            with_is_first=True, metric_names=family.metric_names, aggregator=setup.aggregator,
        )
        self.host_player = family.make_host_player(self.hp.host_device)

    gradient_steps = property(lambda self: self.hp.gradient_steps)
    train_steps = property(lambda self: self.hp.train_steps)
    grant_backlog = property(lambda self: self.hp.grant_backlog)
    staged_rows = property(lambda self: self.hp.runner.staged_count)

    # -- player ---------------------------------------------------------------
    def act(self, obs) -> Sequence[jax.Array]:
        jobs = self.family.prepare_obs(self.fabric, obs, cnn_keys=self.cnn_keys, num_envs=self.n_envs)
        # Host-CPU policy on the snapshot params: numpy obs +
        # CPU-committed params keep the whole step off the wire.
        return self.host_player.get_actions(self.hp.host_params, jobs, self.hp.host_key())

    def reset_player(self, idxs=None) -> None:
        self.host_player.init_states(self.hp.host_params, idxs)

    def poll(self) -> None:
        self.hp.poll()

    # -- replay ---------------------------------------------------------------
    def stage_step(self, step_data) -> None:
        if self.host_mirror:
            self.rb.add(step_data, validate_args=self.cfg.buffer.validate_args)
        self.hp.stage_step(step_data)

    def stage_reset(self, reset_data, idxs) -> None:
        if self.host_mirror:
            self.rb.add(reset_data, idxs, validate_args=self.cfg.buffer.validate_args)
        self.hp.stage_reset(reset_data, idxs)

    def patch_last(self, env_idx: int, updates: Dict[str, float]) -> None:
        if self.host_mirror:
            _patch_last_row(self.rb, env_idx, updates)
        # Same truncation patch on the row still in staging, for the keys the
        # device ring stores (truncated isn't one).
        self.hp.patch_last(env_idx, {k: v for k, v in updates.items() if k in self.hp.ring_keys})

    # -- training -------------------------------------------------------------
    def train(self, grants: int) -> None:
        self.hp.grant(grants)
        self.hp.pump()

    # -- checkpoint and end ---------------------------------------------------
    def checkpoint_state(self):
        # Latest trainer-thread handles (at most one burst stale).
        return tuple(self.hp.carry[:3]), self.rng, self.rb

    def finish(self):
        # Flush the tail: Ratio already counted the remaining grants; grants
        # that can never execute (data still shorter than a window) are
        # abandoned with the run.
        return tuple(self.hp.finish()[:3])


def platform_trainer(fabric, cfg):
    """The seam's implementation ``algo.hybrid_player.enabled`` asks for
    (``auto``: the burst topology iff the mesh is off the host CPU)."""
    return BurstTrainer if resolve_hybrid_player(cfg.algo.get("hybrid_player") or {}, fabric.mesh) else HostSampledTrainer


def _restore_replay(rb, replay):
    """The host buffer a checkpoint's ``rb`` entry resumes into."""
    from sheeprl_tpu.replay import DeviceReplayState, restore_host_env_buffer

    if isinstance(replay, list):
        return replay[0]
    if isinstance(replay, EnvIndependentReplayBuffer):
        return replay
    if isinstance(replay, DeviceReplayState):
        # a device ring's own snapshot (written by the coupled-resident topology
        # these mains no longer have): fill the host per-env buffers so the
        # collected experience survives the crossover
        restore_host_env_buffer(replay, rb, fill_missing={"truncated": ((1,), np.float32)})
        return rb
    raise RuntimeError(f"Cannot restore the replay buffer from {type(replay)}")


def _settle_device_resident(cfg, resumed: bool) -> None:
    """``buffer.device_resident`` is not these mains' option: a fresh run that
    sets it is refused; a resumed run, whose saved config wins over the command
    line, is told so and goes on without it (its replay resumes onto the host
    buffer, :func:`_restore_replay`)."""
    resident = cfg.buffer.get("device_resident", False)
    if str(resident).strip().lower() == "false":
        return
    if not resumed:
        raise ValueError(
            f"buffer.device_resident={resident} is not available to '{cfg.algo.name}': the coupled Dreamer mains keep "
            "their replay on the device through the burst topology, which `algo.hybrid_player.enabled` chooses "
            "(auto: on whenever the mesh is off the host CPU). Leave buffer.device_resident=false."
        )
    warnings.warn(
        f"The resumed run was started with buffer.device_resident={resident}, a topology '{cfg.algo.name}' no longer "
        "has: its replay resumes onto the host buffer, the run continues with buffer.device_resident=false, and "
        "`algo.hybrid_player.enabled` chooses whether the replay lives on the device (the burst topology)."
    )
    cfg.buffer.device_resident = False


def run(
    fabric,
    cfg: Dict[str, Any],
    build: Callable[[gym.spaces.Dict, Tuple[int, ...], bool], Family],
    *,
    trainer: Callable[..., Any],
    resume: Optional[Dict[str, Any]] = None,
    replay: Any = None,
    random_prefill: bool = True,
) -> None:
    """Run a coupled world-model main to its last iteration.

    ``build`` is called once the env's spaces are known. ``trainer`` is the
    seam's implementation (:func:`platform_trainer`, or one of the two classes).
    ``resume`` is the checkpoint whose counters, ``Ratio`` and key stream the
    run continues (None: a fresh run), ``replay`` a checkpoint's ``rb`` entry
    to restore. With ``random_prefill`` a fresh run acts at random until
    ``algo.learning_starts``."""
    _settle_device_resident(cfg, resumed=resume is not None)
    rank = fabric.global_rank

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    # Environment setup via the factory: FastSyncVectorEnv hot path +
    # RestartOnException resilience (reference: dreamer_v3.py:374-399)
    envs = vectorize_env(
        cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train", restart_on_exception=True
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape if is_continuous else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    family = build(observation_space, actions_dim, is_continuous)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Local data (reference: dreamer_v3.py:479-496)
    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
        obs_keys=tuple(obs_keys),
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    if replay is not None:
        rb = _restore_replay(rb, replay)

    # Counters (single-process world — same convention as PPO/SAC)
    last_train = 0
    start_iter = resume["iter_num"] + 1 if resume is not None else 1
    policy_step = resume["iter_num"] * cfg.env.num_envs if resume is not None else 0
    last_log = resume["last_log"] if resume is not None else 0
    last_checkpoint = resume["last_checkpoint"] if resume is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if resume is not None:
        cfg.algo.per_rank_batch_size = resume["batch_size"]
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if resume is not None:
        ratio.load_state_dict(resume["ratio"])

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    batch_size = int(cfg.algo.per_rank_batch_size)
    if batch_size % fabric.world_size != 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) must be divisible by the number of devices ({fabric.world_size})"
        )
    rng = jax.random.PRNGKey(cfg.seed)
    if resume is not None and resume.get("rng") is not None:
        rng = jnp.asarray(resume["rng"])  # continue the killed run's stream

    trainer = trainer(  # the class; from here on its instance
        fabric, cfg, family,
        Setup(
            rb=rb, restored=replay is not None, buffer_size=buffer_size, rng=rng, aggregator=aggregator,
            log_dir=log_dir, observation_space=observation_space, actions_dim=actions_dim,
        ),
    )

    # First observation (reference: dreamer_v3.py:538-551)
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    trainer.reset_player()

    # looked up on the module at call time: the on-chip benchmark puts its own in its place
    profiler = recorder.TraceProfiler(cfg.metric.get("profiler"), log_dir)

    for iter_num in range(start_iter, total_iters + 1):
        profiler.tick(iter_num)
        policy_step += policy_steps_per_iter
        # Host spans (utils.profiler.SPANS): one `iter` per iteration, closed
        # at the loop's foot; its counters are the values it starts from.
        iter_span = recorder.span(
            "iter", parent=recorder.ROOT, iter_num=iter_num, policy_step=policy_step,
            grad_steps=trainer.gradient_steps,
            grant_backlog=trainer.grant_backlog,
            staged_rows=trainer.staged_rows,
        ).start()

        trainer.poll()

        with timer("Time/env_interaction_time", SumMetric):
            if random_prefill and iter_num <= learning_starts and resume is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    # env-major sample: one-hot each action head along axis -1
                    acts2d = actions.reshape(cfg.env.num_envs, len(actions_dim))
                    actions = np.concatenate(
                        [np.eye(d, dtype=np.float32)[acts2d[:, i]] for i, d in enumerate(actions_dim)],
                        axis=-1,
                    )
            else:
                with recorder.span("player.act"):  # policy forward and the pull of its actions
                    action_list = trainer.act(obs)
                    actions = np.asarray(jnp.concatenate(action_list, axis=-1))
                    if is_continuous:
                        real_actions = actions
                    else:
                        real_actions = np.stack([np.asarray(a).argmax(axis=-1) for a in action_list], axis=-1)

            with recorder.span("stage"):
                step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1)
                trainer.stage_step(step_data)

            with recorder.span("env.step"):  # the wait for the env workers
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, agent_roe in enumerate(infos["restart_on_exception"]):
                if agent_roe and not dones[i]:
                    # the row just staged ends its episode as truncated
                    trainer.patch_last(i, {"terminated": 0.0, "truncated": 1.0, "is_first": 0.0})
                    step_data["is_first"][0, i] = np.ones_like(step_data["is_first"][0, i])

        if cfg.metric.log_level > 0 and "final_info" in infos:
            ep_info = infos["final_info"]
            if isinstance(ep_info, dict) and "episode" in ep_info:
                mask = ep_info.get("_episode", np.ones_like(np.asarray(ep_info["episode"]["r"]), dtype=bool))
                rews = np.asarray(ep_info["episode"]["r"])[mask]
                lens = np.asarray(ep_info["episode"]["l"])[mask]
                for i, (ep_rew, ep_len) in enumerate(zip(rews, lens)):
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                    print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        # Save the real next observation (reference: dreamer_v3.py:621-627)
        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k in obs_keys:
                        real_next_obs[k][idx] = np.asarray(final_obs[k])

        for k in obs_keys:
            step_data[k] = np.asarray(next_obs[k])[np.newaxis]
        obs = next_obs

        rewards = np.asarray(rewards, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["terminated"] = np.asarray(terminated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["rewards"] = clip_rewards_fn(rewards)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))), dtype=np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            with recorder.span("stage"):
                trainer.stage_reset(reset_data, dones_idxes)

            # Reset already-inserted step data (reference: dreamer_v3.py:652-658)
            step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
            step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
            step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
            step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
            trainer.reset_player(dones_idxes)

        # Train (reference: dreamer_v3.py:660-706)
        grants = ratio(policy_step - prefill_steps * policy_steps_per_iter) if iter_num >= learning_starts else 0
        # a resumed Ratio answers its first call with minus everything it granted before the checkpoint (its step
        # count starts again while `_prev` is the checkpoint's): there is nothing to take back
        trainer.train(max(grants, 0))
        gradient_steps, train_step = trainer.gradient_steps, trainer.train_steps

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
            if aggregator and not aggregator.disabled:
                logger.log_dict(aggregator.compute(), policy_step)
                aggregator.reset()
            if policy_step > 0:
                logger.log_dict({"Params/replay_ratio": gradient_steps / policy_step}, policy_step)
            if not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time", 0) > 0:
                    logger.log_dict(
                        {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                        policy_step,
                    )
                if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                    logger.log_dict(
                        {
                            "Time/sps_env_interaction": (
                                (policy_step - last_log) * cfg.env.action_repeat
                            )
                            / timer_metrics["Time/env_interaction_time"]
                        },
                        policy_step,
                    )
                timer.reset()
            last_log = policy_step
            last_train = train_step

        # Checkpoint (reference: dreamer_v3.py:735-760)
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            (params, opts, moments_state), ckpt_rng, replay_ckpt = trainer.checkpoint_state()
            ckpt_state = {
                **family.models(params),
                "optimizers": opts,
                "moments": moments_state,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng": ckpt_rng,
            }
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
                replay_buffer=replay_ckpt if cfg.buffer.checkpoint else None,
            )
        iter_span.stop()

    params, _, moments_state = trainer.finish()

    envs.close()
    profiler.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        family.test(params, log_dir, logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(fabric, log_models, cfg, family.registered_models(params, moments_state))
    logger.close()
