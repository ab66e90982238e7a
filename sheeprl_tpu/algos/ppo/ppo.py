"""PPO — coupled training (reference: ``sheeprl/algos/ppo/ppo.py:30-460``).

TPU-native structure:

- the rollout loop runs on host, with a jitted policy forward per step
  (the env hot loop, reference ``ppo.py:267-320``);
- GAE is one jitted ``lax.scan`` over the time axis (``ops.gae``);
- the whole optimization phase — ``update_epochs`` × minibatches, with
  per-epoch permutation, advantage normalization, losses, global-norm clip and
  optimizer update — is a SINGLE jitted ``shard_map`` over the device mesh:
  data enters batch-sharded on the ``dp`` axis, params replicated, and the
  per-minibatch gradient ``pmean`` over ``dp`` reproduces DDP semantics
  (reference train fn: ``ppo.py:30-102``) with zero per-minibatch dispatch
  overhead.

Minibatching detail: each device permutes its local shard per epoch (the
reference's per-rank ``RandomSampler``); if the local batch is not divisible
by ``per_rank_batch_size`` the permutation is wrapped to pad the last
minibatch (the reference instead emits a ragged last batch).
"""

from __future__ import annotations

import copy
import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from sheeprl_tpu.algos.ppo.agent import build_agent, forward_with_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.analysis.tracecheck import tracecheck
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.ops import gae as gae_op
from sheeprl_tpu.parallel import pod as pod_runtime
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

__all__ = ["main", "make_train_step", "make_local_train"]


def make_local_train(agent, tx, cfg, local_batch: int, guard: bool = False):
    """Build the per-device epoch/minibatch optimization body (see module
    docstring) — a function ``(params, opt_state, data, key, clip_coef,
    ent_coef) -> (params, opt_state, pg, v, ent)`` that must run inside a
    ``shard_map`` with a ``dp`` axis. :func:`make_train_step` wraps it for
    the host-loop path; ``ppo_anakin`` fuses it after an on-device rollout.

    ``guard=True`` arms the divergence sentinel's in-graph half
    (:func:`sheeprl_tpu.ops.finite_guard`): a minibatch whose loss or
    (post-pmean) gradients are non-finite leaves params/optimizer state
    untouched, and the function returns a sixth output — the number of
    skipped updates — for the host-side
    :class:`~sheeprl_tpu.fault.DivergenceSentinel`.

    ``buffer.share_data`` (reference ``ppo.py:40-47,362-366``: all_gather +
    DistributedSampler) maps to an in-graph ``lax.all_gather`` over ``dp``
    followed by a COMMON permutation of the global batch, each device taking
    its own contiguous shard per epoch — identical sampling semantics, but
    the gather rides the mesh interconnect instead of NCCL.
    """
    share_data = bool(cfg.buffer.share_data)
    mb_size = int(cfg.algo.per_rank_batch_size)
    n_mb = max(1, -(-local_batch // mb_size))
    padded = n_mb * mb_size
    if local_batch % mb_size != 0:
        warnings.warn(
            f"Per-device batch ({local_batch}) is not divisible by per_rank_batch_size ({mb_size}): "
            f"the last minibatch of every epoch cyclically repeats {padded - local_batch} already-sampled "
            "transitions (the reference instead emits a ragged last batch). Adjust rollout_steps/num_envs/"
            "per_rank_batch_size to avoid duplicated gradient samples.",
            UserWarning,
        )
    update_epochs = int(cfg.algo.update_epochs)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    vf_coef = float(cfg.algo.vf_coef)
    loss_reduction = str(cfg.algo.loss_reduction)
    n_heads = 1 if agent.is_continuous else len(agent.actions_dim)
    split_sizes = np.cumsum(np.asarray(agent.actions_dim[:-1], dtype=np.int64)).tolist()

    def minibatch_step(carry, batch):
        params, opt_state, clip_coef, ent_coef = carry
        # normalize obs in-graph (reference: train → normalize_obs, ppo.py:58-60)
        obs = {k: batch[k].astype(jnp.float32) / 255.0 - 0.5 for k in agent.cnn_keys}
        obs.update({k: batch[k].astype(jnp.float32) for k in agent.mlp_keys})
        if agent.is_continuous:
            actions = [batch["actions"]]
        else:
            actions = jnp.split(batch["actions"], split_sizes, axis=-1) if n_heads > 1 else [batch["actions"]]

        advantages = batch["advantages"]
        if normalize_adv:
            advantages = (advantages - advantages.mean()) / (advantages.std() + 1e-8)

        def loss_fn(p):
            new_logprobs, entropy, new_values = forward_with_actions(agent, p, obs, actions)
            pg = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, loss_reduction)
            v = value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, loss_reduction)
            ent = entropy_loss(entropy, loss_reduction)
            return pg + vf_coef * v + ent_coef * ent, (pg, v, ent)

        (loss, (pg, v, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = pmean_grads(grads, "dp")
        if guard:
            from sheeprl_tpu.ops import finite_guard, guarded_select

            ok = jnp.logical_and(finite_guard(grads), finite_guard(loss))
            # the loss is per-device (grads are pmean'd but losses are not):
            # all-reduce the verdict so every device takes the same branch
            # and the replicated params stay bit-identical across the mesh
            ok = jax.lax.pmin(ok.astype(jnp.int32), "dp").astype(bool)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            params, opt_state = guarded_select(ok, (new_params, new_opt_state), (params, opt_state))
            return (params, opt_state, clip_coef, ent_coef), (pg, v, ent, 1.0 - ok.astype(jnp.float32))
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, clip_coef, ent_coef), (pg, v, ent)

    def local_train(params, opt_state, data, key, clip_coef, ent_coef):
        # shapes here are per-device: (local_batch, ...)
        n_dev = axis_size("dp")
        if share_data:
            # every device sees the GLOBAL batch; the sampler key stays
            # common across devices (the reference's same-seed
            # DistributedSampler), each device slicing its own shard
            data = jax.tree.map(lambda x: jax.lax.all_gather(x, "dp", tiled=True), data)
        else:
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))

        def epoch_body(carry, epoch_key):
            if share_data:
                perm = jax.random.permutation(epoch_key, local_batch * n_dev)
                perm = jax.lax.dynamic_slice_in_dim(
                    perm, jax.lax.axis_index("dp") * local_batch, local_batch
                )
            else:
                perm = jax.random.permutation(epoch_key, local_batch)
            # cyclic pad up to a whole number of minibatches (handles
            # mb_size > local_batch, e.g. few envs over many devices)
            perm = jnp.resize(perm, (padded,))
            mb_idx = perm.reshape(n_mb, mb_size)
            batches = jax.tree.map(lambda x: x[mb_idx], data)
            carry, losses = jax.lax.scan(minibatch_step, carry, batches)
            return carry, losses

        carry = (params, opt_state, clip_coef, ent_coef)
        carry, losses = jax.lax.scan(epoch_body, carry, jax.random.split(key, update_epochs))
        params, opt_state, _, _ = carry
        if guard:
            pg, v, ent, bad = losses
            pg, v, ent = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), (pg, v, ent))
            return params, opt_state, pg, v, ent, bad.sum()
        pg, v, ent = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, opt_state, pg, v, ent

    return local_train


def make_train_step(agent, tx, cfg, mesh, local_batch: int, donate: bool = True, guard: bool = False):
    """Wrap :func:`make_local_train` in the jitted ``shard_map`` used by the
    host-loop path: data batch-sharded on ``dp``, params replicated.
    ``guard=True`` adds the skipped-update count as a sixth output (see
    :func:`make_local_train`)."""
    local_train = make_local_train(agent, tx, cfg, local_batch, guard=guard)

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P("dp"), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()) if guard else (P(), P(), P(), P(), P()),
        check_vma=False,
    )
    # The decoupled topology disables donation: the player thread still reads
    # the previous params snapshot while the trainer steps (see
    # ppo_decoupled.py), and donated buffers would be deleted under it.
    # Output placements are pinned (everything here is replicated): params and
    # opt_state feed the next call, and a compiler-chosen equivalent placement
    # keys a fresh C++ jit-cache entry — the PR 8 silent-recompile class
    # (checked by graft-audit AUD002 on every fed-back output).
    from jax.sharding import NamedSharding

    return jax.jit(
        shard_train,
        donate_argnums=(0, 1) if donate else (),
        out_shardings=NamedSharding(mesh, P()),
    )


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.fault import DivergenceSentinel, NaNInjector, load_resume_state

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)

    rank = fabric.global_rank
    world_size = fabric.world_size

    state = None
    if cfg.checkpoint.resume_from:
        # corrupt/half-written resume target falls back to the previous
        # complete manifest entry instead of dying
        state = load_resume_state(cfg.checkpoint.resume_from)

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    # Environment setup
    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
    observation_space = envs.single_observation_space

    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder == []:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`cnn_keys.encoder=[rgb]` or `mlp_keys.encoder=[state]`"
        )
    if cfg.metric.log_level > 0:
        print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )

    agent, params, player = build_agent(
        fabric, actions_dim, is_continuous, cfg, observation_space,
        state["agent"] if state is not None else None,
    )

    # Optimizer with injectable lr for annealing (reference scheduler: ppo.py:252-258)
    from sheeprl_tpu.optim.builders import build_optimizer

    lr0 = float(cfg.algo.optimizer.lr)
    tx = optax.inject_hyperparams(
        lambda learning_rate: build_optimizer(
            {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
        )
    )(learning_rate=lr0)
    opt_state = tx.init(params)
    if state is not None:
        opt_state = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, state["optimizer"])
    opt_state = fabric.put_replicated(opt_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Local data
    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = ReplayBuffer(
        cfg.buffer.size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=obs_keys,
    )

    # Global counters (reference: ppo.py:215-240)
    # Counter semantics: devices shard the batch, envs live PER PROCESS —
    # single-process runs keep the old "one process owns all envs" counters,
    # a pod of N workers steps num_envs envs in EACH worker, so global policy
    # steps advance by num_envs * process_count per env step (the reference's
    # per-rank-envs convention, with rank = pod worker). Checkpoint counters
    # use the same convention, so a resumed gang restores the GLOBAL step.
    n_proc = fabric.process_count
    world_envs = int(cfg.env.num_envs * n_proc)
    last_train = 0
    train_step = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_step = state["iter_num"] * world_envs * cfg.algo.rollout_steps if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(world_envs * cfg.algo.rollout_steps)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"]

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

    # Jitted pieces. Each process contributes its local rollout rows;
    # shard_data assembles the GLOBAL batch (concat over processes), so the
    # per-device row count divides the global batch, not the local one.
    local_batch = cfg.algo.rollout_steps * cfg.env.num_envs
    global_batch = local_batch * n_proc
    if global_batch % fabric.world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs*processes ({global_batch}) must be divisible by the number of "
            f"devices ({fabric.world_size})"
        )
    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True))
    sentinel = DivergenceSentinel(sentinel_cfg)
    nan_injector = NaNInjector(cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    # Registered hot paths: post-warmup retraces (and, under the trace-
    # hygiene fixture, implicit transfers) are budget violations.
    train_fn = tracecheck.instrument(
        make_train_step(
            agent, tx, cfg, fabric.mesh, global_batch // fabric.world_size, guard=guard
        ),
        name="ppo.train_step",
    )
    gae_fn = tracecheck.instrument(
        jax.jit(partial(gae_op, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda)),
        name="ppo.gae",
    )

    rng = jax.random.PRNGKey(cfg.seed)
    rng, _ = jax.random.split(rng)
    if state is not None and state.get("rng") is not None:
        # restore the rollout/train RNG so the resumed stream continues
        # where the killed run left off
        rng = jnp.asarray(state["rng"])
    # Commit the carried key to the mesh (replicated) BEFORE the first
    # rollout dispatch: the jitted rollout step returns its carried key
    # committed, so an uncommitted first key means the entire rollout program
    # compiles twice — once for call 1, once for every call after it
    # (caught by analysis.tracecheck on ppo.rollout_step).
    rng = fabric.put_replicated(rng)

    lr = lr0
    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)

    # First observation — filtered to the encoder keys: feeding the raw
    # reset dict (which can carry extra keys, e.g. rgb when only state is
    # encoded) gave the FIRST rollout dispatch a wider signature than every
    # later one — a whole wasted compile of the policy program plus dead
    # host->device bytes (caught by analysis.tracecheck on ppo.rollout_step).
    step_data: Dict[str, np.ndarray] = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {k: np.asarray(reset_obs[k]) for k in obs_keys}
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]

    cnn_keys = cfg.algo.cnn_keys.encoder

    from sheeprl_tpu.utils.profiler import TraceProfiler

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir)

    for iter_num in range(start_iter, total_iters + 1):
        profiler.tick(iter_num)
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += world_envs

            with timer("Time/env_interaction_time", SumMetric):
                jobs = prepare_obs(fabric, next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                rng, env_actions, actions_np, logprobs, values = player.rollout_step(params, rng, jobs)
                real_actions = np.asarray(env_actions)
                actions_np = np.asarray(actions_np)

                obs, rewards, terminated, truncated, info = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0 and "final_obs" in info:
                    real_next_obs = {
                        k: np.stack([np.asarray(info["final_obs"][te][k], dtype=np.float32) for te in truncated_envs])
                        for k in obs_keys
                    }
                    jnext = prepare_obs(fabric, real_next_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                    vals = np.asarray(player.get_values(params, jnext))
                    rewards = rewards.astype(np.float32)
                    rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.uint8)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(cfg.env.num_envs, -1)

            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = np.asarray(values)[np.newaxis]
            step_data["actions"] = actions_np[np.newaxis]
            step_data["logprobs"] = np.asarray(logprobs)[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            if cfg.buffer.memmap:
                step_data["returns"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
                step_data["advantages"] = np.zeros_like(rewards, shape=(1, *rewards.shape))

            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs = {}
            for k in obs_keys:
                _obs = np.asarray(obs[k])
                step_data[k] = _obs[np.newaxis]
                next_obs[k] = _obs

            if cfg.metric.log_level > 0 and "final_info" in info:
                ep_info = info["final_info"]
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

        # GAE on device (reference: ppo.py:346-360). The three host inputs
        # are staged with ONE explicit device_put — feeding numpy views
        # straight into the jitted scan was an implicit per-iteration
        # host->device transfer (flagged by the tracecheck transfer guard).
        local_data = rb.to_numpy()
        jobs = prepare_obs(fabric, next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
        next_values = player.get_values(params, jobs)
        rewards_d, values_d, dones_d = jax.device_put(
            (local_data["rewards"], local_data["values"], local_data["dones"])
        )
        returns, advantages = gae_fn(rewards_d, values_d, dones_d, next_values)

        # Stage ONCE: flatten (T, N) → batch as host-side views (contiguous
        # reshape, no copy), keep the GAE outputs on device, and ship the
        # whole dict in a single sharded device_put — the old path staged
        # every key to the default device (to_tensor) and then re-sharded it
        # key by key, two copies per key per iteration.
        flat_data = {k: v.reshape(-1, *v.shape[2:]) for k, v in local_data.items()}
        flat_data["returns"] = returns.reshape(-1, *returns.shape[2:])
        flat_data["advantages"] = advantages.reshape(-1, *advantages.shape[2:])
        if nan_injector:
            nan_injector.poison(flat_data, "advantages", iter_num)
        flat_data = fabric.shard_data(flat_data)

        with timer("Time/train_time", SumMetric):
            rng, train_key = jax.random.split(rng)
            outs = train_fn(
                params, opt_state, flat_data, train_key,
                jnp.asarray(clip_coef, dtype=jnp.float32), jnp.asarray(ent_coef, dtype=jnp.float32),
            )
            params, opt_state, pg_l, v_l, ent_l = outs[:5]
            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/policy_loss", pg_l)
                aggregator.update("Loss/value_loss", v_l)
                aggregator.update("Loss/entropy_loss", ent_l)
        train_step += 1

        if guard and sentinel.observe(outs[5]):
            def _rollback(good):
                nonlocal params, opt_state, rng
                params = fabric.put_replicated(
                    jax.tree.map(lambda t, s: jnp.asarray(s), params, good["agent"])
                )
                opt_state = fabric.put_replicated(
                    jax.tree.map(
                        lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, good["optimizer"]
                    )
                )
                if good.get("rng") is not None:
                    rng = jnp.asarray(good["rng"])

            sentinel.recover(ckpt_dir, _rollback)

        if cfg.metric.log_level > 0:
            logger.log_dict({"Info/learning_rate": lr, "Info/clip_coef": clip_coef, "Info/ent_coef": ent_coef}, policy_step)
            restarts = getattr(envs, "env_restarts", 0)
            if restarts:
                logger.log_dict({"Fault/env_restarts": restarts}, policy_step)
            if guard and sentinel.total_skipped:
                logger.log_dict({"Fault/skipped_updates": sentinel.total_skipped}, policy_step)
            if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                if aggregator and not aggregator.disabled:
                    logger.log_dict(aggregator.compute(), policy_step)
                    aggregator.reset()
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

        # Anneal lr and coefficients (reference: ppo.py:415-424)
        if cfg.algo.anneal_lr:
            lr = polynomial_decay(iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0)
            opt_state.hyperparams["learning_rate"] = jnp.asarray(lr, dtype=jnp.float32)
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        # Pod worker plumbing: publish the completed global step to the
        # launcher's heartbeat file, and agree ACROSS RANKS on rank-0's drain
        # flag — SIGTERM delivery timing differs per worker, and a gang where
        # one rank checkpoints-and-exits while another enters the next
        # rollout deadlocks in the collectives.
        pod_runtime.beat_step(policy_step)
        drain_now = pod_runtime.drain_requested()
        if n_proc > 1:
            drain_now = bool(np.asarray(fabric.broadcast_obj(np.asarray(drain_now, dtype=np.int32), src=0)))

        if (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or (iter_num == total_iters and cfg.checkpoint.save_last)
            or drain_now
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "scheduler": None,
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng": rng,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state)

        if drain_now:
            # checkpoint-and-exit: the pod launcher drains outermost-first,
            # and a worker that exits 0 here is generation teardown, not a
            # failure — the non-daemon checkpoint writer settles before exit
            print(f"Rank-{rank}: drain requested — checkpointed at policy_step={policy_step}, exiting")
            break

    envs.close()
    profiler.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import register_model

        from sheeprl_tpu.algos.ppo.utils import log_models

        register_model(fabric, log_models, cfg, {"agent": params})
    logger.close()


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


def _abstract_like(tree, sharding=None):
    """ShapeDtypeStruct twin of a pytree carrying the sharding the driver
    stages the real values with (``sharding=None`` keeps each leaf's OWN
    committed sharding, e.g. a DeviceReplayBuffer ring with mixed placements)
    — the audit lowers against these, so the compiled artifact is inspected
    WITHOUT materializing anything."""

    def leaf(x):
        sh = sharding if sharding is not None else getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=sh)

    return jax.tree.map(leaf, tree)


def audit_setup(spec: AuditMesh):
    """Tiny discrete-control PPO program context on the audit mesh — shared
    by the ``ppo.*`` and ``ppo_sebulba.*`` registrations (the two paths run
    the SAME train-step program, donation aside)."""
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.algos.ppo.agent import PPOAgent

    mesh = spec.build()
    num_envs = 2 * spec.devices
    cfg = compose(
        [
            "exp=ppo",
            f"env.num_envs={num_envs}",
            "algo.rollout_steps=16",
            "algo.per_rank_batch_size=8",
            "algo.update_epochs=1",
        ]
    )
    agent = PPOAgent(
        actions_dim=(2,),
        is_continuous=False,
        cnn_keys=(),
        mlp_keys=("state",),
        encoder_cfg=dict(cfg.algo.encoder),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
    )
    params = agent.init(jax.random.PRNGKey(0), {"state": jnp.zeros((num_envs, 4), jnp.float32)})
    tx = optax.inject_hyperparams(
        lambda learning_rate: build_optimizer(
            {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
        )
    )(learning_rate=float(cfg.algo.optimizer.lr))
    opt_state = tx.init(params)
    B = int(cfg.algo.rollout_steps) * num_envs
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("dp"))
    data = {
        "state": jax.ShapeDtypeStruct((B, 4), jnp.float32, sharding=shard),
        "actions": jax.ShapeDtypeStruct((B, 2), jnp.float32, sharding=shard),
        "logprobs": jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=shard),
        "values": jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=shard),
        "returns": jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=shard),
        "advantages": jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=shard),
        "rewards": jax.ShapeDtypeStruct((B, 1), jnp.float32, sharding=shard),
        "dones": jax.ShapeDtypeStruct((B, 1), jnp.uint8, sharding=shard),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return {
        "cfg": cfg,
        "agent": agent,
        "params": params,
        "tx": tx,
        "opt_state": opt_state,
        "mesh": mesh,
        "rep": rep,
        "B": B,
        "num_envs": num_envs,
        "data": data,
        "key": key,
        "scalar": scalar,
    }


def audit_train_step_program(spec: AuditMesh, name: str, donate: bool):
    """The (shared) PPO train-step audit program; ``donate=False`` is the
    Sebulba learner's variant (the player thread still reads old snapshots)."""
    s = audit_setup(spec)
    fn = make_train_step(
        s["agent"], s["tx"], s["cfg"], s["mesh"], s["B"] // spec.devices, donate=donate, guard=True
    )
    return AuditProgram(
        name=name,
        fn=fn,
        args=(
            _abstract_like(s["params"], s["rep"]),
            _abstract_like(s["opt_state"], s["rep"]),
            s["data"],
            s["key"],
            s["scalar"],
            s["scalar"],
        ),
        source=__name__ if name.startswith("ppo.") else "sheeprl_tpu.algos.ppo.ppo_sebulba",
        donate_argnums=(0, 1) if donate else (),
        feedback_outputs=(0, 1),
        out_decl={0: P(), 1: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )


def audit_gae_program(spec: AuditMesh, name: str, num_envs: int = 4, T: int = 16):
    """The jitted GAE scan (single-device: GAE runs where the rollout lands)."""
    cfg_gamma, cfg_lambda = 0.99, 0.95
    fn = jax.jit(partial(gae_op, gamma=cfg_gamma, gae_lambda=cfg_lambda))
    shp = (T, num_envs, 1)
    return AuditProgram(
        name=name,
        fn=fn,
        args=(
            jax.ShapeDtypeStruct(shp, jnp.float32),
            jax.ShapeDtypeStruct(shp, jnp.float32),
            jax.ShapeDtypeStruct(shp, jnp.uint8),
            jax.ShapeDtypeStruct((num_envs, 1), jnp.float32),
        ),
        source=__name__ if name.startswith("ppo.") else "sheeprl_tpu.algos.ppo.ppo_sebulba",
        check_input_shardings=False,
    )


@register_audit_programs("ppo.train_step", "ppo.gae", "ppo.rollout_step")
def _audit_programs(spec: AuditMesh):
    from sheeprl_tpu.algos.ppo.agent import PPOPlayer

    yield audit_train_step_program(spec, "ppo.train_step", donate=True)
    yield audit_gae_program(spec, "ppo.gae")

    s = audit_setup(spec)
    player = PPOPlayer(s["agent"], cnn_keys=(), mlp_keys=("state",))
    yield AuditProgram(
        name="ppo.rollout_step",
        # the tracecheck wrapper is transparent; lower the jitted fn under it
        fn=player._rollout_step.__wrapped__,
        args=(
            _abstract_like(s["params"], s["rep"]),
            s["key"],
            # obs arrive as HOST arrays by contract (prepare_obs) — no
            # declared placement, and input-sharding checks stay off
            {"state": jax.ShapeDtypeStruct((s["num_envs"], 4), jnp.float32)},
        ),
        source=__name__,
        mesh=s["mesh"],
        check_input_shardings=False,
    )
