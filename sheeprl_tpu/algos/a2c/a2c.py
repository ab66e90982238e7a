"""A2C — coupled training (reference: ``sheeprl/algos/a2c/a2c.py:25-380``).

TPU-native structure: same host rollout as PPO; the optimization is ONE
jitted ``shard_map`` step that scans the local minibatches, *accumulates*
gradients (the reference's ``fabric.no_backward_sync`` grad-accumulation,
``a2c.py:61-100``) and applies a single optimizer update per iteration —
gradient ``pmean`` over ``dp`` happens once, on the accumulated gradient,
exactly like DDP syncing only at the last backward."""

from __future__ import annotations

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

from sheeprl_tpu.algos.a2c.agent import build_agent, forward_with_actions
from sheeprl_tpu.algos.a2c.utils import prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.ops import gae as gae_op
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(agent, tx, cfg, mesh, local_batch: int):
    """Build the jitted grad-accumulation step (see module docstring)."""
    mb_size = int(cfg.algo.per_rank_batch_size)
    n_mb = max(1, -(-local_batch // mb_size))
    padded = n_mb * mb_size
    loss_reduction = str(cfg.algo.loss_reduction)
    n_heads = 1 if agent.is_continuous else len(agent.actions_dim)
    split_sizes = np.cumsum(np.asarray(agent.actions_dim[:-1], dtype=np.int64)).tolist()

    def minibatch_grads(params, batch, weight):
        # `weight` zeroes padded rows so the single accumulated-gradient step
        # counts every real sample exactly once (the reference instead emits a
        # ragged last minibatch, a2c.py:61-100)
        obs = {k: batch[k].astype(jnp.float32) for k in agent.mlp_keys}
        if agent.is_continuous:
            actions = [batch["actions"]]
        else:
            actions = jnp.split(batch["actions"], split_sizes, axis=-1) if n_heads > 1 else [batch["actions"]]
        w = weight[:, None]

        def loss_fn(p):
            logprobs, _, values = forward_with_actions(agent, p, obs, actions)
            pg_elem = -(logprobs * batch["advantages"]) * w
            v_elem = ((values - batch["returns"]) ** 2) * w
            if loss_reduction == "mean":
                denom = jnp.maximum(w.sum(), 1.0)
                pg, v = pg_elem.sum() / denom, v_elem.sum() / denom
            else:  # sum
                pg, v = pg_elem.sum(), v_elem.sum()
            return pg + v, (pg, v)

        (_, (pg, v)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, pg, v

    def local_train(params, opt_state, data, key):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        perm = jax.random.permutation(key, local_batch)
        pad = padded - local_batch
        idx = jnp.concatenate([perm, jnp.zeros((pad,), dtype=perm.dtype)])
        weights = jnp.concatenate([jnp.ones((local_batch,), jnp.float32), jnp.zeros((pad,), jnp.float32)])
        batches = jax.tree.map(lambda x: x[idx.reshape(n_mb, mb_size)], data)
        mb_weights = weights.reshape(n_mb, mb_size)

        def body(acc, xs):
            batch, w = xs
            grads, pg, v = minibatch_grads(params, batch, w)
            acc = jax.tree.map(jnp.add, acc, grads)
            return acc, (pg, v)

        zero = jax.tree.map(jnp.zeros_like, params)
        grads, losses = jax.lax.scan(body, zero, (batches, mb_weights))
        grads = pmean_grads(grads, "dp")
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        pg, v = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, opt_state, pg, v

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P("dp"), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_train, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.fault import load_resume_state

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `algo.mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if k in observation_space.keys() and len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the A2C agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}."
            )
    if cfg.metric.log_level > 0:
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = cfg.algo.mlp_keys.encoder

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

    tx = build_optimizer(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    if state is not None:
        opt_state = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, state["optimizer"])
    opt_state = fabric.put_replicated(opt_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

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

    # Counters (single-process world — same convention as PPO)
    last_log = 0
    last_train = 0
    train_step = 0
    policy_step = 0
    last_checkpoint = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        policy_step = state["iter_num"] * policy_steps_per_iter

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

    local_batch_global = cfg.algo.rollout_steps * cfg.env.num_envs
    if local_batch_global % fabric.world_size != 0:
        raise ValueError(
            f"rollout_steps*num_envs ({local_batch_global}) must be divisible by the number of devices "
            f"({fabric.world_size})"
        )
    train_fn = make_train_step(agent, tx, cfg, fabric.mesh, local_batch_global // fabric.world_size)
    gae_fn = jax.jit(partial(gae_op, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda))

    # committed (replicated) so the rollout program compiles once — an
    # uncommitted first key gives call 1 its own one-off compiled signature
    rng = fabric.put_replicated(jax.random.PRNGKey(cfg.seed))

    # filter reset obs to the encoder keys — extra keys would give the first
    # policy dispatch its own one-off compiled signature
    step_data: Dict[str, np.ndarray] = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {k: np.asarray(reset_obs[k]) for k in obs_keys}
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]

    for iter_num in range(start_iter, total_iters + 1):
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += cfg.env.num_envs

            with timer("Time/env_interaction_time", SumMetric):
                jobs = prepare_obs(fabric, next_obs, mlp_keys=obs_keys, num_envs=cfg.env.num_envs)
                # fused single-dispatch step with device-carried PRNG key
                # (same hot-loop treatment as PPO)
                rng, env_actions, actions_np, _logprobs, values = player.rollout_step(params, rng, jobs)
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
                    jnext = prepare_obs(fabric, real_next_obs, mlp_keys=obs_keys, num_envs=len(truncated_envs))
                    vals = np.asarray(player.get_values(params, jnext))
                    rewards = rewards.astype(np.float32)
                    rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.uint8)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(cfg.env.num_envs, -1)

            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = np.asarray(values)[np.newaxis]
            step_data["actions"] = actions_np[np.newaxis]
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

        # GAE (reference: a2c.py:316-323)
        local_data = rb.to_tensor()
        jobs = prepare_obs(fabric, next_obs, mlp_keys=obs_keys, num_envs=cfg.env.num_envs)
        next_values = player.get_values(params, jobs)
        returns, advantages = gae_fn(
            local_data["rewards"], local_data["values"], local_data["dones"], next_values
        )
        local_data["returns"] = returns
        local_data["advantages"] = advantages

        flat_data = {k: v.reshape(-1, *v.shape[2:]) for k, v in local_data.items()}
        flat_data = fabric.shard_data(flat_data)

        with timer("Time/train_time", SumMetric):
            rng, train_key = jax.random.split(rng)
            params, opt_state, pg_l, v_l = train_fn(params, opt_state, flat_data, train_key)
            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/policy_loss", pg_l)
                aggregator.update("Loss/value_loss", v_l)
        train_step += 1

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
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

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state)

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(fabric, log_models, cfg, {"agent": params})
    logger.close()
