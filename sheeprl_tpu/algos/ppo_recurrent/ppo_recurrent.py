"""Recurrent PPO — coupled training
(reference: ``sheeprl/algos/ppo_recurrent/ppo_recurrent.py``).

TPU-native structure:

- host rollout carries the LSTM state; it resets on done
  (``reset_recurrent_state_on_done``) and stores per-step ``prev_hx/prev_cx``
  so any chunked sequence can restart the recurrence exactly;
- after GAE, the rollout is chunked host-side into per-episode sequences of
  ``per_rank_sequence_length`` padded with a mask
  (reference: ``ppo_recurrent.py:406-445``);
- the sequence count is right-padded with zero-mask sequences to a
  power-of-two bucket divisible by (devices × num-batches) so the jitted
  train step sees a small, stable set of shapes instead of recompiling every
  iteration (XLA static-shape requirement; the padded sequences contribute
  nothing to the masked losses);
- the optimization (epochs × minibatches of sequences, LSTM re-run from the
  stored initial state, masked losses, grad ``pmean``) is one jitted
  ``shard_map`` over the mesh, sequences sharded on ``dp``.
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

from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent, forward_with_actions
from sheeprl_tpu.algos.ppo_recurrent.utils import chunk_sequences, prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.ops import gae as gae_op
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(agent, tx, cfg, mesh, s_local: int):
    """Jitted epochs×minibatches optimization over ``(SL, S)`` sequence
    batches (see module docstring). ``s_local`` sequences per device."""
    nb = max(1, int(cfg.algo.per_rank_num_batches))
    mb = max(1, s_local // nb)
    n_mb = s_local // mb
    update_epochs = int(cfg.algo.update_epochs)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    vf_coef = float(cfg.algo.vf_coef)
    n_heads = 1 if agent.is_continuous else len(agent.actions_dim)
    split_sizes = np.cumsum(np.asarray(agent.actions_dim[:-1], dtype=np.int64)).tolist()
    cnn_keys = list(agent.cnn_keys)
    obs_keys = list(agent.cnn_keys) + list(agent.mlp_keys)

    def minibatch_step(carry, batch):
        params, opt_state, clip_coef, ent_coef = carry
        w = batch["mask"][..., None]  # (SL, mb, 1)
        wsum = jnp.maximum(w.sum(), 1.0)
        obs = {}
        for k in obs_keys:
            v = batch[k]
            obs[k] = v / 255.0 - 0.5 if k in cnn_keys else v
        if agent.is_continuous:
            actions = [batch["actions"]]
        else:
            actions = jnp.split(batch["actions"], split_sizes, axis=-1) if n_heads > 1 else [batch["actions"]]

        advantages = batch["advantages"]
        if normalize_adv:
            mean = (advantages * w).sum() / wsum
            var = (((advantages - mean) ** 2) * w).sum() / wsum
            advantages = (advantages - mean) / (jnp.sqrt(var) + 1e-8)

        hx0 = batch["prev_hx"][0]
        cx0 = batch["prev_cx"][0]

        def loss_fn(p):
            new_logprobs, entropy, new_values = forward_with_actions(
                agent, p, obs, batch["prev_actions"], hx0, cx0, actions
            )
            # masked-mean PPO losses (reference train(): ppo_recurrent.py:31-115)
            logratio = new_logprobs - batch["logprobs"]
            ratio = jnp.exp(logratio)
            pg1 = -advantages * ratio
            pg2 = -advantages * jnp.clip(ratio, 1.0 - clip_coef, 1.0 + clip_coef)
            pg = (jnp.maximum(pg1, pg2) * w).sum() / wsum

            if clip_vloss:
                v_clipped = batch["values"] + jnp.clip(
                    new_values - batch["values"], -clip_coef, clip_coef
                )
                v_elem = jnp.maximum((new_values - batch["returns"]) ** 2, (v_clipped - batch["returns"]) ** 2)
                v = 0.5 * (v_elem * w).sum() / wsum
            else:
                v = ((new_values - batch["returns"]) ** 2 * w).sum() / wsum

            ent = -(entropy * w).sum() / wsum
            return pg + vf_coef * v + ent_coef * ent, (pg, v, ent)

        (_, (pg, v, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = pmean_grads(grads, "dp")
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, clip_coef, ent_coef), (pg, v, ent)

    def local_train(params, opt_state, data, key, clip_coef, ent_coef):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))

        def epoch_body(carry, epoch_key):
            perm = jax.random.permutation(epoch_key, s_local)
            mb_idx = perm[: n_mb * mb].reshape(n_mb, mb)
            batches = jax.tree.map(lambda x: jnp.moveaxis(x[:, mb_idx], 1, 0), data)
            carry, losses = jax.lax.scan(minibatch_step, carry, batches)
            return carry, losses

        carry = (params, opt_state, clip_coef, ent_coef)
        carry, losses = jax.lax.scan(epoch_body, carry, jax.random.split(key, update_epochs))
        params, opt_state, _, _ = carry
        pg, v, ent = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, opt_state, pg, v, ent

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(None, "dp"), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_train, donate_argnums=(0, 1))


def _bucket(n: int, quantum: int) -> int:
    """Round ``n`` up to ``quantum * 2^k`` (shape-stability bucketing)."""
    units = max(1, -(-n // quantum))
    p = 1
    while p < units:
        p *= 2
    return quantum * p


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.fault import load_resume_state

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)

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

    rb = ReplayBuffer(
        cfg.algo.rollout_steps,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=obs_keys,
    )

    # Counters (single-process world — same convention as PPO)
    last_train = 0
    train_step = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps)
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

    seq_len = int(cfg.algo.per_rank_sequence_length)
    nb = max(1, int(cfg.algo.per_rank_num_batches))
    quantum = fabric.world_size * nb
    gae_fn = jax.jit(partial(gae_op, gamma=cfg.algo.gamma, gae_lambda=cfg.algo.gae_lambda))
    data_sharding = NamedSharding(fabric.mesh, P(None, "dp"))
    train_fns: Dict[int, Any] = {}

    rng = jax.random.PRNGKey(cfg.seed)
    lr = lr0
    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)
    cnn_keys = cfg.algo.cnn_keys.encoder

    # filter reset obs to the encoder keys — extra keys would give the first
    # policy dispatch its own one-off compiled signature
    step_data: Dict[str, np.ndarray] = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {k: np.asarray(reset_obs[k]) for k in obs_keys}
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]

    states = player.reset_states()
    prev_actions = np.zeros((1, cfg.env.num_envs, int(sum(actions_dim))), dtype=np.float32)

    for iter_num in range(start_iter, total_iters + 1):
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += cfg.env.num_envs

            with timer("Time/env_interaction_time", SumMetric):
                jobs = prepare_obs(fabric, next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                rng, subkey = jax.random.split(rng)
                prev_hx, prev_cx = np.asarray(states[0]), np.asarray(states[1])
                actions, logprobs, values, new_states = player(
                    params, jobs, jax.device_put(prev_actions), states, subkey
                )
                if is_continuous:
                    real_actions = np.concatenate([np.asarray(a) for a in actions], axis=-1)
                else:
                    real_actions = np.stack([np.asarray(a).argmax(axis=-1) for a in actions], axis=-1)
                actions_np = np.concatenate([np.asarray(a) for a in actions], axis=-1).reshape(
                    1, cfg.env.num_envs, -1
                )

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
                    trunc_states = tuple(s[truncated_envs] for s in new_states)
                    vals, _ = player.get_values(
                        params,
                        jnext,
                        jax.device_put(actions_np[:, truncated_envs]),
                        trunc_states,
                    )
                    rewards = rewards.astype(np.float32)
                    rewards[truncated_envs] += cfg.algo.gamma * np.asarray(vals).reshape(
                        rewards[truncated_envs].shape
                    )
                dones = np.logical_or(terminated, truncated).reshape(1, cfg.env.num_envs, -1).astype(np.float32)
                rewards = np.asarray(rewards, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)

            step_data["dones"] = dones
            step_data["values"] = np.asarray(values).reshape(1, cfg.env.num_envs, -1)
            step_data["actions"] = actions_np
            step_data["rewards"] = rewards
            step_data["logprobs"] = np.asarray(logprobs).reshape(1, cfg.env.num_envs, -1)
            step_data["prev_hx"] = prev_hx[np.newaxis]
            step_data["prev_cx"] = prev_cx[np.newaxis]
            step_data["prev_actions"] = prev_actions.copy()
            if cfg.buffer.memmap:
                step_data["returns"] = np.zeros_like(rewards)
                step_data["advantages"] = np.zeros_like(rewards)

            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            prev_actions = ((1 - dones) * actions_np).astype(np.float32)
            next_obs = {}
            for k in obs_keys:
                _obs = np.asarray(obs[k])
                step_data[k] = _obs[np.newaxis]
                next_obs[k] = _obs

            # Reset the states on done (reference: ppo_recurrent.py:372-375)
            if cfg.algo.reset_recurrent_state_on_done:
                done_mask = jnp.asarray(1.0 - dones[0], dtype=jnp.float32)
                states = tuple(done_mask * s for s in new_states)
            else:
                states = new_states

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

        # GAE (reference: ppo_recurrent.py:383-404)
        local_data = {k: np.asarray(v.array if hasattr(v, "array") else v) for k, v in rb.buffer.items()}
        jobs = prepare_obs(fabric, next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
        next_values, _ = player.get_values(
            params, jobs, jax.device_put(np.asarray(actions_np)), states
        )
        returns, advantages = gae_fn(
            jnp.asarray(local_data["rewards"]),
            jnp.asarray(local_data["values"]),
            jnp.asarray(local_data["dones"]),
            next_values[0],  # drop the T=1 axis of the time-major player output
        )
        local_data["returns"] = np.asarray(returns, dtype=np.float32)
        local_data["advantages"] = np.asarray(advantages, dtype=np.float32)

        # Sequence chunking + shape bucketing (see module docstring)
        padded, mask = chunk_sequences(local_data, cfg.algo.rollout_steps, cfg.env.num_envs, seq_len)
        S = mask.shape[1]
        S_pad = _bucket(S, quantum)
        if S_pad > S:
            padded = {
                k: np.concatenate([v, np.zeros((seq_len, S_pad - S, *v.shape[2:]), dtype=v.dtype)], axis=1)
                for k, v in padded.items()
            }
            mask = np.concatenate([mask, np.zeros((seq_len, S_pad - S), dtype=mask.dtype)], axis=1)
        padded["mask"] = mask
        # only the first row of the stored recurrent state restarts each
        # sequence — drop the rest before shipping to device
        padded["prev_hx"] = padded["prev_hx"][:1]
        padded["prev_cx"] = padded["prev_cx"][:1]
        seq_data = {k: jax.device_put(v, data_sharding) for k, v in padded.items()}

        s_local = S_pad // fabric.world_size
        if s_local not in train_fns:
            train_fns[s_local] = make_train_step(agent, tx, cfg, fabric.mesh, s_local)

        with timer("Time/train_time", SumMetric):
            rng, train_key = jax.random.split(rng)
            params, opt_state, pg_l, v_l, ent_l = train_fns[s_local](
                params, opt_state, seq_data, train_key,
                jnp.asarray(clip_coef, dtype=jnp.float32), jnp.asarray(ent_coef, dtype=jnp.float32),
            )
            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/policy_loss", pg_l)
                aggregator.update("Loss/value_loss", v_l)
                aggregator.update("Loss/entropy_loss", ent_l)
        train_step += 1

        if cfg.metric.log_level > 0:
            logger.log_dict(
                {"Info/learning_rate": lr, "Info/clip_coef": clip_coef, "Info/ent_coef": ent_coef}, policy_step
            )
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

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
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
