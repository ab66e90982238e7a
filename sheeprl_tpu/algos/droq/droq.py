"""DroQ — coupled training (reference: ``sheeprl/algos/droq/droq.py``).

Differences from SAC (reference train fn, ``droq.py:31-138``):

- high replay ratio (20) with Dropout+LayerNorm critics;
- per iteration: G granted critic minibatch updates with a target-EMA after
  EVERY update, then ONE actor + alpha update on a separately sampled batch;
- the actor regresses the ensemble *mean* Q, not the min.

Structure mirrors the TPU SAC: the whole G-step critic scan + the single
actor/alpha update runs as one jitted ``shard_map`` over the ``dp`` mesh.
The reference updates each critic of the ensemble with its own MSE/optimizer
step and per-critic EMA (``droq.py:99-118``); with elementwise Adam the summed
ensemble loss produces identical per-critic updates, so here it is one vmapped
ensemble update per minibatch."""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.droq.agent import DROQAgent, build_agent
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(agent: DROQAgent, actor_tx, critic_tx, alpha_tx, cfg, mesh):
    gamma = float(cfg.algo.gamma)
    target_entropy = agent.target_entropy
    one = jnp.float32(1.0)

    def critic_step(carry, xs):
        params, copt = carry
        batch, key = xs
        k_target, k_online = jax.random.split(key)

        td_target = agent.next_target_q_droq(
            params, batch["next_observations"], batch["rewards"], batch["terminated"], gamma, k_target
        )
        td_target = jax.lax.stop_gradient(td_target)

        def c_loss(cp):
            q = agent.q_values_droq(cp, batch["observations"], batch["actions"], k_online)
            return critic_loss(q, td_target, agent.critic.n)

        qf_loss, cgrads = jax.value_and_grad(c_loss)(params["critic"])
        cgrads = pmean_grads(cgrads, "dp")
        cupd, copt = critic_tx.update(cgrads, copt, params["critic"])
        params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}
        # EMA after every critic update (reference: droq.py:116-118)
        params = {**params, "target_critic": agent.ema(params["critic"], params["target_critic"], one)}
        return (params, copt), qf_loss

    def local_train(params, aopt, copt, lopt, critic_data, actor_data, key):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(critic_data)[0].shape[0]
        k_scan, k_actor, k_q = jax.random.split(key, 3)
        (params, copt), qf_losses = jax.lax.scan(
            critic_step, (params, copt), (critic_data, jax.random.split(k_scan, n_steps))
        )

        # Single actor + alpha update on a separate batch (reference: droq.py:119-138)
        alpha = jax.lax.stop_gradient(jnp.exp(params["log_alpha"]))
        obs = actor_data["observations"]

        def a_loss(ap):
            actions, logp = agent.sample_action(ap, obs, k_actor)
            q = agent.q_values_droq(params["critic"], obs, actions, k_q)
            mean_q = jnp.mean(q, axis=-1, keepdims=True)
            return policy_loss(alpha, logp, mean_q), logp

        (actor_loss, logp), agrads = jax.value_and_grad(a_loss, has_aux=True)(params["actor"])
        agrads = pmean_grads(agrads, "dp")
        aupd, aopt = actor_tx.update(agrads, aopt, params["actor"])
        params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        def l_loss(la):
            return entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)

        alpha_loss, lgrads = jax.value_and_grad(l_loss)(params["log_alpha"])
        lgrads = pmean_grads(lgrads, "dp")
        lupd, lopt = alpha_tx.update(lgrads, lopt, params["log_alpha"])
        params = {**params, "log_alpha": optax.apply_updates(params["log_alpha"], lupd)}

        qf = jax.lax.pmean(qf_losses.mean(), "dp")
        al = jax.lax.pmean(actor_loss, "dp")
        ll = jax.lax.pmean(alpha_loss, "dp")
        return params, aopt, copt, lopt, qf, al, ll

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(None, "dp"), P("dp"), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_train, donate_argnums=(0, 1, 2, 3))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.fault import load_resume_state

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("DroQ algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the DroQ agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the DroQ agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}."
            )
    if cfg.metric.log_level > 0:
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)

    agent, params, player = build_agent(
        fabric, cfg, observation_space, action_space, state["agent"] if state is not None else None
    )

    critic_tx = build_optimizer(cfg.algo.critic.optimizer)
    actor_tx = build_optimizer(cfg.algo.actor.optimizer)
    alpha_tx = build_optimizer(cfg.algo.alpha.optimizer)
    copt = critic_tx.init(params["critic"])
    aopt = actor_tx.init(params["actor"])
    lopt = alpha_tx.init(params["log_alpha"])
    if state is not None:
        aopt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, aopt, state["actor_optimizer"])
        copt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, copt, state["qf_optimizer"])
        lopt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, lopt, state["alpha_optimizer"])
    aopt, copt, lopt = (fabric.put_replicated(o) for o in (aopt, copt, lopt))

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=("observations",),
    )
    if state is not None and cfg.buffer.checkpoint:
        if isinstance(state["rb"], list):
            rb = state["rb"][0]
        elif isinstance(state["rb"], ReplayBuffer):
            rb = state["rb"]
        else:
            raise RuntimeError(f"Cannot restore the replay buffer from {type(state['rb'])}")

    last_train = 0
    train_step = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_step = state["iter_num"] * cfg.env.num_envs if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"]
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state is not None:
        ratio.load_state_dict(state["ratio"])

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
    train_fn = make_train_step(agent, actor_tx, critic_tx, alpha_tx, cfg, fabric.mesh)
    critic_sharding = NamedSharding(fabric.mesh, P(None, "dp"))
    actor_sharding = NamedSharding(fabric.mesh, P("dp"))

    rng = jax.random.PRNGKey(cfg.seed)
    mlp_keys = cfg.algo.mlp_keys.encoder

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]

    cumulative_per_rank_gradient_steps = 0
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter

        with timer("Time/env_interaction_time", SumMetric):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            else:
                jobs = prepare_obs(fabric, obs, mlp_keys=mlp_keys, num_envs=cfg.env.num_envs)
                rng, subkey = jax.random.split(rng)
                actions = np.asarray(player(params, jobs, subkey))
            next_obs, rewards, terminated, truncated, infos = envs.step(actions.reshape(envs.action_space.shape))
            rewards = np.asarray(rewards, dtype=np.float32).reshape(cfg.env.num_envs, -1)

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

        step_data["terminated"] = np.asarray(terminated, dtype=np.uint8).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.uint8).reshape(1, cfg.env.num_envs, -1)
        step_data["actions"] = np.asarray(actions, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["observations"] = np.concatenate(
            [np.asarray(obs[k], dtype=np.float32) for k in mlp_keys], axis=-1
        ).reshape(1, cfg.env.num_envs, -1)
        if not cfg.buffer.sample_next_obs:
            real_next_obs = copy.deepcopy(next_obs)
            if "final_obs" in infos:
                for idx, final_obs in enumerate(infos["final_obs"]):
                    if final_obs is not None:
                        for k, v in final_obs.items():
                            real_next_obs[k][idx] = v
            step_data["next_observations"] = np.concatenate(
                [np.asarray(real_next_obs[k], dtype=np.float32) for k in mlp_keys], axis=-1
            ).reshape(1, cfg.env.num_envs, -1)
        step_data["rewards"] = rewards[np.newaxis]
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        if iter_num >= learning_starts:
            # NOTE: unlike SAC, the reference DroQ converts prefill iterations
            # to policy steps here (droq.py:350)
            per_rank_gradient_steps = ratio(policy_step - prefill_steps * policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                critic_sample = rb.sample(
                    batch_size=batch_size,
                    n_samples=per_rank_gradient_steps,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )  # (G, B, ...)
                actor_sample = rb.sample(
                    batch_size=batch_size,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )  # (1, B, ...)
                critic_data = {
                    k: jax.device_put(np.asarray(v, dtype=np.float32), critic_sharding)
                    for k, v in critic_sample.items()
                }
                actor_data = {
                    k: jax.device_put(np.asarray(v[0], dtype=np.float32), actor_sharding)
                    for k, v in actor_sample.items()
                }
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    params, aopt, copt, lopt, qf_l, a_l, al_l = train_fn(
                        params, aopt, copt, lopt, critic_data, actor_data, train_key
                    )
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Loss/value_loss", qf_l)
                        aggregator.update("Loss/policy_loss", a_l)
                        aggregator.update("Loss/alpha_loss", al_l)
                cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                train_step += 1

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
            if aggregator and not aggregator.disabled:
                logger.log_dict(aggregator.compute(), policy_step)
                aggregator.reset()
            if policy_step > 0:
                logger.log_dict(
                    {"Params/replay_ratio": cumulative_per_rank_gradient_steps / policy_step}, policy_step
                )
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
                "qf_optimizer": copt,
                "actor_optimizer": aopt,
                "alpha_optimizer": lopt,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(fabric, log_models, cfg, {"agent": params})
    logger.close()
