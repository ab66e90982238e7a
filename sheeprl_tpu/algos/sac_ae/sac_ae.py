"""SAC-AE — coupled training (reference: ``sheeprl/algos/sac_ae/sac_ae.py``).

Per granted gradient step (reference train fn, ``sac_ae.py:35-117``):

1. critic update (encoder + Q ensemble) against the TD target from the target
   encoder/Qs;
2. target EMA (separate taus for Qs and encoder) every
   ``critic.per_rank_target_network_update_freq`` cumulative steps;
3. actor + alpha update every ``actor.per_rank_update_freq`` steps, with
   gradient-stopped trunk features (detached-encoder actor);
4. decoder reconstruction update (encoder + decoder optimizers) every
   ``decoder.per_rank_update_freq`` steps, pixel targets bit-reduced to 5 bits.

All G steps run as one jitted ``shard_map`` + ``lax.scan``; the cumulative
gradient-step counter rides the scan carry so the update-frequency gates are
evaluated in-graph (``lax.cond``). Encoder params deliberately live in BOTH
the critic and the encoder optimizer states (the reference registers them in
two Adams, ``sac_ae.py:206-232``)."""

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

from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac_ae.agent import SACAEAgent, build_agent
from sheeprl_tpu.algos.sac_ae.utils import prepare_obs, preprocess_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(agent: SACAEAgent, txs: Dict[str, Any], cfg, mesh):
    gamma = float(cfg.algo.gamma)
    target_entropy = agent.target_entropy
    cnn_enc = list(cfg.algo.cnn_keys.encoder)
    mlp_enc = list(cfg.algo.mlp_keys.encoder)
    cnn_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_dec = list(cfg.algo.mlp_keys.decoder)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    actor_freq = int(cfg.algo.actor.per_rank_update_freq)
    decoder_freq = int(cfg.algo.decoder.per_rank_update_freq)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)

    def normalize(batch, prefix=""):
        obs = {}
        for k in cnn_enc + mlp_enc:
            v = batch[prefix + k]
            obs[k] = v / 255.0 if k in cnn_enc else v
        return obs

    def gradient_step(carry, xs):
        params, opts, cum = carry
        batch, key = xs
        k_next, k_actor, k_noise = jax.random.split(key, 3)
        obs = normalize(batch)
        next_obs = normalize(batch, prefix="next_")

        # 1. critic (encoder + qfs) update
        td_target = agent.next_target_q(params, next_obs, batch["rewards"], batch["terminated"], gamma, k_next)
        td_target = jax.lax.stop_gradient(td_target)

        def c_loss(cp):
            q = agent.q_values({**params, **cp}, obs, batch["actions"])
            return critic_loss(q, td_target, agent.qfs.n)

        critic_params = {"encoder": params["encoder"], "qfs": params["qfs"]}
        qf_loss, cgrads = jax.value_and_grad(c_loss)(critic_params)
        cgrads = pmean_grads(cgrads, "dp")
        cupd, opts["qf"] = txs["qf"].update(cgrads, opts["qf"], critic_params)
        params = {**params, **optax.apply_updates(critic_params, cupd)}

        # 2. target EMA (reference: sac_ae.py:74-77)
        ema_flag = (cum % target_freq == 0).astype(jnp.float32)
        params = agent.ema(params, ema_flag)

        # 3. actor + alpha update (reference: sac_ae.py:79-100)
        def actor_update(operand):
            params, aopt, lopt = operand
            alpha = jax.lax.stop_gradient(jnp.exp(params["log_alpha"]))

            def a_loss(ap):
                actions, logp = agent.sample_action({**params, **ap}, obs, k_actor)
                q = agent.q_values(params, obs, actions)
                min_q = jnp.min(q, axis=-1, keepdims=True)
                return policy_loss(alpha, logp, min_q), logp

            actor_params = {"actor": params["actor"], "actor_enc_head": params["actor_enc_head"]}
            (actor_loss, logp), agrads = jax.value_and_grad(a_loss, has_aux=True)(actor_params)
            agrads = pmean_grads(agrads, "dp")
            aupd, aopt = txs["actor"].update(agrads, aopt, actor_params)
            params = {**params, **optax.apply_updates(actor_params, aupd)}

            def l_loss(la):
                return entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)

            alpha_loss, lgrads = jax.value_and_grad(l_loss)(params["log_alpha"])
            lgrads = pmean_grads(lgrads, "dp")
            lupd, lopt = txs["alpha"].update(lgrads, lopt, params["log_alpha"])
            params = {**params, "log_alpha": optax.apply_updates(params["log_alpha"], lupd)}
            return (params, aopt, lopt), actor_loss, alpha_loss

        def actor_skip(operand):
            params, aopt, lopt = operand
            return (params, aopt, lopt), jnp.float32(0.0), jnp.float32(0.0)

        (params, opts["actor"], opts["alpha"]), actor_loss, alpha_loss = jax.lax.cond(
            cum % actor_freq == 0, actor_update, actor_skip, (params, opts["actor"], opts["alpha"])
        )

        # 4. decoder reconstruction (reference: sac_ae.py:100-117)
        def decoder_update(operand):
            params, eopt, dopt = operand

            def r_loss(ed):
                hidden = agent.critic_features(ed["encoder"], obs)
                recon = agent.decoder.apply(ed["decoder"], hidden)
                l2 = (0.5 * jnp.sum(hidden**2, axis=1)).mean()
                loss = jnp.float32(0.0)
                for k in cnn_dec + mlp_dec:
                    if k in cnn_dec:
                        target = preprocess_obs(batch[k], bits=5, key=k_noise)
                    else:
                        target = batch[k]
                    loss = loss + jnp.mean((target - recon[k]) ** 2) + l2_lambda * l2
                return loss

            ed_params = {"encoder": params["encoder"], "decoder": params["decoder"]}
            rec_loss, grads = jax.value_and_grad(r_loss)(ed_params)
            grads = pmean_grads(grads, "dp")
            eupd, eopt = txs["encoder"].update({"e": grads["encoder"]}, eopt, {"e": ed_params["encoder"]})
            dupd, dopt = txs["decoder"].update({"d": grads["decoder"]}, dopt, {"d": ed_params["decoder"]})
            params = {
                **params,
                "encoder": optax.apply_updates({"e": ed_params["encoder"]}, eupd)["e"],
                "decoder": optax.apply_updates({"d": ed_params["decoder"]}, dupd)["d"],
            }
            return (params, eopt, dopt), rec_loss

        def decoder_skip(operand):
            params, eopt, dopt = operand
            return (params, eopt, dopt), jnp.float32(0.0)

        (params, opts["encoder"], opts["decoder"]), rec_loss = jax.lax.cond(
            cum % decoder_freq == 0, decoder_update, decoder_skip, (params, opts["encoder"], opts["decoder"])
        )

        return (params, opts, cum + 1), (qf_loss, actor_loss, alpha_loss, rec_loss)

    def local_train(params, opts, data, key, cum0):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(data)[0].shape[0]
        keys = jax.random.split(key, n_steps)
        (params, opts, cum), losses = jax.lax.scan(gradient_step, (params, opts, cum0), (data, keys))
        qf, al, ll, rl = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, opts, qf, al, ll, rl

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(None, "dp"), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
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

    # These arguments cannot be changed (reference: sac_ae.py:137)
    cfg.env.screen_size = 64

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if not isinstance(action_space, gym.spaces.Box):
        raise RuntimeError(f"Unexpected action space, should be continuous, got: {action_space}")
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjoint")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError("The CNN keys of the decoder must be contained in the encoder ones")
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError("The MLP keys of the decoder must be contained in the encoder ones")
    if cfg.metric.log_level > 0:
        print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
        print("Decoder CNN keys:", cfg.algo.cnn_keys.decoder)
        print("Decoder MLP keys:", cfg.algo.mlp_keys.decoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    agent, params, player = build_agent(
        fabric, cfg, observation_space, action_space, state["agent"] if state is not None else None
    )

    txs = {
        "qf": build_optimizer(cfg.algo.critic.optimizer),
        "actor": build_optimizer(cfg.algo.actor.optimizer),
        "alpha": build_optimizer(cfg.algo.alpha.optimizer),
        "encoder": build_optimizer(cfg.algo.encoder.optimizer),
        "decoder": build_optimizer(cfg.algo.decoder.optimizer),
    }
    opts = {
        "qf": txs["qf"].init({"encoder": params["encoder"], "qfs": params["qfs"]}),
        "actor": txs["actor"].init({"actor": params["actor"], "actor_enc_head": params["actor_enc_head"]}),
        "alpha": txs["alpha"].init(params["log_alpha"]),
        "encoder": txs["encoder"].init({"e": params["encoder"]}),
        "decoder": txs["decoder"].init({"d": params["decoder"]}),
    }
    if state is not None:
        opts = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opts, state["optimizers"])
    opts = fabric.put_replicated(opts)

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
        obs_keys=tuple(obs_keys),
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
    train_fn = make_train_step(agent, txs, cfg, fabric.mesh)
    data_sharding = NamedSharding(fabric.mesh, P(None, "dp"))

    rng = jax.random.PRNGKey(cfg.seed)
    cnn_keys = cfg.algo.cnn_keys.encoder
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
                jobs = prepare_obs(fabric, obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=cfg.env.num_envs)
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

        # Save the real next observation (reference: sac_ae.py:348-355)
        real_next_obs = copy.deepcopy(next_obs)
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k, v in final_obs.items():
                        real_next_obs[k][idx] = v

        for k in obs_keys:
            step_data[k] = np.asarray(obs[k])[np.newaxis]
            if not cfg.buffer.sample_next_obs:
                step_data[f"next_{k}"] = np.asarray(real_next_obs[k])[np.newaxis]
        step_data["terminated"] = np.asarray(terminated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["actions"] = np.asarray(actions, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["rewards"] = rewards[np.newaxis]
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        if iter_num >= learning_starts:
            # NOTE: unlike SAC, the reference SAC-AE converts prefill iterations
            # to policy steps here (sac_ae.py:378)
            per_rank_gradient_steps = ratio(policy_step - prefill_steps * policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                sample = rb.sample(
                    batch_size=batch_size,
                    n_samples=per_rank_gradient_steps,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )  # (G, B, ...)
                data = {
                    k: jax.device_put(np.asarray(v, dtype=np.float32), data_sharding) for k, v in sample.items()
                }
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    params, opts, qf_l, a_l, al_l, rec_l = train_fn(
                        params, opts, data, train_key, jnp.int32(cumulative_per_rank_gradient_steps)
                    )
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Loss/value_loss", qf_l)
                        aggregator.update("Loss/policy_loss", a_l)
                        aggregator.update("Loss/alpha_loss", al_l)
                        aggregator.update("Loss/reconstruction_loss", rec_l)
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
                "optimizers": opts,
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

        register_model(
            fabric,
            log_models,
            cfg,
            {"agent": params, "encoder": params["encoder"], "decoder": params["decoder"]},
        )
    logger.close()
