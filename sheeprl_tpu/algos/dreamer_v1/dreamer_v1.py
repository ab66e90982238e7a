"""Dreamer-V1 — coupled training (reference: ``sheeprl/algos/dreamer_v1/dreamer_v1.py``).

Same TPU-native skeleton as Dreamer-V2/V3 (dynamic learning and imagination
as two ``lax.scan``s inside one jitted shard_map G-step update), with the V1
training deltas (reference ``train()``, ``dreamer_v1.py:59-375``):

- continuous Gaussian latents; plain KL with free nats (no balancing);
- the imagined trajectory holds H *post-step* latents (no initial state row);
- V1's own lambda-return recursion (H inputs → H-1 outputs,
  ``utils.compute_lambda_values``);
- actor loss = dynamics backprop only: ``-mean(discount * lambda)``;
- single critic — no target network;
- epsilon exploration noise on the player (``actor.expl_amount = 0.3``).
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Optional, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.dreamer_v1.agent import PlayerDV1, WorldModel, actor_sample, build_agent
from sheeprl_tpu.algos.dreamer_v1.loss import actor_loss, critic_loss, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v1.utils import compute_lambda_values, prepare_obs, test
from sheeprl_tpu.algos.dreamer_v2.agent import Actor
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.ring import build_burst_train_step
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, conv_heavy_compile_options, resolve_hybrid_player, save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(
    world_model: WorldModel,
    actor: Actor,
    critic,
    cfg,
    mesh,
    actions_dim: Sequence[int],
    is_continuous: bool,
    txs: Dict[str, Any],
    ring: Optional[Dict[str, Any]] = None,
):
    """Build the fully-jitted G-step Dreamer-V1 update (see module docstring).

    With ``ring`` the returned function is the burst variant owning a
    device-resident sequence ring (see ``data/ring.py``; carry =
    ``(params, opts)``)."""
    rssm = world_model.rssm
    wm_cfg = cfg.algo.world_model
    cnn_enc = list(cfg.algo.cnn_keys.encoder)
    mlp_enc = list(cfg.algo.mlp_keys.encoder)
    cnn_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_dec = list(cfg.algo.mlp_keys.decoder)
    stochastic_size = int(wm_cfg.stochastic_size)
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    use_continues = bool(wm_cfg.use_continues)

    def dynamic_rollout(wmp, embedded, actions, key):
        """T-step representation rollout as one scan (reference Python loop:
        ``dreamer_v1.py:144-157``)."""
        T, B = actions.shape[:2]
        rec0 = jnp.zeros((B, recurrent_state_size), dtype=embedded.dtype)
        post0 = jnp.zeros((B, stochastic_size), dtype=embedded.dtype)

        def step(carry, xs):
            rec, post = carry
            emb_t, act_t, k = xs
            rec, post, post_ms, prior_ms = rssm.dynamic(wmp, post, rec, act_t, emb_t, k)
            return (rec, post), (rec, post, post_ms[0], post_ms[1], prior_ms[0], prior_ms[1])

        keys = jax.random.split(key, T)
        _, (recs, posts, post_means, post_stds, prior_means, prior_stds) = jax.lax.scan(
            step, (rec0, post0), (embedded, actions, keys)
        )
        return recs, posts, (post_means, post_stds), (prior_means, prior_stds)

    def gradient_step(carry, xs):
        params, opts = carry
        batch, key = xs  # batch: (T, B_local, ...)
        k_dyn, k_img = jax.random.split(key)

        batch_obs = {k: batch[k] / 255.0 - 0.5 for k in cnn_enc}
        batch_obs.update({k: batch[k] for k in mlp_enc})
        batch_actions = batch["actions"]  # same row convention as V2

        # -- world-model update (reference: dreamer_v1.py:112-217)
        def wm_loss_fn(wmp):
            embedded = world_model.encoder.apply(wmp["encoder"], batch_obs)
            recs, posts, post_ms, prior_ms = dynamic_rollout(wmp, embedded, batch_actions, k_dyn)
            latents = jnp.concatenate([posts, recs], axis=-1)
            recon = world_model.decode(wmp, latents)
            qo = {k: Independent(Normal(recon[k], 1.0), 3) for k in cnn_dec}
            qo.update({k: Independent(Normal(recon[k], 1.0), 1) for k in mlp_dec})
            qr = Independent(Normal(world_model.reward_model.apply(wmp["reward_model"], latents), 1.0), 1)
            if use_continues:
                qc = Independent(
                    BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], latents)), 1
                )
                continue_targets = (1 - batch["terminated"]) * gamma
            else:
                qc = continue_targets = None
            posteriors_dist = Independent(Normal(post_ms[0], post_ms[1]), 1)
            priors_dist = Independent(Normal(prior_ms[0], prior_ms[1]), 1)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                qo,
                batch_obs,
                qr,
                batch["rewards"],
                posteriors_dist,
                priors_dist,
                float(wm_cfg.kl_free_nats),
                float(wm_cfg.kl_regularizer),
                qc,
                continue_targets,
                float(wm_cfg.continue_scale_factor),
            )
            post_ent = posteriors_dist.entropy().mean()
            prior_ent = priors_dist.entropy().mean()
            aux = (recs, posts, kl, state_loss, reward_loss, observation_loss, continue_loss, post_ent, prior_ent)
            return rec_loss, aux

        (rec_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["world_model"])
        recs, posts, kl, state_loss, reward_loss, observation_loss, continue_loss, post_ent, prior_ent = wm_aux
        wm_grads = pmean_grads(wm_grads, "dp")
        wupd, opts["world"] = txs["world"].update(wm_grads, opts["world"], params["world_model"])
        params = {**params, "world_model": optax.apply_updates(params["world_model"], wupd)}

        # -- behaviour learning (reference: dreamer_v1.py:219-330)
        wmp = params["world_model"]
        T, B = batch_actions.shape[:2]
        prior0 = jax.lax.stop_gradient(posts).reshape(T * B, stochastic_size)
        rec0 = jax.lax.stop_gradient(recs).reshape(T * B, recurrent_state_size)

        def actor_loss_fn(ap):
            def img_step(carry, k):
                prior, rec = carry
                k_act, k_prior = jax.random.split(k)
                latent = jnp.concatenate([prior, rec], axis=-1)
                act = jnp.concatenate(
                    actor_sample(actor, ap, jax.lax.stop_gradient(latent), k_act)[0], axis=-1
                )
                prior, rec = rssm.imagination(wmp, prior, rec, act, k_prior)
                new_latent = jnp.concatenate([prior, rec], axis=-1)
                return (prior, rec), new_latent

            _, traj = jax.lax.scan(img_step, (prior0, rec0), jax.random.split(k_img, horizon))
            # traj: (H, TB, L) — V1 keeps only post-step latents

            values = critic.apply(params["critic"], traj)
            rewards = world_model.reward_model.apply(wmp["reward_model"], traj)
            if use_continues:
                continues = jax.nn.sigmoid(world_model.continue_model.apply(wmp["continue_model"], traj))
            else:
                continues = jnp.ones_like(rewards) * gamma

            lambda_values = compute_lambda_values(rewards, values, continues, values[-1], lmbda=lmbda)
            discount = jax.lax.stop_gradient(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], axis=0), axis=0)
            )
            policy_loss = actor_loss(discount * lambda_values)
            aux = (jax.lax.stop_gradient(traj), jax.lax.stop_gradient(lambda_values), discount)
            return policy_loss, aux

        (policy_loss, (traj_sg, lambda_sg, discount)), actor_grads = jax.value_and_grad(
            actor_loss_fn, has_aux=True
        )(params["actor"])
        actor_grads = pmean_grads(actor_grads, "dp")
        aupd, opts["actor"] = txs["actor"].update(actor_grads, opts["actor"], params["actor"])
        params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        # -- critic update (reference: dreamer_v1.py:328-351)
        def critic_loss_fn(cp):
            qv = Independent(Normal(critic.apply(cp, traj_sg)[:-1], 1.0), 1)
            return critic_loss(qv, lambda_sg, discount[..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        critic_grads = pmean_grads(critic_grads, "dp")
        cupd, opts["critic"] = txs["critic"].update(critic_grads, opts["critic"], params["critic"])
        params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}

        metrics = (
            rec_loss, observation_loss, reward_loss, state_loss, continue_loss,
            kl, post_ent, prior_ent, policy_loss, value_loss,
        )
        return (params, opts), metrics

    if ring is not None:
        return build_burst_train_step(
            gradient_step, mesh, ring, compiler_options=conv_heavy_compile_options(mesh)
        )

    def local_train(params, opts, data, key):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(data)[0].shape[0]
        keys = jax.random.split(key, n_steps)
        (params, opts), metrics = jax.lax.scan(gradient_step, (params, opts), (data, keys))
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), metrics)
        return params, opts, metrics

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(None, None, "dp"), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_train, donate_argnums=(0, 1), compiler_options=conv_heavy_compile_options(mesh))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.fault import load_resume_state

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference: dreamer_v1.py:389-391)
    cfg.env.screen_size = 64
    cfg.env.frame_stack = 1

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")


    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
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
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
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

    world_model, actor, critic, params, player = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if state is not None else None,
        state["actor"] if state is not None else None,
        state["critic"] if state is not None else None,
    )

    txs = {
        "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
        "actor": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
    }
    opts = {
        "world": txs["world"].init(params["world_model"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
    }
    if state is not None:
        opts = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opts, state["optimizers"])
    opts = fabric.put_replicated(opts)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Local data (reference: dreamer_v1.py:486-494)
    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 4
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
        obs_keys=tuple(obs_keys),
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    if state is not None and cfg.buffer.checkpoint:
        if isinstance(state["rb"], list):
            rb = state["rb"][0]
        elif isinstance(state["rb"], EnvIndependentReplayBuffer):
            rb = state["rb"]
        else:
            raise RuntimeError(f"Cannot restore the replay buffer from {type(state['rb'])}")

    train_step = 0
    last_train = 0
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
    seq_len = int(cfg.algo.per_rank_sequence_length)
    if batch_size % fabric.world_size != 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) must be divisible by the number of devices ({fabric.world_size})"
        )
    data_sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))

    rng = jax.random.PRNGKey(cfg.seed)
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder

    # TPU-native overlap (same design as Dreamer-V3/SAC `hybrid_player`):
    # host-CPU policy from a packed bf16 snapshot, device-resident uint8
    # sequence ring, Ratio grants dispatched in bursts on a trainer thread.
    hp_cfg = cfg.algo.get("hybrid_player") or {}
    burst_mode = resolve_hybrid_player(hp_cfg, fabric.mesh)
    host_mirror = (not burst_mode) or bool(cfg.buffer.checkpoint)

    if burst_mode:
        from sheeprl_tpu.utils.burst import DREAMER_METRIC_NAMES, HybridPlayerHarness

        # V1 training reads obs/actions/rewards/terminated only — no is_first
        # (zero-latent resets ride the zero-action reset rows).
        def _player_subset(p):
            wm = p["world_model"]
            return {
                "world_model": {
                    "encoder": wm["encoder"],
                    "recurrent_model": wm["recurrent_model"],
                    "representation_model": wm["representation_model"],
                },
                "actor": p["actor"],
            }

        hp = HybridPlayerHarness(
            fabric, cfg,
            observation_space=observation_space, cnn_keys=cnn_keys, mlp_keys=mlp_keys,
            actions_dim=actions_dim, capacity=buffer_size, seq_len=seq_len, batch_size=batch_size,
            policy_steps_per_iter=policy_steps_per_iter,
            make_burst_fn=lambda ring: make_train_step(
                world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs, ring=ring
            ),
            player_subset=_player_subset,
            carry=(params, opts),
            rb=rb if (state is not None and cfg.buffer.checkpoint) else None,
            with_is_first=False, metric_names=DREAMER_METRIC_NAMES, aggregator=aggregator,
        )
        host_player = PlayerDV1(
            world_model,
            actor,
            actions_dim,
            cfg.env.num_envs,
            int(cfg.algo.world_model.stochastic_size),
            int(cfg.algo.world_model.recurrent_model.recurrent_state_size),
            expl_amount=player.expl_amount,
            actor_type=player.actor_type,
            host_device=hp.host_device,
        )
        hp.extra_metrics["Params/exploration_amount"] = lambda: host_player.expl_amount
    else:
        train_fn = make_train_step(world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs)

    # First observation: row 0 = {o0, zero action/reward} (reference: dreamer_v1.py:530-551)
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["actions"] = np.zeros((1, cfg.env.num_envs, int(np.sum(actions_dim))), dtype=np.float32)
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    if host_mirror:
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
    if burst_mode:
        hp.stage_step(step_data)
        host_player.init_states(hp.host_params)
    else:
        player.init_states(params)

    cumulative_per_rank_gradient_steps = 0
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter

        if burst_mode:
            hp.poll()

        with timer("Time/env_interaction_time", SumMetric):
            if iter_num <= learning_starts and state is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    acts2d = actions.reshape(cfg.env.num_envs, len(actions_dim))
                    actions = np.concatenate(
                        [np.eye(d, dtype=np.float32)[acts2d[:, i]] for i, d in enumerate(actions_dim)],
                        axis=-1,
                    )
            else:
                jobs = prepare_obs(fabric, obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                if burst_mode:
                    # Host-CPU policy on the snapshot params: numpy obs +
                    # CPU-committed params keep the whole step off the wire.
                    action_list = host_player.get_actions(hp.host_params, jobs, hp.host_key())
                else:
                    rng, subkey = jax.random.split(rng)
                    action_list = player.get_actions(params, jobs, subkey)
                actions = np.asarray(jnp.concatenate(action_list, axis=-1))
                if is_continuous:
                    real_actions = actions
                else:
                    real_actions = np.stack([np.asarray(a).argmax(axis=-1) for a in action_list], axis=-1)

            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

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

        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k in obs_keys:
                        real_next_obs[k][idx] = np.asarray(final_obs[k])

        for k in obs_keys:
            step_data[k] = real_next_obs[k][np.newaxis]
        obs = next_obs

        step_data["terminated"] = np.asarray(terminated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(
            np.asarray(rewards, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        )
        if host_mirror:
            rb.add(step_data, validate_args=cfg.buffer.validate_args)
        if burst_mode:
            hp.stage_step(step_data)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (np.asarray(next_obs[k])[dones_idxes])[np.newaxis]
            reset_data["terminated"] = np.zeros((1, reset_envs, 1), dtype=np.float32)
            reset_data["truncated"] = np.zeros((1, reset_envs, 1), dtype=np.float32)
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))), dtype=np.float32)
            reset_data["rewards"] = np.zeros((1, reset_envs, 1), dtype=np.float32)
            if host_mirror:
                rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
            if burst_mode:
                hp.stage_reset(reset_data, dones_idxes)
            for d in dones_idxes:
                step_data["terminated"][0, d] = np.zeros_like(step_data["terminated"][0, d])
                step_data["truncated"][0, d] = np.zeros_like(step_data["truncated"][0, d])
            if burst_mode:
                host_player.init_states(hp.host_params, dones_idxes)
            else:
                player.init_states(params, dones_idxes)

        if burst_mode:
            if iter_num >= learning_starts:
                hp.grant(ratio(policy_step - prefill_steps * policy_steps_per_iter))
            hp.pump()
            cumulative_per_rank_gradient_steps, train_step = hp.gradient_steps, hp.train_steps
        elif iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step - prefill_steps * policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                sample = rb.sample(
                    batch_size,
                    sequence_length=seq_len,
                    n_samples=per_rank_gradient_steps,
                )  # (G, T, B, ...)
                data = {
                    k: jax.device_put(np.asarray(v, dtype=np.float32), data_sharding) for k, v in sample.items()
                }
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    params, opts, metrics = train_fn(params, opts, data, train_key)
                    if aggregator and not aggregator.disabled:
                        names = (
                            "Loss/world_model_loss", "Loss/observation_loss", "Loss/reward_loss",
                            "Loss/state_loss", "Loss/continue_loss", "State/kl", "State/post_entropy",
                            "State/prior_entropy", "Loss/policy_loss", "Loss/value_loss",
                        )
                        for name, value in zip(names, metrics):
                            if name in aggregator:
                                aggregator.update(name, value)
                        if "Params/exploration_amount" in aggregator:
                            aggregator.update("Params/exploration_amount", player.expl_amount)
                cumulative_per_rank_gradient_steps += per_rank_gradient_steps
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
            if burst_mode:
                # Latest trainer-thread handles (at most one burst stale).
                params, opts = hp.carry
            ckpt_state = {
                "world_model": params["world_model"],
                "actor": params["actor"],
                "critic": params["critic"],
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

    if burst_mode:
        # Flush the tail: Ratio already counted the remaining grants; grants
        # that can never execute (data still shorter than a window) are
        # abandoned with the run.
        params, opts = hp.finish()

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(
            fabric,
            log_models,
            cfg,
            {"world_model": params["world_model"], "actor": params["actor"], "critic": params["critic"]},
        )
    logger.close()
