"""Plan2Explore on Dreamer-V2 — exploration phase
(reference: ``sheeprl/algos/p2e_dv2/p2e_dv2_exploration.py``).

The Dreamer-V2 jitted G-step update extended with the P2E phases: after the
world-model update (reward/continue heads on STOP-GRADIENT latents), the
vmapped ensemble regresses the next stochastic state; the exploration actor
maximizes the ensemble-disagreement intrinsic reward through V2-style
imagination with a dedicated (hard-updated) target critic; the task
actor/critic run the standard V2 zero-shot update.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.dreamer_v2.agent import PlayerDV2, actor_dists, actor_sample
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.p2e_dv2.agent import build_agent, ensembles_apply
from sheeprl_tpu.algos.p2e_dv2.utils import compute_lambda_values, prepare_obs, test
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, EpisodeBuffer, SequentialReplayBuffer
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal, OneHotCategorical
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, conv_heavy_compile_options, resolve_hybrid_player, save_configs

__all__ = ["main", "make_train_step"]


def make_train_step(world_model, ens_module, actor, critic, cfg, mesh, actions_dim, is_continuous, txs, ring=None):
    rssm = world_model.rssm
    wm_cfg = cfg.algo.world_model
    cnn_enc = list(cfg.algo.cnn_keys.encoder)
    mlp_enc = list(cfg.algo.mlp_keys.encoder)
    cnn_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_dec = list(cfg.algo.mlp_keys.decoder)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    objective_mix = float(cfg.algo.actor.objective_mix)
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    use_continues = bool(wm_cfg.use_continues)
    target_update_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    split_sizes = np.cumsum(np.asarray(actions_dim[:-1], dtype=np.int64)).tolist()

    def dynamic_rollout(wmp, embedded, actions, is_first, key):
        T, B = actions.shape[:2]
        rec0 = jnp.zeros((B, recurrent_state_size), dtype=embedded.dtype)
        post0 = jnp.zeros((B, stoch_state_size), dtype=embedded.dtype)

        def step(carry, xs):
            rec, post = carry
            emb_t, act_t, first_t, k = xs
            rec, post, post_logits, prior_logits = rssm.dynamic(wmp, post, rec, act_t, emb_t, first_t, k)
            return (rec, post), (rec, post, post_logits, prior_logits)

        keys = jax.random.split(key, T)
        _, outs = jax.lax.scan(step, (rec0, post0), (embedded, actions, is_first, keys))
        return outs

    def imagine(wmp, actor_params, prior0, rec0, key):
        """V2-style imagination: action slot 0 is the zero action."""
        latent0 = jnp.concatenate([prior0, rec0], axis=-1)

        def img_step(carry, k):
            prior, rec = carry
            k_act, k_prior = jax.random.split(k)
            latent = jnp.concatenate([prior, rec], axis=-1)
            act = jnp.concatenate(actor_sample(actor, actor_params, jax.lax.stop_gradient(latent), k_act)[0], axis=-1)
            prior, rec = rssm.imagination(wmp, prior, rec, act, k_prior)
            new_latent = jnp.concatenate([prior, rec], axis=-1)
            return (prior, rec), (new_latent, act)

        _, (latents, acts) = jax.lax.scan(img_step, (prior0, rec0), jax.random.split(key, horizon))
        traj = jnp.concatenate([latent0[None], latents], axis=0)
        imagined_actions = jnp.concatenate([jnp.zeros_like(acts[:1]), acts], axis=0)
        return traj, imagined_actions

    def v2_policy_loss(ap, traj, imagined_actions, lambda_values, baseline, discount):
        policies = actor_dists(actor, actor.apply(ap, jax.lax.stop_gradient(traj[:-2])))
        dynamics = lambda_values[1:]
        advantage = jax.lax.stop_gradient(lambda_values[1:] - baseline[:-2])
        if is_continuous:
            logprob = policies[0].log_prob(jax.lax.stop_gradient(imagined_actions[1:-1]))[..., None]
        else:
            act_parts = (
                jnp.split(imagined_actions, split_sizes, axis=-1) if len(actions_dim) > 1 else [imagined_actions]
            )
            logprob = jnp.stack(
                [p.log_prob(jax.lax.stop_gradient(a[1:-1]))[..., None] for p, a in zip(policies, act_parts)],
                axis=-1,
            ).sum(-1)
        objective = objective_mix * (logprob * advantage) + (1 - objective_mix) * dynamics
        try:
            entropy = ent_coef * jnp.stack([p.entropy() for p in policies], axis=-1).sum(-1)
        except NotImplementedError:
            entropy = jnp.zeros(objective.shape[:-1], dtype=objective.dtype)
        return -jnp.mean(discount[:-2] * (objective + entropy[..., None]))

    def gradient_step(carry, xs):
        params, opts, cum = carry
        batch, key = xs
        k_dyn, k_img_expl, k_img_task = jax.random.split(key, 3)
        metrics: Dict[str, jax.Array] = {}

        # hard target copies every freq steps (task + exploration)
        mix = jnp.where(cum % target_update_freq == 0, 1.0, 0.0)
        params = {
            **params,
            "target_critic_task": jax.tree.map(
                lambda c, t: mix * c + (1.0 - mix) * t, params["critic_task"], params["target_critic_task"]
            ),
            "target_critic_exploration": jax.tree.map(
                lambda c, t: mix * c + (1.0 - mix) * t,
                params["critic_exploration"],
                params["target_critic_exploration"],
            ),
        }

        batch_obs = {k: batch[k] / 255.0 - 0.5 for k in cnn_enc}
        batch_obs.update({k: batch[k] for k in mlp_enc})
        is_first = batch["is_first"].at[0].set(1.0)
        batch_actions = batch["actions"]

        def wm_loss_fn(wmp):
            embedded = world_model.encoder.apply(wmp["encoder"], batch_obs)
            recs, posts, post_logits, prior_logits = dynamic_rollout(wmp, embedded, batch_actions, is_first, k_dyn)
            latents = jnp.concatenate([posts, recs], axis=-1)
            recon = world_model.decode(wmp, latents)
            po = {k: Independent(Normal(recon[k], 1.0), 3) for k in cnn_dec}
            po.update({k: Independent(Normal(recon[k], 1.0), 1) for k in mlp_dec})
            latents_sg = jax.lax.stop_gradient(latents)
            pr = Independent(Normal(world_model.reward_model.apply(wmp["reward_model"], latents_sg), 1.0), 1)
            if use_continues:
                pc = Independent(
                    BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], latents_sg)), 1
                )
                continue_targets = (1 - batch["terminated"]) * gamma
            else:
                pc = continue_targets = None
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                batch_obs,
                pr,
                batch["rewards"],
                prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size),
                post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size),
                float(wm_cfg.kl_balancing_alpha),
                float(wm_cfg.kl_free_nats),
                bool(wm_cfg.kl_free_avg),
                float(wm_cfg.kl_regularizer),
                pc,
                continue_targets,
                float(wm_cfg.discount_scale_factor),
            )
            aux = (recs, posts, post_logits, prior_logits, kl, state_loss, reward_loss, observation_loss, continue_loss)
            return rec_loss, aux

        (rec_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["world_model"])
        recs, posts, post_logits, prior_logits, kl, state_loss, reward_loss, observation_loss, continue_loss = wm_aux
        wm_grads = pmean_grads(wm_grads, "dp")
        wupd, opts["world"] = txs["world"].update(wm_grads, opts["world"], params["world_model"])
        params = {**params, "world_model": optax.apply_updates(params["world_model"], wupd)}
        metrics.update(
            {
                "Loss/world_model_loss": rec_loss,
                "Loss/observation_loss": observation_loss,
                "Loss/reward_loss": reward_loss,
                "Loss/state_loss": state_loss,
                "Loss/continue_loss": continue_loss,
                "State/kl": kl,
            }
        )

        wmp = params["world_model"]
        T, B = batch_actions.shape[:2]
        posts_sg = jax.lax.stop_gradient(posts)
        recs_sg = jax.lax.stop_gradient(recs)
        latents_sg = jnp.concatenate([posts_sg, recs_sg], axis=-1)

        # ensembles: next stochastic state from (latent, action)
        ens_in = jnp.concatenate([latents_sg, batch_actions], axis=-1)

        def ens_loss_fn(ep):
            outs = ensembles_apply(ens_module, ep, ens_in)
            if outs.shape[1] > 1:
                pred, tgt = outs[:, :-1], posts_sg[None, 1:]
            else:  # degenerate T=1 (dry runs)
                pred, tgt = outs, posts_sg[None]
            per_member = -Independent(Normal(pred, 1.0), 1).log_prob(tgt).mean(axis=(1, 2))
            return per_member.sum()

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        ens_grads = pmean_grads(ens_grads, "dp")
        eupd, opts["ensembles"] = txs["ensembles"].update(ens_grads, opts["ensembles"], params["ensembles"])
        params = {**params, "ensembles": optax.apply_updates(params["ensembles"], eupd)}
        metrics["Loss/ensemble_loss"] = ens_loss

        prior0 = posts_sg.reshape(T * B, stoch_state_size)
        rec0 = recs_sg.reshape(T * B, recurrent_state_size)
        true_continue = (1 - batch["terminated"]).reshape(1, T * B, 1) * gamma

        # exploration behaviour (intrinsic reward, target-critic baseline)
        def expl_actor_loss_fn(ap):
            traj, imagined_actions = imagine(wmp, ap, prior0, rec0, k_img_expl)
            target_values = critic.apply(params["target_critic_exploration"], traj)
            ens_pred = ensembles_apply(
                ens_module,
                params["ensembles"],
                jax.lax.stop_gradient(jnp.concatenate([traj, imagined_actions], axis=-1)),
            )
            intrinsic_reward = ens_pred.var(axis=0).mean(-1, keepdims=True) * intrinsic_mult
            if use_continues:
                continues = jax.nn.sigmoid(world_model.continue_model.apply(wmp["continue_model"], traj))
                continues = jnp.concatenate([true_continue, continues[1:]], axis=0)
            else:
                continues = jnp.ones_like(intrinsic_reward) * gamma
            lambda_values = compute_lambda_values(
                intrinsic_reward[:-1], target_values[:-1], continues[:-1], bootstrap=target_values[-1:], lmbda=lmbda
            )
            discount = jax.lax.stop_gradient(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], axis=0), axis=0)
            )
            policy_loss = v2_policy_loss(ap, traj, imagined_actions, lambda_values, target_values, discount)
            aux = (
                jax.lax.stop_gradient(traj),
                jax.lax.stop_gradient(lambda_values),
                discount,
                jax.lax.stop_gradient(intrinsic_reward.mean()),
            )
            return policy_loss, aux

        (policy_loss_expl, (traj_sg, lambda_sg, discount, intr_mean)), a_grads = jax.value_and_grad(
            expl_actor_loss_fn, has_aux=True
        )(params["actor_exploration"])
        a_grads = pmean_grads(a_grads, "dp")
        aupd, opts["actor_exploration"] = txs["actor_exploration"].update(
            a_grads, opts["actor_exploration"], params["actor_exploration"]
        )
        params = {**params, "actor_exploration": optax.apply_updates(params["actor_exploration"], aupd)}
        metrics["Loss/policy_loss_exploration"] = policy_loss_expl
        metrics["Rewards/intrinsic"] = intr_mean

        def expl_critic_loss_fn(cp):
            qv = Independent(Normal(critic.apply(cp, traj_sg[:-1]), 1.0), 1)
            return -jnp.mean(discount[:-1, ..., 0] * qv.log_prob(lambda_sg))

        vloss_expl, c_grads = jax.value_and_grad(expl_critic_loss_fn)(params["critic_exploration"])
        c_grads = pmean_grads(c_grads, "dp")
        cupd, opts["critic_exploration"] = txs["critic_exploration"].update(
            c_grads, opts["critic_exploration"], params["critic_exploration"]
        )
        params = {**params, "critic_exploration": optax.apply_updates(params["critic_exploration"], cupd)}
        metrics["Loss/value_loss_exploration"] = vloss_expl

        # task behaviour (zero-shot Dreamer-V2 update)
        def task_actor_loss_fn(ap):
            traj, imagined_actions = imagine(wmp, ap, prior0, rec0, k_img_task)
            target_values = critic.apply(params["target_critic_task"], traj)
            rewards = world_model.reward_model.apply(wmp["reward_model"], traj)
            if use_continues:
                continues = jax.nn.sigmoid(world_model.continue_model.apply(wmp["continue_model"], traj))
                continues = jnp.concatenate([true_continue, continues[1:]], axis=0)
            else:
                continues = jnp.ones_like(rewards) * gamma
            lambda_values = compute_lambda_values(
                rewards[:-1], target_values[:-1], continues[:-1], bootstrap=target_values[-1:], lmbda=lmbda
            )
            discount = jax.lax.stop_gradient(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], axis=0), axis=0)
            )
            policy_loss = v2_policy_loss(ap, traj, imagined_actions, lambda_values, target_values, discount)
            aux = (jax.lax.stop_gradient(traj), jax.lax.stop_gradient(lambda_values), discount)
            return policy_loss, aux

        (policy_loss_task, (traj_sg_t, lambda_sg_t, discount_t)), at_grads = jax.value_and_grad(
            task_actor_loss_fn, has_aux=True
        )(params["actor_task"])
        at_grads = pmean_grads(at_grads, "dp")
        atupd, opts["actor_task"] = txs["actor_task"].update(at_grads, opts["actor_task"], params["actor_task"])
        params = {**params, "actor_task": optax.apply_updates(params["actor_task"], atupd)}
        metrics["Loss/policy_loss_task"] = policy_loss_task

        def task_critic_loss_fn(cp):
            qv = Independent(Normal(critic.apply(cp, traj_sg_t[:-1]), 1.0), 1)
            return -jnp.mean(discount_t[:-1, ..., 0] * qv.log_prob(lambda_sg_t))

        vloss_task, ct_grads = jax.value_and_grad(task_critic_loss_fn)(params["critic_task"])
        ct_grads = pmean_grads(ct_grads, "dp")
        ctupd, opts["critic_task"] = txs["critic_task"].update(ct_grads, opts["critic_task"], params["critic_task"])
        params = {**params, "critic_task": optax.apply_updates(params["critic_task"], ctupd)}
        metrics["Loss/value_loss_task"] = vloss_task

        metrics["State/post_entropy"] = Independent(
            OneHotCategorical(logits=post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size)), 1
        ).entropy().mean()
        metrics["State/prior_entropy"] = Independent(
            OneHotCategorical(logits=prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size)), 1
        ).entropy().mean()
        return (params, opts, cum + 1), metrics

    if ring is not None:
        from sheeprl_tpu.data.ring import build_burst_train_step

        return build_burst_train_step(
            gradient_step, mesh, ring, compiler_options=conv_heavy_compile_options(mesh)
        )

    def local_train(params, opts, data, key, cum0):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(data)[0].shape[0]
        keys = jax.random.split(key, n_steps)
        (params, opts, _), metrics = jax.lax.scan(gradient_step, (params, opts, cum0), (data, keys))
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), metrics)
        return params, opts, metrics

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(None, None, "dp"), P(), P()),
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

    # These arguments cannot be changed (reference: p2e_dv2_exploration.py:430-433)
    cfg.env.screen_size = 64
    cfg.env.frame_stack = 1
    cfg.algo.player.actor_type = "exploration"

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
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    world_model, ens_module, actor, critic, params, player = build_agent(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if state is not None else None,
        state["ensembles"] if state is not None else None,
        state["actor_task"] if state is not None else None,
        state["critic_task"] if state is not None else None,
        state["target_critic_task"] if state is not None else None,
        state["actor_exploration"] if state is not None else None,
        state["critic_exploration"] if state is not None else None,
        state["target_critic_exploration"] if state is not None else None,
    )

    txs = {
        "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
        "actor_task": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic_task": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
        "actor_exploration": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic_exploration": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
        "ensembles": build_optimizer(cfg.algo.ensembles.optimizer, max_grad_norm=cfg.algo.ensembles.clip_gradients),
    }
    opts = {
        "world": txs["world"].init(params["world_model"]),
        "actor_task": txs["actor_task"].init(params["actor_task"]),
        "critic_task": txs["critic_task"].init(params["critic_task"]),
        "actor_exploration": txs["actor_exploration"].init(params["actor_exploration"]),
        "critic_exploration": txs["critic_exploration"].init(params["critic_exploration"]),
        "ensembles": txs["ensembles"].init(params["ensembles"]),
    }
    if state is not None:
        opts = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opts, state["optimizers"])
    opts = fabric.put_replicated(opts)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 4
    buffer_type = str(cfg.buffer.type).lower()
    if buffer_type == "sequential":
        rb = EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=cfg.env.num_envs,
            obs_keys=tuple(obs_keys),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
            buffer_cls=SequentialReplayBuffer,
        )
    elif buffer_type == "episode":
        rb = EpisodeBuffer(
            buffer_size,
            minimum_episode_length=1 if cfg.dry_run else cfg.algo.per_rank_sequence_length,
            n_envs=cfg.env.num_envs,
            obs_keys=tuple(obs_keys),
            prioritize_ends=cfg.buffer.prioritize_ends,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        )
    else:
        raise ValueError(f"Unrecognized buffer type: must be one of `sequential` or `episode`, received: {buffer_type}")
    if state is not None and cfg.buffer.checkpoint:
        if isinstance(state["rb"], list):
            rb = state["rb"][0]
        elif isinstance(state["rb"], (EnvIndependentReplayBuffer, EpisodeBuffer)):
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
    rng = jax.random.PRNGKey(cfg.seed)
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder

    def player_params():
        return {"world_model": params["world_model"], "actor": params["actor_exploration"]}

    # TPU-native overlap, shared with the Dreamer mains (`algo.hybrid_player`).
    hp_cfg = cfg.algo.get("hybrid_player") or {}
    burst_mode = resolve_hybrid_player(hp_cfg, fabric.mesh)
    if burst_mode and buffer_type != "sequential":
        warnings.warn("hybrid_player burst mode requires buffer.type=sequential; falling back to host sampling")
        burst_mode = False
    host_mirror = (not burst_mode) or bool(cfg.buffer.checkpoint)

    if burst_mode:
        from sheeprl_tpu.utils.burst import HybridPlayerHarness

        wm_cfg_ = cfg.algo.world_model

        def _player_subset(p):
            wm = p["world_model"]
            return {
                "world_model": {
                    "encoder": wm["encoder"],
                    "recurrent_model": wm["recurrent_model"],
                    "representation_model": wm["representation_model"],
                },
                "actor": p["actor_exploration"],
            }

        hp = HybridPlayerHarness(
            fabric, cfg,
            observation_space=observation_space, cnn_keys=cnn_keys, mlp_keys=mlp_keys,
            actions_dim=actions_dim, capacity=buffer_size, seq_len=seq_len, batch_size=batch_size,
            policy_steps_per_iter=policy_steps_per_iter,
            make_burst_fn=lambda ring: make_train_step(
                world_model, ens_module, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs,
                ring=ring,
            ),
            player_subset=_player_subset,
            carry=(params, opts, jnp.int32(0)),
            rb=rb if (state is not None and cfg.buffer.checkpoint) else None,
            with_is_first=True, aggregator=aggregator,
        )
        host_player = PlayerDV2(
            world_model,
            actor,
            actions_dim,
            cfg.env.num_envs,
            int(wm_cfg_.stochastic_size),
            int(wm_cfg_.recurrent_model.recurrent_state_size),
            discrete_size=int(wm_cfg_.discrete_size),
            expl_amount=player.expl_amount,
            actor_type=player.actor_type,
            host_device=hp.host_device,
        )
        hp.extra_metrics["Params/exploration_amount"] = lambda: host_player.expl_amount
    else:
        train_fn = make_train_step(
            world_model, ens_module, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs
        )
    data_sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    if cfg.dry_run:
        step_data["truncated"] = step_data["truncated"] + 1
        step_data["terminated"] = step_data["terminated"] + 1
    step_data["actions"] = np.zeros((1, cfg.env.num_envs, int(np.sum(actions_dim))), dtype=np.float32)
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    if host_mirror:
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
    if burst_mode:
        hp.stage_step(step_data)
        host_player.init_states(hp.host_params)
    else:
        player.init_states(player_params())

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
                    action_list = host_player.get_actions(hp.host_params, jobs, hp.host_key())
                else:
                    rng, subkey = jax.random.split(rng)
                    action_list = player.get_actions(player_params(), jobs, subkey)
                actions = np.asarray(jnp.concatenate(action_list, axis=-1))
                if is_continuous:
                    real_actions = actions
                else:
                    real_actions = np.stack([np.asarray(a).argmax(axis=-1) for a in action_list], axis=-1)

            step_data["is_first"] = np.logical_or(step_data["terminated"], step_data["truncated"]).astype(
                np.float32
            )
            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)
            if cfg.dry_run and buffer_type == "episode":
                dones = np.ones_like(dones)
                terminated = np.ones_like(terminated)

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
            reset_data["is_first"] = np.ones_like(reset_data["terminated"])
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
                player.init_states(player_params(), dones_idxes)

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
                )
                data = {
                    k: jax.device_put(np.asarray(v, dtype=np.float32), data_sharding) for k, v in sample.items()
                }
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    params, opts, metrics = train_fn(
                        params, opts, data, train_key, jnp.int32(cumulative_per_rank_gradient_steps)
                    )
                    if aggregator and not aggregator.disabled:
                        for name, value in metrics.items():
                            if name in aggregator:
                                aggregator.update(name, value)
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
                params, opts, _ = hp.carry
            ckpt_state = {
                "world_model": params["world_model"],
                "ensembles": params["ensembles"],
                "actor_task": params["actor_task"],
                "critic_task": params["critic_task"],
                "target_critic_task": params["target_critic_task"],
                "actor_exploration": params["actor_exploration"],
                "critic_exploration": params["critic_exploration"],
                "target_critic_exploration": params["target_critic_exploration"],
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
        params, opts, _ = hp.finish()

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        player.actor_type = "task"
        test_params = {"world_model": params["world_model"], "actor": params["actor_task"]}
        test(player, test_params, fabric, cfg, log_dir, "zero-shot", writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(
            fabric,
            log_models,
            cfg,
            {
                "world_model": params["world_model"],
                "ensembles": params["ensembles"],
                "actor_task": params["actor_task"],
                "critic_task": params["critic_task"],
                "target_critic_task": params["target_critic_task"],
                "actor_exploration": params["actor_exploration"],
                "critic_exploration": params["critic_exploration"],
                "target_critic_exploration": params["target_critic_exploration"],
            },
        )
    logger.close()
