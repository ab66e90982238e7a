"""Plan2Explore on Dreamer-V3 — exploration phase
(reference: ``sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py``).

One jitted shard_map G-step update per grant, each gradient step running
the four phases of P2E (Sekar et al., arXiv:2005.05960) as compiled scans:

1. world-model update (Dreamer-V3 reconstruction loss; reward/continue heads
   fed STOP-GRADIENT latents, reference ``:159-166``);
2. ensemble update — N vmapped forward models regress the next stochastic
   state from ``(latent, action)`` (``:204-226``);
3. exploration behaviour — imagination with the exploration actor; each
   configured critic contributes a Moments-normalized advantage, weighted by
   ``weight / sum(weights)``; ``intrinsic`` critics read the ensemble
   disagreement ``Var_N(next-state prediction)`` as reward (``:240-330``);
4. task behaviour (zero-shot) — the standard Dreamer-V3 actor/critic update
   on the same world model (``:375-460``).

The env rollout uses the exploration actor (``cfg.algo.player.actor_type =
"exploration"``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.dreamer_v3.agent import actor_dists, actor_sample
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import host_player_factory, player_snapshot
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent, ensembles_apply
from sheeprl_tpu.algos.p2e_dv3.utils import (
    compute_lambda_values,
    init_moments,
    moments_update,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.world_model_loop import Family, platform_trainer, run as run_loop
from sheeprl_tpu.distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.registry import register_algorithm

__all__ = ["main", "make_train_step"]


def make_train_step(
    world_model,
    ens_module,
    actor,
    critic,
    critics_spec: Dict[str, Dict[str, Any]],
    cfg,
    mesh,
    actions_dim: Sequence[int],
    is_continuous: bool,
    txs: Dict[str, Any],
    ring=None,
):
    """Build the fully-jitted G-step P2E-DV3 update (see module docstring)."""
    rssm = world_model.rssm
    wm_cfg = cfg.algo.world_model
    cnn_enc = list(cfg.algo.cnn_keys.encoder)
    mlp_enc = list(cfg.algo.mlp_keys.encoder)
    cnn_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_dec = list(cfg.algo.mlp_keys.decoder)
    stoch_state_size = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    target_update_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    tau = float(cfg.algo.critic.tau)
    moments_cfg = cfg.algo.actor.moments
    split_sizes = np.cumsum(np.asarray(actions_dim[:-1], dtype=np.int64)).tolist()
    critic_names = sorted(critics_spec.keys())
    weights_sum = sum(critics_spec[k]["weight"] for k in critic_names)

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
        _, (recs, posts, post_logits, prior_logits) = jax.lax.scan(
            step, (rec0, post0), (embedded, actions, is_first, keys)
        )
        return recs, posts, post_logits, prior_logits

    def imagine(wmp, actor_params, prior0, rec0, key):
        """H+1 latents / actions, one action sampled at every state
        (reference: ``p2e_dv3_exploration.py:240-260``)."""
        latent0 = jnp.concatenate([prior0, rec0], axis=-1)
        k0, k_scan = jax.random.split(key)
        a0 = jnp.concatenate(actor_sample(actor, actor_params, jax.lax.stop_gradient(latent0), k0)[0], axis=-1)

        def img_step(carry, k):
            prior, rec, act = carry
            k_prior, k_act = jax.random.split(k)
            prior, rec = rssm.imagination(wmp, prior, rec, act, k_prior)
            latent = jnp.concatenate([prior, rec], axis=-1)
            new_act = jnp.concatenate(
                actor_sample(actor, actor_params, jax.lax.stop_gradient(latent), k_act)[0], axis=-1
            )
            return (prior, rec, new_act), (latent, new_act)

        _, (latents, acts) = jax.lax.scan(img_step, (prior0, rec0, a0), jax.random.split(k_scan, horizon))
        traj = jnp.concatenate([latent0[None], latents], axis=0)  # (H+1, TB, L)
        imagined_actions = jnp.concatenate([a0[None], acts], axis=0)
        return traj, imagined_actions

    def policy_objective(actor_params, traj, imagined_actions, advantage):
        policies = actor_dists(actor, actor.apply(actor_params, jax.lax.stop_gradient(traj)))
        if is_continuous:
            objective = advantage
        else:
            act_parts = (
                jnp.split(imagined_actions, split_sizes, axis=-1) if len(actions_dim) > 1 else [imagined_actions]
            )
            logprob = jnp.stack(
                [p.log_prob(jax.lax.stop_gradient(a))[..., None][:-1] for p, a in zip(policies, act_parts)],
                axis=-1,
            ).sum(-1)
            objective = logprob * jax.lax.stop_gradient(advantage)
        try:
            entropy = ent_coef * jnp.stack([p.entropy() for p in policies], axis=-1).sum(-1)
        except NotImplementedError:
            entropy = jnp.zeros(traj.shape[:-1], dtype=traj.dtype)
        return objective, entropy

    def critic_update(cp_key, traj_sg, lambda_sg, discount, params_c, opt_c, tx):
        def loss_fn(cp):
            qv = TwoHotEncodingDistribution(critic.apply(cp, traj_sg[:-1]), dims=1)
            target_values = TwoHotEncodingDistribution(critic.apply(cp_key, traj_sg[:-1]), dims=1).mean
            vloss = -qv.log_prob(lambda_sg) - qv.log_prob(jax.lax.stop_gradient(target_values))
            return jnp.mean(vloss * discount[:-1, ..., 0])

        vloss, grads = jax.value_and_grad(loss_fn)(params_c)
        grads = pmean_grads(grads, "dp")
        upd, opt_c = tx.update(grads, opt_c, params_c)
        return vloss, optax.apply_updates(params_c, upd), opt_c

    def gradient_step(carry, xs):
        params, opts, moments_state, cum = carry
        batch, key = xs
        k_dyn, k_img_expl, k_img_task = jax.random.split(key, 3)
        metrics: Dict[str, jax.Array] = {}

        # -- target EMA gates (task + every exploration critic)
        tau_eff = jnp.where(cum == 0, 1.0, tau)
        mix = jnp.where(cum % target_update_freq == 0, tau_eff, 0.0)
        params = {
            **params,
            "target_critic_task": jax.tree.map(
                lambda c, t: mix * c + (1.0 - mix) * t, params["critic_task"], params["target_critic_task"]
            ),
            "critics_exploration": {
                k: {
                    "module": params["critics_exploration"][k]["module"],
                    "target": jax.tree.map(
                        lambda c, t: mix * c + (1.0 - mix) * t,
                        params["critics_exploration"][k]["module"],
                        params["critics_exploration"][k]["target"],
                    ),
                }
                for k in critic_names
            },
        }

        batch_obs = {k: batch[k] / 255.0 - 0.5 for k in cnn_enc}
        batch_obs.update({k: batch[k] for k in mlp_enc})
        is_first = batch["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0)

        # -- 1. world model (reward/continue heads on sg(latents))
        def wm_loss_fn(wmp):
            embedded = world_model.encoder.apply(wmp["encoder"], batch_obs)
            recs, posts, post_logits, prior_logits = dynamic_rollout(wmp, embedded, batch_actions, is_first, k_dyn)
            latents = jnp.concatenate([posts, recs], axis=-1)
            recon = world_model.decode(wmp, latents)
            po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec}
            po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec})
            latents_sg = jax.lax.stop_gradient(latents)
            pr = TwoHotEncodingDistribution(world_model.reward_model.apply(wmp["reward_model"], latents_sg), dims=1)
            pc = Independent(
                BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], latents_sg)), 1
            )
            continue_targets = 1 - batch["terminated"]
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                batch_obs,
                pr,
                batch["rewards"],
                prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size),
                post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size),
                float(wm_cfg.kl_dynamic),
                float(wm_cfg.kl_representation),
                float(wm_cfg.kl_free_nats),
                float(wm_cfg.kl_regularizer),
                pc,
                continue_targets,
                float(wm_cfg.continue_scale_factor),
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
        T, B = batch["actions"].shape[:2]
        posts_sg = jax.lax.stop_gradient(posts)
        recs_sg = jax.lax.stop_gradient(recs)
        latents_sg = jnp.concatenate([posts_sg, recs_sg], axis=-1)

        # -- 2. ensembles: predict next stochastic state from (latent, action)
        ens_in = jnp.concatenate([latents_sg, batch["actions"]], axis=-1)

        def ens_loss_fn(ep):
            outs = ensembles_apply(ens_module, ep, ens_in)  # (N, T, B, S)
            if outs.shape[1] > 1:
                pred, tgt = outs[:, :-1], posts_sg[None, 1:]
            else:  # degenerate T=1 (dry runs): fit the only row
                pred, tgt = outs, posts_sg[None]
            per_member = -MSEDistribution(pred, dims=1).log_prob(tgt).mean(axis=(1, 2))
            return per_member.sum()

        ens_loss, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        ens_grads = pmean_grads(ens_grads, "dp")
        eupd, opts["ensembles"] = txs["ensembles"].update(ens_grads, opts["ensembles"], params["ensembles"])
        params = {**params, "ensembles": optax.apply_updates(params["ensembles"], eupd)}
        metrics["Loss/ensemble_loss"] = ens_loss

        prior0 = posts_sg.reshape(T * B, stoch_state_size)
        rec0 = recs_sg.reshape(T * B, recurrent_state_size)
        true_continue = (1 - batch["terminated"]).reshape(1, T * B, 1)

        # -- 3. exploration behaviour
        def expl_actor_loss_fn(ap, mstate):
            traj, imagined_actions = imagine(wmp, ap, prior0, rec0, k_img_expl)
            continues = Independent(
                BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], traj)), 1
            ).mode
            continues = jnp.concatenate([true_continue, continues[1:]], axis=0)

            advantages = []
            lambda_per_critic = {}
            new_mstate = dict(mstate)
            aux_metrics = {}
            for name in critic_names:
                cp = params["critics_exploration"][name]["module"]
                values = TwoHotEncodingDistribution(critic.apply(cp, traj), dims=1).mean
                if critics_spec[name]["reward_type"] == "intrinsic":
                    ens_pred = ensembles_apply(
                        ens_module,
                        params["ensembles"],
                        jax.lax.stop_gradient(jnp.concatenate([traj, imagined_actions], axis=-1)),
                    )
                    reward = ens_pred.var(axis=0).mean(-1, keepdims=True) * intrinsic_mult
                    aux_metrics["Rewards/intrinsic"] = reward.mean()
                else:
                    reward = TwoHotEncodingDistribution(
                        world_model.reward_model.apply(wmp["reward_model"], traj), dims=1
                    ).mean
                lambda_values = compute_lambda_values(reward[1:], values[1:], continues[1:] * gamma, lmbda)
                lambda_per_critic[name] = jax.lax.stop_gradient(lambda_values)
                new_mstate[name], offset, invscale = moments_update(
                    mstate[name],
                    lambda_values,
                    decay=float(moments_cfg.decay),
                    max_=float(moments_cfg.max),
                    percentile_low=float(moments_cfg.percentile.low),
                    percentile_high=float(moments_cfg.percentile.high),
                    axis_name="dp",
                )
                normed_lambda = (lambda_values - offset) / invscale
                normed_baseline = (values[:-1] - offset) / invscale
                advantages.append((normed_lambda - normed_baseline) * critics_spec[name]["weight"] / weights_sum)
                aux_metrics[f"Values_exploration/predicted_values_{name}"] = values.mean()
                aux_metrics[f"Values_exploration/lambda_values_{name}"] = lambda_values.mean()

            advantage = sum(advantages)
            discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)
            objective, entropy = policy_objective(ap, traj, imagined_actions, advantage)
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
            aux = (jax.lax.stop_gradient(traj), lambda_per_critic, discount, new_mstate, aux_metrics)
            return policy_loss, aux

        (policy_loss_expl, (traj_sg, lambda_per_critic, discount, m_expl, aux_metrics)), a_grads = (
            jax.value_and_grad(expl_actor_loss_fn, has_aux=True)(
                params["actor_exploration"], moments_state["exploration"]
            )
        )
        moments_state = {**moments_state, "exploration": m_expl}
        a_grads = pmean_grads(a_grads, "dp")
        aupd, opts["actor_exploration"] = txs["actor_exploration"].update(
            a_grads, opts["actor_exploration"], params["actor_exploration"]
        )
        params = {**params, "actor_exploration": optax.apply_updates(params["actor_exploration"], aupd)}
        metrics["Loss/policy_loss_exploration"] = policy_loss_expl
        metrics.update(aux_metrics)

        new_critics = {}
        for name in critic_names:
            vloss, new_cp, opts["critics_exploration"][name] = critic_update(
                params["critics_exploration"][name]["target"],
                traj_sg,
                lambda_per_critic[name],
                discount,
                params["critics_exploration"][name]["module"],
                opts["critics_exploration"][name],
                txs["critics_exploration"][name],
            )
            new_critics[name] = {"module": new_cp, "target": params["critics_exploration"][name]["target"]}
            metrics[f"Loss/value_loss_{name}"] = vloss
        params = {**params, "critics_exploration": new_critics}

        # -- 4. task behaviour (zero-shot Dreamer-V3 update)
        def task_actor_loss_fn(ap, mstate):
            traj, imagined_actions = imagine(wmp, ap, prior0, rec0, k_img_task)
            values = TwoHotEncodingDistribution(critic.apply(params["critic_task"], traj), dims=1).mean
            rewards = TwoHotEncodingDistribution(
                world_model.reward_model.apply(wmp["reward_model"], traj), dims=1
            ).mean
            continues = Independent(
                BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], traj)), 1
            ).mode
            continues = jnp.concatenate([true_continue, continues[1:]], axis=0)

            lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * gamma, lmbda)
            discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)
            new_mstate, offset, invscale = moments_update(
                mstate,
                lambda_values,
                decay=float(moments_cfg.decay),
                max_=float(moments_cfg.max),
                percentile_low=float(moments_cfg.percentile.low),
                percentile_high=float(moments_cfg.percentile.high),
                axis_name="dp",
            )
            advantage = (lambda_values - offset) / invscale - (values[:-1] - offset) / invscale
            objective, entropy = policy_objective(ap, traj, imagined_actions, advantage)
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
            aux = (jax.lax.stop_gradient(traj), jax.lax.stop_gradient(lambda_values), discount, new_mstate)
            return policy_loss, aux

        (policy_loss_task, (traj_sg_t, lambda_sg_t, discount_t, m_task)), at_grads = jax.value_and_grad(
            task_actor_loss_fn, has_aux=True
        )(params["actor_task"], moments_state["task"])
        moments_state = {**moments_state, "task": m_task}
        at_grads = pmean_grads(at_grads, "dp")
        atupd, opts["actor_task"] = txs["actor_task"].update(at_grads, opts["actor_task"], params["actor_task"])
        params = {**params, "actor_task": optax.apply_updates(params["actor_task"], atupd)}
        metrics["Loss/policy_loss_task"] = policy_loss_task

        vloss_task, new_ct, opts["critic_task"] = critic_update(
            params["target_critic_task"], traj_sg_t, lambda_sg_t, discount_t,
            params["critic_task"], opts["critic_task"], txs["critic_task"],
        )
        params = {**params, "critic_task": new_ct}
        metrics["Loss/value_loss_task"] = vloss_task

        metrics["State/post_entropy"] = Independent(
            OneHotCategorical(logits=post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size)), 1
        ).entropy().mean()
        metrics["State/prior_entropy"] = Independent(
            OneHotCategorical(logits=prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size)), 1
        ).entropy().mean()
        return (params, opts, moments_state, cum + 1), metrics

    if ring is not None:
        from sheeprl_tpu.data.ring import build_burst_train_step

        return build_burst_train_step(gradient_step, mesh, ring)

    def local_train(params, opts, moments_state, data, key, cum0):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(data)[0].shape[0]
        keys = jax.random.split(key, n_steps)
        (params, opts, moments_state, _), metrics = jax.lax.scan(
            gradient_step, (params, opts, moments_state, cum0), (data, keys)
        )
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), metrics)
        return params, opts, moments_state, metrics

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, None, "dp"), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(shard_train, donate_argnums=(0, 1, 2))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.fault import load_resume_state

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference: p2e_dv3_exploration.py:530-532)
    cfg.env.frame_stack = 1
    cfg.algo.player.actor_type = "exploration"
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if cfg.metric.log_level > 0:
        print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)

    model_keys = (
        "world_model", "ensembles", "actor_task", "critic_task", "target_critic_task", "actor_exploration",
        "critics_exploration",
    )

    def models(p):
        return {k: p[k] for k in model_keys}

    def build(observation_space, actions_dim, is_continuous):
        world_model, ens_module, actor, critic, critics_spec, params, player = build_agent(
            fabric,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            *(state[k] if state is not None else None for k in model_keys),
        )

        txs = {
            "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
            "actor_task": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
            "critic_task": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
            "actor_exploration": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
            "ensembles": build_optimizer(cfg.algo.ensembles.optimizer, max_grad_norm=cfg.algo.ensembles.clip_gradients),
            "critics_exploration": {
                k: build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients)
                for k in critics_spec
            },
        }
        opts = {
            "world": txs["world"].init(params["world_model"]),
            "actor_task": txs["actor_task"].init(params["actor_task"]),
            "critic_task": txs["critic_task"].init(params["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(params["actor_exploration"]),
            "ensembles": txs["ensembles"].init(params["ensembles"]),
            "critics_exploration": {
                k: txs["critics_exploration"][k].init(params["critics_exploration"][k]["module"]) for k in critics_spec
            },
        }
        if state is not None:
            opts = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opts, state["optimizers"])
        opts = fabric.put_replicated(opts)

        moments_state = {"task": init_moments(), "exploration": {k: init_moments() for k in critics_spec}}
        if state is not None:
            moments_state = jax.tree.map(jnp.asarray, state["moments"])
        moments_state = fabric.put_replicated(moments_state)

        def zero_shot_test(p, log_dir, logger):
            # Zero-shot task test (reference: p2e_dv3_exploration.py:800-812)
            player.actor_type = "task"
            test_params = {"world_model": p["world_model"], "actor": p["actor_task"]}
            test(player, test_params, fabric, cfg, log_dir, "zero-shot", greedy=False, writer=logger)

        return Family(
            carry=(params, opts, moments_state),
            make_train_step=lambda ring=None: make_train_step(
                world_model, ens_module, actor, critic, critics_spec, cfg, fabric.mesh, actions_dim, is_continuous,
                txs, ring=ring,
            ),
            player=player,
            prepare_obs=prepare_obs,
            # the env rollout uses the exploration actor
            player_params=lambda p, trained: {"world_model": p["world_model"], "actor": p["actor_exploration"]},
            models=models,
            test=zero_shot_test,
            registered_models=lambda p, moments: {
                **models(p),
                "moments_task": moments["task"],
                "moments_exploration": moments["exploration"],
            },
            make_host_player=host_player_factory(world_model, actor, actions_dim, cfg, actor_type="exploration"),
            player_subset=lambda p: player_snapshot(p["world_model"], p["actor_exploration"]),
        )

    run_loop(
        fabric,
        cfg,
        build,
        trainer=platform_trainer(fabric, cfg),
        resume=state,
        replay=state["rb"] if state is not None and cfg.buffer.checkpoint else None,
    )
