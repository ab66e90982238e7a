"""Plan2Explore on Dreamer-V3 — finetuning phase
(reference: ``sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py``).

Resumes the exploration checkpoint and trains the TASK actor/critic (and the
world model) on real rewards with the standard Dreamer-V3 update — the train
step IS :func:`sheeprl_tpu.algos.dreamer_v3.dreamer_v3.make_train_step`. The
env rollout starts with the exploration actor and switches to the task actor
at the first granted gradient step (reference ``:344-356``). Model/config
hyper-parameters are pinned to the exploration run's (reference ``:46-70``;
the env-level pinning happens in ``cli.run_algorithm``).
"""

from __future__ import annotations

import pathlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers, make_train_step
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments, prepare_obs, test
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.algos.world_model_loop import Family, HostSampledTrainer, run as run_loop
from sheeprl_tpu.utils.burst import DREAMER_METRIC_NAMES
from sheeprl_tpu.utils.registry import register_algorithm

__all__ = ["main"]


@register_algorithm()
def main(fabric, cfg: Dict[str, Any], exploration_cfg: Dict[str, Any]):
    from sheeprl_tpu.utils.checkpoint import load_state

    ckpt_path = pathlib.Path(cfg.checkpoint.exploration_ckpt_path)
    resume_from_checkpoint = bool(cfg.checkpoint.resume_from)
    state = load_state(pathlib.Path(cfg.checkpoint.resume_from) if resume_from_checkpoint else ckpt_path)

    # All the models must be equal to the ones of the exploration phase
    # (reference: p2e_dv3_finetuning.py:46-70)
    for k in (
        "gamma", "lmbda", "horizon", "dense_units", "mlp_layers", "dense_act", "cnn_act",
        "unimix", "hafner_initialization",
    ):
        cfg.algo[k] = exploration_cfg.algo[k]
    cfg.algo.world_model = exploration_cfg.algo.world_model
    cfg.algo.actor = exploration_cfg.algo.actor
    cfg.algo.critic = exploration_cfg.algo.critic
    cfg.env.clip_rewards = exploration_cfg.env.clip_rewards
    if cfg.buffer.load_from_exploration and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs
    cfg.algo.cnn_keys = exploration_cfg.algo.cnn_keys
    cfg.algo.mlp_keys = exploration_cfg.algo.mlp_keys
    cfg.env.frame_stack = 1

    def build(observation_space, actions_dim, is_continuous):
        world_model, _, actor, critic, _, p2e_params, player = build_agent(
            fabric,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state["world_model"],
            state.get("ensembles"),
            state["actor_task"],
            state["critic_task"],
            state["target_critic_task"],
            state["actor_exploration"],
            state.get("critics_exploration"),
        )
        # Dreamer-V3-shaped params for the shared train step; the exploration
        # actor rides along for the pre-switch player.
        params = {
            "world_model": p2e_params["world_model"],
            "actor": p2e_params["actor_task"],
            "critic": p2e_params["critic_task"],
            "target_critic": p2e_params["target_critic_task"],
        }
        actor_exploration_params = p2e_params["actor_exploration"]

        saved_opts = state.get("optimizers", {})
        opt_key_map = {"world": "world", "actor": "actor_task", "critic": "critic_task"}
        if resume_from_checkpoint:
            opt_key_map = {"world": "world", "actor": "actor", "critic": "critic"}
        txs, opts = build_optimizers(
            cfg, fabric, params, {mine: saved_opts[theirs] for mine, theirs in opt_key_map.items() if theirs in saved_opts}
        )

        moments_state = init_moments()
        saved_moments = state.get("moments")
        if saved_moments is not None:
            if not resume_from_checkpoint and "task" in saved_moments:
                saved_moments = saved_moments["task"]
            moments_state = jax.tree.map(jnp.asarray, saved_moments)
        moments_state = fabric.put_replicated(moments_state)

        def player_params(p, trained):
            # The player starts with the exploration actor and switches to the
            # task actor at the first granted gradient step
            # (reference: p2e_dv3_finetuning.py:344-356)
            player.actor_type = "task" if trained else "exploration"
            return {"world_model": p["world_model"], "actor": p["actor"] if trained else actor_exploration_params}

        def models(p):
            return {
                "world_model": p["world_model"],
                "actor_task": p["actor"],
                "critic_task": p["critic"],
                "target_critic_task": p["target_critic"],
            }

        return Family(
            carry=(params, opts, moments_state),
            make_train_step=lambda: make_train_step(
                world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs
            ),
            player=player,
            prepare_obs=prepare_obs,
            player_params=player_params,
            models=lambda p: {**models(p), "actor_exploration": actor_exploration_params},
            test=lambda p, log_dir, logger: test(
                player, player_params(p, True), fabric, cfg, log_dir, greedy=False, writer=logger
            ),
            registered_models=lambda p, moments: {**models(p), "moments_task": moments},
            metric_names=DREAMER_METRIC_NAMES,
        )

    load_replay = resume_from_checkpoint or (cfg.buffer.load_from_exploration and exploration_cfg.buffer.checkpoint)
    run_loop(
        fabric,
        cfg,
        build,
        trainer=HostSampledTrainer,  # the burst topology for this main is a named debt (ROADMAP D1)
        resume=state if resume_from_checkpoint else None,
        replay=state["rb"] if load_replay else None,
        random_prefill=False,  # the loaded exploration actor acts from the first step
    )
