"""PPO host-side helpers (reference: ``sheeprl/algos/ppo/utils.py``)."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import numpy as np

from sheeprl_tpu.envs.factory import make_env
from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

# Fault/* counters are cumulative gauges logged directly (logger.log_dict),
# not aggregated — keep them out of the aggregator key set.
AGGREGATOR_KEYS = {"Rewards/rew_avg", "Game/ep_len_avg", "Loss/value_loss", "Loss/policy_loss", "Loss/entropy_loss"}
MODELS_TO_REGISTER = {"agent"}


def normalize_obs(
    obs: Dict[str, np.ndarray], cnn_keys: Sequence[str], obs_keys: Sequence[str]
) -> Dict[str, np.ndarray]:
    """Pixel keys to [-0.5, 0.5] (reference: ``utils.py:70-73``)."""
    return {k: obs[k] / 255.0 - 0.5 if k in cnn_keys else obs[k] for k in obs_keys}


def prepare_obs(
    fabric, obs: Dict[str, np.ndarray], *, cnn_keys: Sequence[str] = (), mlp_keys: Sequence[str] = (), num_envs: int = 1, **kwargs
) -> Dict[str, np.ndarray]:
    """Host numpy obs → normalized float32 arrays shaped ``(num_envs, ...)``
    (reference: ``utils.py:25-37``, NHWC here).

    Deliberately returns *host* arrays: callers feed them straight into jitted
    player fns, whose placement follows the (committed) params. An explicit
    ``device_put`` here would commit every step's obs to the default device —
    a per-step host↔device copy when the rollout runs on a different backend
    than JAX's default (e.g. a CPU rollout in a process that also sees a chip)."""
    out = {}
    for k in obs.keys():
        v = np.asarray(obs[k], dtype=np.float32)
        if k in cnn_keys:
            v = v.reshape(num_envs, *v.shape[-3:])
            v = v / 255.0 - 0.5
        else:
            v = v.reshape(num_envs, -1)
        out[k] = v
    return out


def test(player, params, fabric, cfg: Dict[str, Any], log_dir: str, writer=None) -> None:
    """Greedy evaluation episode (reference: ``utils.py:40-67``)."""
    env = make_env(cfg, None if cfg.seed is None else cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
    done = False
    cumulative_rew = 0.0
    obs = env.reset(seed=cfg.seed)[0]
    key = jax.random.PRNGKey(cfg.seed or 0)
    while not done:
        jobs = prepare_obs(fabric, obs, cnn_keys=cfg.algo.cnn_keys.encoder, num_envs=1)
        key, subkey = jax.random.split(key)
        actions = player.get_actions(params, jobs, subkey, greedy=True)
        if player.is_continuous:
            real_actions = np.concatenate([np.asarray(a) for a in actions], axis=-1)
        else:
            real_actions = np.concatenate([np.asarray(a).argmax(axis=-1) for a in actions], axis=-1)
        obs, reward, done, truncated, _ = env.step(real_actions.reshape(env.action_space.shape))
        done = done or truncated
        cumulative_rew += reward
        if cfg.dry_run:
            done = True
    print("Test - Reward:", cumulative_rew)
    if cfg.metric.log_level > 0 and writer is not None:
        writer.log_dict({"Test/cumulative_reward": cumulative_rew}, 0)
    env.close()


from sheeprl_tpu.utils.mlflow import log_models  # noqa: E402  (shared registry helper)


def log_models_from_checkpoint(fabric, env, cfg, state):  # pragma: no cover - mlflow optional
    if not _IS_MLFLOW_AVAILABLE:
        raise ModuleNotFoundError("mlflow is not installed")
    import mlflow

    from sheeprl_tpu.algos.ppo.agent import build_agent

    is_continuous = isinstance(env.action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(env.action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        env.action_space.shape
        if is_continuous
        else (env.action_space.nvec.tolist() if is_multidiscrete else [env.action_space.n])
    )
    agent, params, _ = build_agent(fabric, actions_dim, is_continuous, cfg, env.observation_space, state["agent"])
    model_info = {}
    with mlflow.start_run(run_id=cfg.run.id, experiment_id=cfg.experiment.id, run_name=cfg.run.name, nested=True):
        model_info["agent"] = mlflow.log_dict(
            jax.tree.map(lambda x: np.asarray(x).tolist(), state["agent"]), "agent_params.json"
        )
        mlflow.log_dict(dict(cfg.to_log), "config.json")
    return model_info
