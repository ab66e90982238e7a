"""Environment factory (reference: ``sheeprl/utils/env.py:26-249``).

``make_env(cfg, seed, rank, ...)`` returns a thunk building a gymnasium env
whose observation space is always a ``gym.spaces.Dict``, with pixel keys
resized/grayscaled to ``(screen_size, screen_size, C)`` **channel-last**
(TPU conv layout; the reference emits channel-first) and vector keys float32.

``vectorize_env`` builds the Sync/Async vector env with SAME_STEP autoreset,
matching the reference's gym-0.29-era semantics (``final_obs``/``final_info``
delivered on the step where done is observed) that all the rollout loops rely
on.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Callable, Dict, Optional

import gymnasium as gym
import numpy as np

from sheeprl_tpu.config import instantiate
from sheeprl_tpu.envs.wrappers import (
    ActionRepeat,
    ActionsAsObservationWrapper,
    FrameStack,
    GrayscaleRenderWrapper,
    MaskVelocityWrapper,
    RewardAsObservationWrapper,
)

__all__ = ["make_env", "vectorize_env", "get_dummy_env"]


class _AsDictObs(gym.ObservationWrapper):
    """Wrap a Box observation into a single-key dict space."""

    def __init__(self, env: gym.Env, key: str):
        super().__init__(env)
        self._key = key
        self.observation_space = gym.spaces.Dict({key: env.observation_space})

    def observation(self, observation):
        return {self._key: observation}


class _AddRenderObs(gym.Wrapper):
    """Add the rendered frame as an extra pixel observation key (replaces the
    reference's PixelObservationWrapper usage, ``env.py:110-117``)."""

    def __init__(self, env: gym.Env, pixel_key: str, state_key: Optional[str] = None):
        super().__init__(env)
        self._pixel_key = pixel_key
        self._state_key = state_key
        frame = self._render_frame()
        spaces = {pixel_key: gym.spaces.Box(0, 255, frame.shape, np.uint8)}
        if state_key is not None:
            spaces[state_key] = env.observation_space
        self.observation_space = gym.spaces.Dict(spaces)

    def _render_frame(self) -> np.ndarray:
        frame = self.env.render()
        if frame is None:
            raise RuntimeError(
                "The environment returned no render frame; pixel observations require render_mode='rgb_array'"
            )
        return np.asarray(frame)

    def _convert(self, obs):
        out = {self._pixel_key: self._render_frame()}
        if self._state_key is not None:
            out[self._state_key] = obs
        return out

    def step(self, action):
        obs, reward, done, truncated, info = self.env.step(action)
        return self._convert(obs), reward, done, truncated, info

    def reset(self, *, seed=None, options=None):
        obs, info = self.env.reset(seed=seed, options=options)
        return self._convert(obs), info


class _TransformPixels(gym.ObservationWrapper):
    """Resize / grayscale pixel keys to (screen_size, screen_size, C) uint8
    channel-last (reference transform: ``env.py:161-203``, NCHW there)."""

    def __init__(self, env: gym.Env, cnn_keys, screen_size: int, grayscale: bool):
        super().__init__(env)
        import copy as _copy

        self._cnn_keys = cnn_keys
        self._screen_size = screen_size
        self._grayscale = grayscale
        self.observation_space = _copy.deepcopy(env.observation_space)
        for k in cnn_keys:
            self.observation_space[k] = gym.spaces.Box(
                0, 255, (screen_size, screen_size, 1 if grayscale else 3), np.uint8
            )

    def observation(self, obs):
        import cv2

        for k in self._cnn_keys:
            current = np.asarray(obs[k])
            shape = current.shape
            is_3d = len(shape) == 3
            is_grayscale = not is_3d or shape[0] == 1 or shape[-1] == 1
            channel_first = is_3d and shape[0] in (1, 3) and shape[-1] not in (1, 3)

            if not is_3d:
                current = current[..., None]
            elif channel_first:
                current = np.transpose(current, (1, 2, 0))

            if current.shape[:-1] != (self._screen_size, self._screen_size):
                current = cv2.resize(
                    current, (self._screen_size, self._screen_size), interpolation=cv2.INTER_AREA
                )
                if current.ndim == 2:
                    current = current[..., None]

            if self._grayscale and not (current.shape[-1] == 1):
                current = cv2.cvtColor(current, cv2.COLOR_RGB2GRAY)[..., None]
            if not self._grayscale and current.shape[-1] == 1:
                current = np.repeat(current, 3, axis=-1)

            obs[k] = current.astype(np.uint8)
        return obs


class _FloatVectorObs(gym.ObservationWrapper):
    """Cast non-pixel keys to float32 vectors."""

    def __init__(self, env: gym.Env, mlp_keys):
        super().__init__(env)
        import copy as _copy

        self._mlp_keys = mlp_keys
        self.observation_space = _copy.deepcopy(env.observation_space)
        for k in mlp_keys:
            space = env.observation_space[k]
            low = np.asarray(space.low, dtype=np.float32).reshape(-1)
            high = np.asarray(space.high, dtype=np.float32).reshape(-1)
            self.observation_space[k] = gym.spaces.Box(low, high, (int(np.prod(space.shape or (1,))),), np.float32)

    def observation(self, obs):
        for k in self._mlp_keys:
            obs[k] = np.asarray(obs[k], dtype=np.float32).reshape(-1)
        return obs


def get_dummy_env(id: str):
    """(reference: ``env.py:236-249``)"""
    if "continuous" in id:
        from sheeprl_tpu.envs.dummy import ContinuousDummyEnv

        return ContinuousDummyEnv()
    elif "multidiscrete" in id:
        from sheeprl_tpu.envs.dummy import MultiDiscreteDummyEnv

        return MultiDiscreteDummyEnv()
    elif "discrete" in id:
        from sheeprl_tpu.envs.dummy import DiscreteDummyEnv

        return DiscreteDummyEnv()
    raise ValueError(f"Unrecognized dummy environment: {id}")


def make_env(
    cfg: Dict[str, Any],
    seed: int,
    rank: int,
    run_name: Optional[str] = None,
    prefix: str = "",
    vector_env_idx: int = 0,
) -> Callable[[], gym.Env]:
    def thunk() -> gym.Env:
        try:
            env_spec = gym.spec(cfg.env.id).entry_point
        except Exception:
            env_spec = ""

        wrapper_cfg = dict(cfg.env.wrapper)
        if "seed" in wrapper_cfg:
            wrapper_cfg["seed"] = seed
        if "rank" in wrapper_cfg:
            wrapper_cfg["rank"] = rank + vector_env_idx
        env = instantiate(wrapper_cfg)

        # Atari-protocol envs (AtariPreprocessing, AtariProtocolDummyEnv)
        # implement frame-skip themselves — stacking ActionRepeat on top
        # would square the repeat (reference guard: ``env.py``'s env_spec
        # check; the attribute covers envs gym.spec cannot resolve).
        built_in_skip = int(getattr(env, "frame_skip", 1) or 1)
        if cfg.env.action_repeat > 1 and "atari" not in str(env_spec) and built_in_skip <= 1:
            env = ActionRepeat(env, cfg.env.action_repeat)

        if cfg.env.get("mask_velocities", False):
            env = MaskVelocityWrapper(env)

        cnn_enc = list(cfg.algo.cnn_keys.encoder or [])
        mlp_enc = list(cfg.algo.mlp_keys.encoder or [])
        if len(cnn_enc + mlp_enc) == 0:
            raise ValueError(
                "`algo.cnn_keys.encoder` and `algo.mlp_keys.encoder` must be non-empty lists of strings, got: "
                f"cnn={cfg.algo.cnn_keys.encoder} mlp={cfg.algo.mlp_keys.encoder}"
            )

        # Dict-ify the observation space (reference: env.py:100-146)
        obs_space = env.observation_space
        if isinstance(obs_space, gym.spaces.Box) and len(obs_space.shape) < 2:
            if len(cnn_enc) > 0:
                if len(cnn_enc) > 1:
                    warnings.warn(f"Only one pixel observation is allowed in {cfg.env.id}; keeping {cnn_enc[0]}")
                env = _AddRenderObs(env, pixel_key=cnn_enc[0], state_key=mlp_enc[0] if mlp_enc else None)
            else:
                if len(mlp_enc) > 1:
                    warnings.warn(f"Only one vector observation is allowed in {cfg.env.id}; keeping {mlp_enc[0]}")
                env = _AsDictObs(env, mlp_enc[0])
        elif isinstance(obs_space, gym.spaces.Box) and 2 <= len(obs_space.shape) <= 3:
            if len(cnn_enc) == 0:
                raise ValueError(
                    "You have selected a pixel observation but no cnn key has been specified. "
                    "Please set at least one cnn key in the config file: `algo.cnn_keys.encoder=[your_cnn_key]`"
                )
            if len(cnn_enc) > 1:
                warnings.warn(f"Only one pixel observation is allowed in {cfg.env.id}; keeping {cnn_enc[0]}")
            env = _AsDictObs(env, cnn_enc[0])

        if len(set(env.observation_space.keys()).intersection(set(mlp_enc + cnn_enc))) == 0:
            raise ValueError(
                f"The user specified keys `{mlp_enc + cnn_enc}` are not a subset of the environment "
                f"`{list(env.observation_space.keys())}` observation keys."
            )

        env_cnn_keys = {k for k in env.observation_space.spaces.keys() if len(env.observation_space[k].shape) in {2, 3}}
        cnn_keys = sorted(env_cnn_keys.intersection(set(cnn_enc)))
        env_mlp_keys = {k for k in env.observation_space.spaces.keys() if len(env.observation_space[k].shape) < 2}
        mlp_keys = sorted(env_mlp_keys.intersection(set(mlp_enc)))

        if cnn_keys:
            env = _TransformPixels(env, cnn_keys, cfg.env.screen_size, cfg.env.grayscale)
        if mlp_keys:
            env = _FloatVectorObs(env, mlp_keys)

        if cnn_keys and cfg.env.frame_stack > 1:
            if cfg.env.frame_stack_dilation <= 0:
                raise ValueError(
                    f"The frame stack dilation argument must be greater than zero, got: {cfg.env.frame_stack_dilation}"
                )
            env = FrameStack(env, cfg.env.frame_stack, cnn_keys, cfg.env.frame_stack_dilation)

        if cfg.env.actions_as_observation.num_stack > 0:
            env = ActionsAsObservationWrapper(env, **cfg.env.actions_as_observation)

        if cfg.env.reward_as_observation:
            env = RewardAsObservationWrapper(env)

        env.action_space.seed(seed)
        env.observation_space.seed(seed)
        if cfg.env.max_episode_steps and cfg.env.max_episode_steps > 0:
            env = gym.wrappers.TimeLimit(env, max_episode_steps=cfg.env.max_episode_steps)
        env = gym.wrappers.RecordEpisodeStatistics(env)
        if cfg.env.capture_video and rank == 0 and vector_env_idx == 0 and run_name is not None:
            if cfg.env.grayscale:
                env = GrayscaleRenderWrapper(env)
            video_dir = os.path.join(run_name, prefix + "_videos" if prefix else "videos")
            env = gym.wrappers.RecordVideo(env, video_dir, disable_logger=True)
        return env

    return thunk


def vectorize_env(
    cfg: Dict[str, Any],
    seed: int,
    rank: int,
    run_name: Optional[str] = None,
    prefix: str = "",
    restart_on_exception: bool = False,
):
    """Build the Sync/Async vector env with SAME_STEP autoreset
    (reference launch point: ``ppo.py:137-150``). The sync path uses
    :class:`sheeprl_tpu.envs.vector.FastSyncVectorEnv` (the env step is on
    the host critical path of every coupled main — see its docstring);
    ``restart_on_exception`` wraps each sub-env in
    :class:`~sheeprl_tpu.envs.wrappers.RestartOnException` (the long-run
    Dreamer/P2E mains, mirroring the reference's minedojo resilience)."""
    from functools import partial

    from gymnasium.vector import AsyncVectorEnv, AutoresetMode

    from sheeprl_tpu.envs.vector import FastSyncVectorEnv
    from sheeprl_tpu.envs.wrappers import RestartOnException

    thunks = [
        make_env(cfg, seed + rank * cfg.env.num_envs + i, rank, run_name, prefix=prefix, vector_env_idx=i)
        for i in range(cfg.env.num_envs)
    ]
    if restart_on_exception:
        thunks = [partial(RestartOnException, t) for t in thunks]
    if cfg.env.sync_env:
        # env.restart_attempts/step_timeout: per-worker self-healing (crash
        # retry with backoff + hang watchdog); the async path keeps
        # gymnasium's worker processes, where a crash already only kills the
        # worker.
        return FastSyncVectorEnv(
            thunks,
            autoreset_mode=AutoresetMode.SAME_STEP,
            restart_attempts=int(cfg.env.get("restart_attempts", 0) or 0),
            restart_backoff=float(cfg.env.get("restart_backoff", 0.5) or 0.0),
            step_timeout=cfg.env.get("step_timeout"),
        )
    # Workers come from a fork server, never from a fork of this process: by
    # now it has opened its JAX backend (threads, and on a chip machine
    # libtpu), and a forked copy of that deadlocks or fights for the chip.
    return AsyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP, context="forkserver")
