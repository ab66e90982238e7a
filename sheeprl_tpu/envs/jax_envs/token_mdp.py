"""A token-level MDP: the environment a language-model policy acts in.

``reset`` draws a prompt of ``prompt_len`` token ids uniformly from the
vocabulary; an action is a token id, appended to the sequence; the episode
ends after ``response_len`` actions (``truncated``: there is no end-of-text
token, so every episode has the same length). The reward is zero until the
last step and there a programmatic score of prompt and response: the share of
response tokens of the same parity as a fixed affine permutation of the
prompt's tail (``target[i] = (a * prompt[P - R + i] + b) mod vocab``). A policy
that knows nothing scores a half, give or take, so every episode carries a
reward to learn from (were only the exact token to count, sampling from a
large vocabulary would never find one). No tokenizer, no dataset, no model:
the ids mean nothing beyond this rule.

The observation is the sequence so far, ``(prompt_len + response_len,)`` ids
with zeros where nothing has been written; a policy that keeps a cache reads
only the prompt (at reset) and its own last action.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.envs.jax_envs.base import JaxEnv, register_jax_env

__all__ = ["JaxTokenMDP", "TokenState", "TokenParams"]


class TokenState(NamedTuple):
    tokens: jax.Array  # (prompt_len + response_len,) int32
    t: jax.Array  # () int32 actions taken this episode


class TokenParams(NamedTuple):
    """The score's permutation ``x -> (mul * x + add) mod vocab``, and the
    horizon (as every env's params carry theirs)."""

    mul: jax.Array  # () int32, coprime to the vocabulary
    add: jax.Array  # () int32
    max_episode_steps: jax.Array  # () int32: the response's length


@register_jax_env("TokenMDP-v0")
class JaxTokenMDP(JaxEnv):
    def __init__(self, vocab_size: int = 64, prompt_len: int = 16, response_len: int = 8, max_episode_steps: int = 0):
        del max_episode_steps  # the episode's length is the response's
        self.vocab_size, self.prompt_len, self.response_len = int(vocab_size), int(prompt_len), int(response_len)
        if self.response_len > self.prompt_len:
            raise ValueError("the score reads the prompt's last response_len tokens: response_len <= prompt_len")

    @property
    def observation_space(self) -> gym.Space:
        return gym.spaces.Box(0, self.vocab_size - 1, (self.prompt_len + self.response_len,), np.int32)

    @property
    def action_space(self) -> gym.Space:
        return gym.spaces.Discrete(self.vocab_size)

    def default_params(self) -> TokenParams:
        mul = next(m for m in range(max(2, self.vocab_size // 3), 2 * self.vocab_size + 3) if math.gcd(m, self.vocab_size) == 1)
        return TokenParams(mul=jnp.int32(mul % self.vocab_size), add=jnp.int32(7 % self.vocab_size),
                           max_episode_steps=jnp.int32(self.response_len))

    def reset(self, key: jax.Array, params: TokenParams = None) -> Tuple[TokenState, jax.Array]:
        prompt = jax.random.randint(key, (self.prompt_len,), 0, self.vocab_size, jnp.int32)
        tokens = jnp.concatenate([prompt, jnp.zeros((self.response_len,), jnp.int32)])
        return TokenState(tokens=tokens, t=jnp.zeros((), jnp.int32)), tokens

    def score(self, tokens: jax.Array, params: TokenParams) -> jax.Array:
        P, R = self.prompt_len, self.response_len
        tail = tokens[P - R : P].astype(jnp.uint32)
        target = (params.mul.astype(jnp.uint32) * tail + params.add.astype(jnp.uint32)) % jnp.uint32(self.vocab_size)
        return jnp.mean((tokens[P:].astype(jnp.uint32) % 2 == target % 2).astype(jnp.float32))

    def step(
        self, state: TokenState, action: jax.Array, params: TokenParams = None
    ) -> Tuple[TokenState, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        p = params if params is not None else self.default_params()
        with jax.named_scope("env.token"):
            tokens = jax.lax.dynamic_update_slice(
                state.tokens, action.astype(jnp.int32)[None], (self.prompt_len + state.t,)
            )
            t = state.t + 1
            truncated = t >= p.max_episode_steps
            reward = jnp.where(truncated, self.score(tokens, p), 0.0).astype(jnp.float32)
        info = {"terminated": jnp.zeros((), bool), "truncated": truncated}
        return TokenState(tokens=tokens, t=t), tokens, reward, truncated, info
