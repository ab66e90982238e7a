"""The token MDP's contract: reset, step, truncation, the terminal score and
the batched wrapper's auto-reset."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, is_jax_env, make_jax_env


def make(vocab=37984, prompt=16, response=4):
    return make_jax_env("TokenMDP-v0", vocab_size=vocab, prompt_len=prompt, response_len=response)


def test_registered_like_the_classic_control_envs():
    assert is_jax_env("TokenMDP-v0")
    env = make()
    assert env.observation_space.shape == (20,) and env.action_space.n == 37984


def test_reset_draws_a_prompt_from_the_vocabulary_and_is_seeded():
    env = make(vocab=50)
    state, obs = env.reset(jax.random.PRNGKey(3), env.default_params())
    again = env.reset(jax.random.PRNGKey(3), env.default_params())[1]
    other = env.reset(jax.random.PRNGKey(4), env.default_params())[1]
    np.testing.assert_array_equal(obs, again)
    assert not np.array_equal(obs, other)
    assert obs.dtype == jnp.int32 and int(obs[:16].max()) < 50 and not np.asarray(obs[16:]).any() and int(state.t) == 0


@pytest.mark.parametrize("same", ["the target", "another id of its parity"])
@pytest.mark.parametrize("right", [0, 2, 4])
def test_an_episode_is_response_len_actions_with_a_terminal_score(right, same):
    env = make()
    p = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(0), p)
    target = (np.asarray(state.tokens[12:16], np.int64) * int(p.mul) + int(p.add)) % 37984
    good = target if same == "the target" else (target + 2) % 37984  # 37984 is even: the parity holds around the end
    actions = np.where(np.arange(4) < right, good, (target + 1) % 37984)
    step = jax.jit(env.step)
    for t, a in enumerate(actions):
        state, obs, reward, done, info = step(state, jnp.int32(a), p)
        assert int(obs[16 + t]) == a and bool(done) == (t == 3) == bool(info["truncated"]) and not bool(info["terminated"])
        assert float(reward) == (right / 4 if t == 3 else 0.0)


def test_the_score_needs_the_prompts_tail():
    with pytest.raises(ValueError, match="response_len <= prompt_len"):
        make(prompt=4, response=8)


def test_a_policy_that_knows_nothing_scores_about_a_half():
    env = make(vocab=1000, prompt=64, response=64)
    p = env.default_params()
    keys = jax.random.split(jax.random.PRNGKey(5), 64)
    tokens = jax.vmap(lambda k: jax.random.randint(k, (128,), 0, 1000, jnp.int32))(keys)
    scores = np.asarray(jax.vmap(lambda t: env.score(t, p))(tokens))
    assert abs(scores.mean() - 0.5) < 0.03 and scores.std() > 0.02  # a reward in every episode, not the same in each


def test_batched_step_resets_at_the_episodes_end_and_keeps_the_final_tokens():
    env = make(vocab=64, prompt=8, response=2)
    benv = BatchedJaxEnv(env, 3)
    p = env.default_params()
    state, obs = benv.reset(jax.random.PRNGKey(1), p)
    first = np.asarray(obs)
    state, obs, _, done, _ = benv.step(state, jnp.array([1, 2, 3]), p)
    assert not np.asarray(done).any() and np.array_equal(np.asarray(obs[:, 8]), [1, 2, 3])
    state, obs, _, done, info = benv.step(state, jnp.array([4, 5, 6]), p)
    assert np.asarray(done).all()
    np.testing.assert_array_equal(info["final_obs"][:, :8], first[:, :8])
    np.testing.assert_array_equal(info["final_obs"][:, 8:], [[1, 4], [2, 5], [3, 6]])
    assert not np.asarray(obs[:, 8:]).any() and not np.array_equal(np.asarray(obs[:, :8]), first[:, :8])  # new prompts
