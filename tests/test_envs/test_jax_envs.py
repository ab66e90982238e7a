"""Pure-JAX env parity against gymnasium + auto-reset/vmap semantics.

Parity strategy: jax PRNG and numpy PRNG cannot produce the same reset
states, so the gymnasium twin is *state-synced* from the jax env at every
episode start (``env.unwrapped.state = ...``) and both are driven with the
same seeded action sequence. The jax envs compute in float32 vs gymnasium's
float64, so trace comparisons carry a small per-episode drift tolerance;
single-step checks (re-synced every step) are tight.
"""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.envs.jax_envs import (
    JAX_ENV_REGISTRY,
    BatchedJaxEnv,
    JaxAcrobot,
    JaxCartPole,
    JaxMountainCar,
    JaxPendulum,
    is_jax_env,
    make_jax_env,
)

TRACE_STEPS = 200


def test_registry():
    assert is_jax_env("CartPole-v1") and is_jax_env("Pendulum-v1") and is_jax_env("Acrobot-v1")
    assert is_jax_env("MountainCar-v0")
    assert not is_jax_env("MsPacmanNoFrameskip-v4")
    assert isinstance(make_jax_env("CartPole-v1"), JaxCartPole)
    assert isinstance(make_jax_env("Pendulum-v1"), JaxPendulum)
    assert isinstance(make_jax_env("Acrobot-v1"), JaxAcrobot)
    assert isinstance(make_jax_env("MountainCar-v0"), JaxMountainCar)
    with pytest.raises(ValueError, match="No pure-JAX environment"):
        make_jax_env("Walker2d-v4")


def test_register_jax_env_auto_discovery():
    """Adding an env is one ``@register_jax_env`` decorated module in the
    package: the package ``__init__`` auto-imports siblings and re-exports
    every registered class (no hand-maintained import list)."""
    import sheeprl_tpu.envs.jax_envs as pkg

    assert set(JAX_ENV_REGISTRY) >= {"CartPole-v1", "Pendulum-v1", "Acrobot-v1"}
    for cls in JAX_ENV_REGISTRY.values():
        # every registered env class is re-exported from the package
        assert getattr(pkg, cls.__name__) is cls
        assert cls.__name__ in pkg.__all__


def _sync_cartpole(genv, state):
    genv.unwrapped.state = np.asarray(state.physics, dtype=np.float64)


def _sync_pendulum(genv, state):
    genv.unwrapped.state = np.array([float(state.theta), float(state.theta_dot)], dtype=np.float64)


def test_cartpole_trace_parity():
    """Seeded 200-step trace: obs/reward/termination match gymnasium, with
    state re-sync (both PRNGs differ) at each episode start only."""
    jenv = JaxCartPole()
    genv = gym.make("CartPole-v1")
    genv.reset(seed=0)
    key = jax.random.PRNGKey(0)
    key, sub = jax.random.split(key)
    state, obs = jenv.reset(sub)
    _sync_cartpole(genv, state)
    rng = np.random.RandomState(1)
    for t in range(TRACE_STEPS):
        a = int(rng.randint(2))
        state, jobs, jr, jdone, jinfo = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-4, rtol=1e-4)
        assert float(jr) == float(gr) == 1.0
        assert bool(jinfo["terminated"]) == gterm
        assert bool(jdone) == (gterm or gtrunc)
        if jdone:
            key, sub = jax.random.split(key)
            state, obs = jenv.reset(sub)
            genv.reset()
            _sync_cartpole(genv, state)
    genv.close()


def test_cartpole_single_step_parity_tight():
    """Dynamics-exact check: re-sync every step, so no drift accumulates."""
    jenv = JaxCartPole()
    genv = gym.make("CartPole-v1")
    genv.reset(seed=0)
    state, _ = jenv.reset(jax.random.PRNGKey(7))
    rng = np.random.RandomState(2)
    for t in range(50):
        _sync_cartpole(genv, state)
        a = int(rng.randint(2))
        state, jobs, _, jdone, _ = jenv.step(state, jnp.asarray(a))
        gobs, _, gterm, _, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-5, rtol=1e-5)
        if jdone:
            state, _ = jenv.reset(jax.random.PRNGKey(100 + t))
            genv.reset()
    genv.close()


def test_pendulum_trace_parity():
    """200-step trace = exactly one episode (no termination, truncated at
    200). float32-vs-float64 drift bounds the tolerance."""
    jenv = JaxPendulum()
    genv = gym.make("Pendulum-v1")
    genv.reset(seed=0)
    state, obs = jenv.reset(jax.random.PRNGKey(3))
    _sync_pendulum(genv, state)
    rng = np.random.RandomState(3)
    for t in range(TRACE_STEPS):
        a = rng.uniform(-2, 2, size=(1,)).astype(np.float32)
        state, jobs, jr, jdone, jinfo = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=2e-2, rtol=2e-2)
        np.testing.assert_allclose(float(jr), float(gr), atol=5e-2)
        assert not bool(jinfo["terminated"]) and not gterm
        assert bool(jdone) == (gterm or gtrunc)
        assert bool(jdone) == (t == TRACE_STEPS - 1)
    genv.close()


def test_pendulum_single_step_parity_tight():
    jenv = JaxPendulum()
    genv = gym.make("Pendulum-v1")
    genv.reset(seed=0)
    state, _ = jenv.reset(jax.random.PRNGKey(4))
    rng = np.random.RandomState(4)
    for _ in range(50):
        _sync_pendulum(genv, state)
        a = rng.uniform(-2, 2, size=(1,)).astype(np.float32)
        state, jobs, jr, _, _ = jenv.step(state, jnp.asarray(a))
        gobs, gr, _, _, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(float(jr), float(gr), atol=1e-4)
    genv.close()


def _sync_acrobot(genv, state):
    genv.unwrapped.state = np.asarray(state.physics, dtype=np.float64)


def test_acrobot_trace_parity():
    """Seeded trace: obs/reward/termination match gymnasium with state
    re-sync at episode starts only. The double pendulum is chaotic, so f32
    vs f64 drift grows exponentially along an episode — the trace is kept
    short of the horizon where roundoff noise dominates, and the tolerance
    is looser than the single-step check below."""
    jenv = JaxAcrobot()
    genv = gym.make("Acrobot-v1")
    genv.reset(seed=0)
    key = jax.random.PRNGKey(6)
    key, sub = jax.random.split(key)
    state, obs = jenv.reset(sub)
    _sync_acrobot(genv, state)
    rng = np.random.RandomState(6)
    for t in range(60):
        a = int(rng.randint(3))
        state, jobs, jr, jdone, jinfo = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=2e-2, rtol=2e-2)
        assert float(jr) == float(gr)
        assert bool(jinfo["terminated"]) == gterm
        assert bool(jdone) == (gterm or gtrunc)
        if jdone:
            key, sub = jax.random.split(key)
            state, obs = jenv.reset(sub)
            genv.reset()
            _sync_acrobot(genv, state)
    genv.close()


def test_acrobot_single_step_parity_tight():
    """Dynamics-exact check: re-sync every step so no drift accumulates —
    one RK4 step in float32 must match gymnasium's float64 step tightly."""
    jenv = JaxAcrobot()
    genv = gym.make("Acrobot-v1")
    genv.reset(seed=0)
    state, _ = jenv.reset(jax.random.PRNGKey(8))
    rng = np.random.RandomState(8)
    for t in range(50):
        _sync_acrobot(genv, state)
        a = int(rng.randint(3))
        state, jobs, jr, jdone, _ = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, _, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-4, rtol=1e-4)
        assert float(jr) == float(gr)
        assert bool(jdone) == bool(gterm)  # no truncation inside 50 steps
        if jdone:
            state, _ = jenv.reset(jax.random.PRNGKey(200 + t))
            genv.reset()
            _sync_acrobot(genv, state)
    genv.close()


def test_acrobot_truncation_and_termination_reward():
    """-1 per step, 0 on the terminating step; the 500-step limit raises
    truncated, not terminated."""
    jenv = JaxAcrobot(max_episode_steps=5)
    state, _ = jenv.reset(jax.random.PRNGKey(0))
    for t in range(5):
        state, _, rew, done, info = jenv.step(state, jnp.asarray(1))
        if bool(info["terminated"]):
            assert float(rew) == 0.0
            pytest.skip("episode terminated before the tiny time limit")
        assert float(rew) == -1.0
        assert bool(info["truncated"]) == (t == 4)
        assert bool(done) == (t == 4)


def _sync_mountain_car(genv, state):
    genv.unwrapped.state = np.asarray(state.physics, dtype=np.float64)


def test_mountain_car_trace_parity():
    """Seeded 200-step trace (= one truncated episode under a random policy;
    the hill is essentially never escaped by chance): obs/reward/termination
    match gymnasium with state re-sync at episode starts only."""
    jenv = JaxMountainCar()
    genv = gym.make("MountainCar-v0")
    genv.reset(seed=0)
    key = jax.random.PRNGKey(9)
    key, sub = jax.random.split(key)
    state, obs = jenv.reset(sub)
    _sync_mountain_car(genv, state)
    rng = np.random.RandomState(9)
    for t in range(TRACE_STEPS):
        a = int(rng.randint(3))
        state, jobs, jr, jdone, jinfo = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-4, rtol=1e-4)
        assert float(jr) == float(gr) == -1.0
        assert bool(jinfo["terminated"]) == gterm
        assert bool(jdone) == (gterm or gtrunc)
        if jdone:
            key, sub = jax.random.split(key)
            state, obs = jenv.reset(sub)
            genv.reset()
            _sync_mountain_car(genv, state)
    genv.close()


def test_mountain_car_single_step_parity_tight():
    """Dynamics-exact check: re-sync every step so no drift accumulates —
    includes the left-wall inelastic velocity clamp and both clips."""
    jenv = JaxMountainCar()
    genv = gym.make("MountainCar-v0")
    genv.reset(seed=0)
    state, _ = jenv.reset(jax.random.PRNGKey(10))
    rng = np.random.RandomState(10)
    for t in range(50):
        _sync_mountain_car(genv, state)
        a = int(rng.randint(3))
        state, jobs, jr, jdone, _ = jenv.step(state, jnp.asarray(a))
        gobs, gr, gterm, _, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-5, rtol=1e-5)
        assert float(jr) == float(gr)
        assert not bool(jdone) and not gterm  # 50 random steps never reach the goal
    genv.close()


def test_mountain_car_left_wall_clamps_velocity():
    """Hitting the left wall at speed: position clips to min_position and the
    velocity zeroes (gymnasium's inelastic collision), it does not bounce.
    The state is synthesized at the wall — a random policy essentially never
    gets there (the engine is weaker than gravity), so the trace test above
    does not exercise this branch."""
    from sheeprl_tpu.envs.jax_envs.mountain_car import MountainCarState

    jenv = JaxMountainCar()
    genv = gym.make("MountainCar-v0")
    genv.reset(seed=0)
    state = MountainCarState(
        physics=jnp.asarray([-1.15, -0.07], jnp.float32), t=jnp.zeros((), jnp.int32)
    )
    _sync_mountain_car(genv, state)
    state, jobs, _, _, _ = jenv.step(state, jnp.asarray(0))  # keep pushing left
    gobs, _, _, _, _ = genv.step(0)
    assert float(jobs[0]) == pytest.approx(jenv.min_position)
    assert float(jobs[1]) == 0.0
    np.testing.assert_allclose(np.asarray(jobs), gobs, atol=1e-6)
    genv.close()


def test_truncation_flag_cartpole():
    """A time-limited CartPole sets truncated (not terminated) at the limit,
    mirroring gymnasium's TimeLimit."""
    jenv = JaxCartPole(max_episode_steps=5)
    state, _ = jenv.reset(jax.random.PRNGKey(0))
    for t in range(5):
        state, _, _, done, info = jenv.step(state, jnp.asarray(0))
        if bool(info["terminated"]):
            pytest.skip("episode terminated before the tiny time limit")
        assert bool(info["truncated"]) == (t == 4)
        assert bool(done) == (t == 4)


def test_batched_autoreset_matches_manual_key_stream():
    """BatchedJaxEnv == a hand-rolled per-env loop with the same key
    discipline, bitwise (same ops, same dtypes), including SAME_STEP
    auto-resets: on the done step the returned obs is the NEW episode's
    first obs and info['final_obs'] is the terminal obs."""
    N = 4
    raw = JaxCartPole(max_episode_steps=20)
    benv = BatchedJaxEnv(raw, N)
    master = jax.random.PRNGKey(11)
    bstate, bobs = benv.reset(master)

    # manual replica of the wrapper's key discipline
    keys = jax.random.split(master, N)
    man_state, man_obs, man_keys = [], [], []
    for i in range(N):
        k, sub = jax.random.split(keys[i])
        s, o = raw.reset(sub)
        man_keys.append(k)
        man_state.append(s)
        man_obs.append(o)
    np.testing.assert_array_equal(np.asarray(bobs), np.stack([np.asarray(o) for o in man_obs]))

    rng = np.random.RandomState(5)
    for t in range(60):
        acts = rng.randint(2, size=(N,))
        bstate, bobs, brew, bdone, binfo = benv.step(bstate, jnp.asarray(acts))
        for i in range(N):
            s2, o2, r2, d2, info2 = raw.step(man_state[i], jnp.asarray(acts[i]))
            assert float(brew[i]) == float(r2)
            assert bool(bdone[i]) == bool(d2)
            # terminal obs rides in final_obs on the done step
            np.testing.assert_array_equal(np.asarray(binfo["final_obs"][i]), np.asarray(o2))
            assert bool(binfo["terminated"][i]) == bool(info2["terminated"])
            assert bool(binfo["truncated"][i]) == bool(info2["truncated"])
            if bool(d2):
                k2, sub = jax.random.split(man_keys[i])
                man_state[i], o_reset = raw.reset(sub)
                man_keys[i] = k2
                np.testing.assert_array_equal(np.asarray(bobs[i]), np.asarray(o_reset))
            else:
                man_state[i] = s2
                np.testing.assert_array_equal(np.asarray(bobs[i]), np.asarray(o2))


def test_batched_shapes_and_spaces():
    for env_id, n in [("CartPole-v1", 3), ("Pendulum-v1", 2), ("Acrobot-v1", 2), ("MountainCar-v0", 2)]:
        raw = make_jax_env(env_id)
        benv = BatchedJaxEnv(raw, n)
        assert benv.single_observation_space == raw.observation_space
        assert benv.single_action_space == raw.action_space
        state, obs = jax.jit(benv.reset)(jax.random.PRNGKey(0))
        assert obs.shape == (n, *raw.observation_space.shape)
        if isinstance(raw.action_space, gym.spaces.Box):
            acts = jnp.zeros((n, *raw.action_space.shape), jnp.float32)
        else:
            acts = jnp.zeros((n,), jnp.int32)
        state, obs, rew, done, info = jax.jit(benv.step)(state, acts)
        assert obs.shape == (n, *raw.observation_space.shape)
        assert rew.shape == (n,) and done.shape == (n,)
        assert info["final_obs"].shape == obs.shape


# --------------------------------------------------------------------------- #
# Env-params pytrees (the scenario axis)
# --------------------------------------------------------------------------- #


def _rand_action(env, rng):
    if isinstance(env.action_space, gym.spaces.Box):
        return jnp.asarray(rng.uniform(-1, 1, size=env.action_space.shape).astype(np.float32))
    return jnp.asarray(int(rng.randint(env.action_space.n)))


@pytest.mark.parametrize("env_id", sorted(JAX_ENV_REGISTRY))
def test_default_params_round_trip(env_id):
    """Every registered env: ``default_params()`` is a flat NamedTuple of ()
    jnp scalars (float32 dynamics + int32 horizon), stepping with the
    default pytree passed EXPLICITLY matches stepping with ``params=None``
    bitwise, and the pytree is jit-stable — passing it as a traced argument
    to a jitted step compiles once and reproduces the eager result."""
    env = make_jax_env(env_id)
    params = env.default_params()
    assert isinstance(params, tuple) and hasattr(params, "_fields")
    for leaf in jax.tree.leaves(params):
        assert leaf.shape == () and leaf.dtype in (jnp.float32, jnp.int32)
    assert params.max_episode_steps.dtype == jnp.int32

    state, obs = env.reset(jax.random.PRNGKey(0), params)
    rng = np.random.RandomState(0)
    jstep = jax.jit(env.step)
    for it in range(10):
        a = _rand_action(env, rng)
        s_none, o_none, r_none, d_none, i_none = env.step(state, a)
        s_expl, o_expl, r_expl, d_expl, i_expl = env.step(state, a, params)
        # explicit default pytree == params=None, bitwise (same eager path)
        for a_leaf, b_leaf in zip(
            jax.tree.leaves((s_none, o_none, r_none, d_none, i_none)),
            jax.tree.leaves((s_expl, o_expl, r_expl, d_expl, i_expl)),
        ):
            np.testing.assert_array_equal(np.asarray(a_leaf), np.asarray(b_leaf))
        # the TRACED-params program reproduces eager within float32 ulp —
        # bitwise eager-vs-jit is NOT a contract (XLA fuses/reassociates),
        # which is exactly why the training blocks trace params everywhere
        # rather than splitting const-folded and traced programs
        s_jit, o_jit, r_jit, d_jit, i_jit = jstep(state, a, env.default_params())
        for a_leaf, b_leaf in zip(
            jax.tree.leaves((s_none, o_none, r_none, d_none, i_none)),
            jax.tree.leaves((s_jit, o_jit, r_jit, d_jit, i_jit)),
        ):
            np.testing.assert_allclose(np.asarray(a_leaf), np.asarray(b_leaf), rtol=1e-6, atol=1e-6)
        state = s_none
    # jit-stable pytree: 10 calls, each with a freshly built params pytree,
    # compiled exactly one program
    assert jstep._cache_size() == 1


@pytest.mark.parametrize("env_id", sorted(JAX_ENV_REGISTRY))
def test_params_vmapped_step_matches_single_steps(env_id):
    """The scenario axis contract: ``vmap``-ing ``step`` over a (P,)-stacked
    params pytree (same state/action per lane) equals P single-param steps.
    Bitwise is NOT asserted — vmapped reductions may reassociate at ulp
    level — but each lane must match its scalar twin to float32 tightness,
    and lanes with different dynamics must actually diverge."""
    env = make_jax_env(env_id)
    defaults = env.default_params()
    P = 3
    # scale the gravity constant across lanes (it feeds every env's velocity
    # update from any state, so lanes genuinely diverge); lane 0 = default
    scale = jnp.asarray([1.0, 1.35, 0.75], jnp.float32)
    vary = {"CartPole-v1": "gravity", "Pendulum-v1": "g"}.get(env_id, "gravity")
    if not hasattr(defaults, vary):
        pytest.skip(f"{env_id} has no continuous dynamics constant to sweep")
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (P,) + x.shape), defaults)
    stacked = stacked._replace(**{vary: getattr(defaults, vary) * scale})

    state, _ = env.reset(jax.random.PRNGKey(1), defaults)
    rng = np.random.RandomState(1)
    a = _rand_action(env, rng)
    vstep = jax.jit(jax.vmap(lambda p: env.step(state, a, p)))
    v_out = jax.device_get(vstep(stacked))
    for lane in range(P):
        p_lane = jax.tree.map(lambda x: x[lane], stacked)
        s_out = jax.device_get(env.step(state, a, p_lane))
        for a_leaf, b_leaf in zip(jax.tree.leaves(s_out), jax.tree.leaves(v_out)):
            np.testing.assert_allclose(
                np.asarray(a_leaf), np.asarray(b_leaf)[lane], rtol=1e-6, atol=1e-6
            )
    # different dynamics constants produce different physics
    obs_lanes = np.asarray(v_out[1])
    assert not np.array_equal(obs_lanes[0], obs_lanes[1])


def test_batched_env_params_vmapped_over_members():
    """A member axis of BatchedJaxEnv instances via ``vmap`` over the params
    pytree — exactly how the population block runs the scenario axis: each
    member's envs step under that member's dynamics row."""
    P, N = 3, 2
    env = make_jax_env("CartPole-v1")
    benv = BatchedJaxEnv(env, N)
    defaults = env.default_params()
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (P,) + x.shape), defaults)
    stacked = stacked._replace(length=defaults.length * jnp.asarray([1.0, 2.0, 0.5], jnp.float32))

    keys = jax.random.split(jax.random.PRNGKey(2), P)
    vreset = jax.jit(jax.vmap(benv.reset))
    state, obs = vreset(keys, stacked)
    assert obs.shape == (P, N, *env.observation_space.shape)
    acts = jnp.zeros((P, N), jnp.int32)
    vstep = jax.jit(jax.vmap(benv.step))
    state2, obs2, rew, done, info = vstep(state, acts, stacked)
    assert obs2.shape == (P, N, *env.observation_space.shape)
    # per-member single dispatch agrees with the vmapped member axis
    for m in range(P):
        p_m = jax.tree.map(lambda x: x[m], stacked)
        s_m, o_m = benv.reset(keys[m], p_m)
        s2_m, o2_m, r_m, d_m, _ = benv.step(s_m, acts[m], p_m)
        np.testing.assert_allclose(np.asarray(o2_m), np.asarray(obs2)[m], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(r_m), np.asarray(rew)[m], rtol=1e-6, atol=1e-6)
