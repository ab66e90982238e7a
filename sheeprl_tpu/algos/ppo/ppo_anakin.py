"""PPO — fully on-device (Anakin) training over pure-JAX envs.

The host-loop PPO (``ppo.py``) drives its rollout from Python: one jitted
policy dispatch plus a host↔device round-trip per env step, which caps the
CartPole benchmark at a few thousand env-steps/s with the TPU idle between
dispatches. Following the Podracer/Anakin architecture
(https://arxiv.org/pdf/2104.06272), when the environment itself is a JAX
function the ENTIRE iteration — rollout, bootstrap, GAE, ``update_epochs`` ×
minibatches — compiles into one XLA program:

- the env step is a :class:`~sheeprl_tpu.envs.jax_envs.BatchedJaxEnv`
  (``vmap`` over envs, SAME_STEP auto-reset in-graph);
- the rollout is a ``lax.scan`` over time inside the program — zero per-step
  dispatch;
- GAE reuses :func:`sheeprl_tpu.ops.gae`; the optimization phase reuses the
  SAME per-device epoch/minibatch machinery as the host loop
  (:func:`sheeprl_tpu.algos.ppo.ppo.make_local_train`) — identical sampling,
  loss and ``pmean`` semantics;
- the whole thing is one jitted ``shard_map`` over the ``dp`` mesh axis with
  ENVS sharded across devices (params replicated), wrapped in a
  multi-iteration ``lax.scan`` (a ``fori_loop`` with stacked per-iteration
  metric outputs) so host dispatch is amortized over a *block* of
  iterations. Episode returns/lengths and losses are ferried out once per
  block, sized to ``metric.log_every`` / ``checkpoint.every`` so logging and
  checkpoint cadence match the host loop's counter semantics.

Truncation handling matches the host loop: on a time-limit truncation the
reward is bootstrapped in-graph with ``gamma * V(final_obs)`` (the host loop
does the same from ``info["final_obs"]``), and GAE masks the terminal
bootstrap with ``done = terminated | truncated``.

Annealing (lr / clip / entropy coefficients) is applied at block granularity
rather than per iteration — identical when annealing is off (the default) and
a block-sized staircase of the same schedule otherwise.

Requires a registered pure-JAX env (``env.id`` in
``sheeprl_tpu.envs.jax_envs.JAX_ENV_REGISTRY``); arbitrary gymnasium envs
stay on the host-loop path.
"""

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

from jax.sharding import PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.ppo.agent import build_agent, sample_actions
from sheeprl_tpu.algos.ppo.ppo import make_local_train
from sheeprl_tpu.analysis.tracecheck import tracecheck
from sheeprl_tpu.algos.ppo.utils import test
from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, is_jax_env, make_jax_env
from sheeprl_tpu.ops import gae as gae_op
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

__all__ = [
    "main",
    "make_anakin_block",
    "make_anakin_local_block",
    "resolve_iters_per_block",
    "AnakinBlockCache",
]

#: per-block metric ferry budget in elements — bounds the stacked episode
#: arrays a single block dispatch ships back to the host
FERRY_ELEMS_BOUND = 1 << 24


def make_anakin_local_block(
    agent,
    tx,
    cfg,
    benv,
    local_envs: int,
    iters_per_block: int,
    obs_key: str,
    ferry_episodes: bool = True,
    guard: bool = False,
    population: bool = False,
):
    """Build the PER-DEVICE fused block body: ``iters_per_block`` × (rollout
    ``lax.scan`` → GAE → epoch/minibatch optimization). Must run inside a
    ``shard_map`` with a ``dp`` axis; :func:`make_anakin_block` wraps it for
    the single-run path, the population driver ``vmap``s it over a leading
    member axis first (``shard_map(vmap(local_block))``).

    ``population=True`` switches the per-run hyperparameters from baked-in
    Python constants to TRACED arguments — the signature grows
    ``(..., gamma, gae_lambda, env_params)`` after the loss coefficients — so
    ONE compile serves every (seed, hparam, scenario) member of a vmapped
    population, and adds a per-iteration ``fit`` metric (mean per-env
    raw-reward sum over the rollout, ``pmean``'d over ``dp``) as the in-graph
    per-scenario fitness the PBT selection step consumes. With
    ``population=False`` the signature grows only the trailing ``env_params``
    (gamma/gae_lambda stay folded constants).

    ``env_params`` is the env's dynamics-constants pytree and is TRACED on
    BOTH paths: XLA rewrites constant-parameter dynamics (reciprocal
    strength-reduction, folded sub-expressions) in ways a traced pytree
    can't follow, so baking defaults into the single-run program while the
    population traced them would break the P=1 bit-parity guarantee. A
    traced scenario costs a handful of loop-invariant scalar ops, hoisted
    out of the rollout scan.
    """
    T = int(cfg.algo.rollout_steps)
    cfg_gamma = float(cfg.algo.gamma)
    cfg_gae_lambda = float(cfg.algo.gae_lambda)
    is_continuous = agent.is_continuous
    n_heads = 1 if is_continuous else len(agent.actions_dim)
    # guard=True: NaN/Inf minibatches skip their update in graph and the
    # per-iteration skip count rides out with the block metrics ("bad") —
    # the only way to sentinel a fused multi-iteration program.
    local_train = make_local_train(agent, tx, cfg, T * local_envs, guard=guard)

    def local_block(params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_key, clip_coef, ent_coef, *hp):
        if population:
            gamma, gae_lambda, env_params = hp
        else:
            gamma, gae_lambda = cfg_gamma, cfg_gae_lambda
            (env_params,) = hp

        def rollout_step(carry, _):
            params, env_state, obs, ep_ret, ep_len, key = carry
            key, akey = jax.random.split(key)
            acts, logprob, value = sample_actions(agent, params, {obs_key: obs}, akey)
            if is_continuous:
                buf_action = jnp.concatenate(acts, axis=-1)
                env_action = buf_action
            else:
                buf_action = jnp.concatenate(acts, axis=-1)
                idx = jnp.stack([a.argmax(axis=-1) for a in acts], axis=-1)
                env_action = idx[..., 0] if n_heads == 1 else idx
            env_state, next_obs, reward, done, info = benv.step(env_state, env_action, env_params)

            # time-limit bootstrap, fused (host loop: rewards[trunc] += gamma *
            # V(final_obs)); cond-gated so the extra critic forward only runs on
            # the rare steps where some env actually hit the time limit
            truncated = info["truncated"]

            def bootstrap(r):
                v_final = agent.apply(params, {obs_key: info["final_obs"]})[1]
                return r + gamma * v_final[..., 0] * truncated.astype(jnp.float32)

            train_reward = jax.lax.cond(truncated.any(), bootstrap, lambda r: r, reward)

            ep_ret = ep_ret + reward
            ep_len = ep_len + 1
            y = {
                "obs": obs,
                "actions": buf_action,
                "logprobs": logprob,
                "values": value,
                "rewards": train_reward[..., None],
                "dones": done.astype(jnp.float32)[..., None],
            }
            if population:
                y["raw_rewards"] = reward
            if ferry_episodes:
                y["ep_done"] = done
                y["ep_ret"] = jnp.where(done, ep_ret, 0.0)
                y["ep_len"] = jnp.where(done, ep_len, 0)
            ep_ret = jnp.where(done, 0.0, ep_ret)
            ep_len = jnp.where(done, 0, ep_len)
            return (params, env_state, next_obs, ep_ret, ep_len, key), y

        def one_iter(carry, train_key):
            params, opt_state, env_state, obs, ep_ret, ep_len, env_key = carry
            (params, env_state, obs, ep_ret, ep_len, env_key), traj = jax.lax.scan(
                rollout_step, (params, env_state, obs, ep_ret, ep_len, env_key), None, length=T
            )
            next_value = agent.apply(params, {obs_key: obs})[1]
            returns, advantages = gae_op(
                traj["rewards"], traj["values"], traj["dones"], next_value, gamma=gamma, gae_lambda=gae_lambda
            )
            data = {
                obs_key: traj["obs"],
                "actions": traj["actions"],
                "logprobs": traj["logprobs"],
                "values": traj["values"],
                "returns": returns,
                "advantages": advantages,
            }
            data = {k: v.reshape(T * local_envs, *v.shape[2:]) for k, v in data.items()}
            outs = local_train(params, opt_state, data, train_key, clip_coef, ent_coef)
            params, opt_state, pg, v, ent = outs[:5]
            metrics = {"pg": pg, "v": v, "ent": ent}
            if guard:
                metrics["bad"] = outs[5]
            if population:
                # fitness: per-env raw-reward sum over this iteration's
                # rollout, averaged over envs and the mesh — defined for every
                # env (episodic or not) and monotone with episodic return
                metrics["fit"] = jax.lax.pmean(traj["raw_rewards"].sum(axis=0).mean(), "dp")
            if ferry_episodes:
                metrics.update(ep_done=traj["ep_done"], ep_ret=traj["ep_ret"], ep_len=traj["ep_len"])
            return (params, opt_state, env_state, obs, ep_ret, ep_len, env_key), metrics

        env_key = env_keys[0]
        train_keys = jax.random.split(train_key, iters_per_block)
        carry = (params, opt_state, env_state, obs, ep_ret, ep_len, env_key)
        carry, metrics = jax.lax.scan(one_iter, carry, train_keys)
        params, opt_state, env_state, obs, ep_ret, ep_len, env_key = carry
        return params, opt_state, env_state, obs, ep_ret, ep_len, env_key[None], metrics

    return local_block


def make_anakin_block(
    agent,
    tx,
    cfg,
    mesh,
    benv,
    local_envs: int,
    iters_per_block: int,
    obs_key: str,
    ferry_episodes: bool = True,
    guard: bool = False,
):
    """Build the jitted fused block: ``iters_per_block`` × (rollout ``lax.scan``
    → GAE → epoch/minibatch optimization) as ONE ``shard_map`` over ``dp``.

    Inputs/outputs sharded on ``dp``: env state pytree, observations and
    episode accumulators (leading env axis), per-device rollout keys.
    Replicated: params, optimizer state, the common train key (preserving
    ``buffer.share_data`` permutation semantics) and loss/coef scalars.

    ``ferry_episodes=False`` (``metric.log_level == 0``) drops the per-step
    episode arrays — ``(iters, T, num_envs)`` × 3 — from the program outputs,
    so a metrics-off run (the benchmark path) transfers only the per-iteration
    loss scalars per block.

    The trailing ``env_params`` input (the env's dynamics-constants pytree,
    replicated) is TRACED so the emitted dynamics match the population
    block's bit-for-bit — see :func:`make_anakin_local_block`.
    """
    local_block = make_anakin_local_block(
        agent, tx, cfg, benv, local_envs, iters_per_block, obs_key,
        ferry_episodes=ferry_episodes, guard=guard,
    )

    env_sharded = P("dp")
    metric_specs = {"pg": P(), "v": P(), "ent": P()}
    if guard:
        metric_specs["bad"] = P()
    if ferry_episodes:
        metric_specs.update(ep_done=P(None, None, "dp"), ep_ret=P(None, None, "dp"), ep_len=P(None, None, "dp"))
    shard_block = shard_map(
        local_block,
        mesh=mesh,
        in_specs=(P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded, P(), P(), P(), P()),
        out_specs=(P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded, metric_specs),
        check_vma=False,
    )
    # Pin the env-carried outputs to the driver's staging sharding: left to
    # inference, jit canonicalizes the shard_map's P("dp") outputs (e.g. to
    # P() on small meshes) — an EQUIVALENT placement but a different C++
    # jit-cache key, so the next block call (fed by this call's outputs)
    # silently recompiled the whole program: one abstract signature, two
    # compiles, no tracing-cache miss.
    from jax.sharding import NamedSharding

    env_out = NamedSharding(mesh, env_sharded)
    # params/opt_state are fed back too: pin their (replicated) placement as
    # well, so NO fed-back output's cache key is ever compiler-chosen (the
    # graft-audit AUD002 contract; metrics are consumed on host and stay
    # unconstrained)
    rep_out = NamedSharding(mesh, P())
    out_shardings = (rep_out, rep_out, env_out, env_out, env_out, env_out, env_out, None)
    return jax.jit(shard_block, donate_argnums=(0, 1, 2, 3, 4, 5, 6), out_shardings=out_shardings)


def resolve_iters_per_block(
    cfg,
    total_iters: int,
    policy_steps_per_iter: int,
    ferry_episodes: bool,
    population_size: int = 1,
) -> int:
    """Iterations fused per host dispatch: the log/checkpoint interval (so
    metrics surface exactly when the host loop would emit them), bounded by
    the per-block metric ferry budget.

    The ferry bound covers the stacked episode arrays — 3 arrays of
    ``(P, iters, T, num_envs)`` — so it divides by the POPULATION size too:
    a P-member block ships P× the episode metrics of a single run, and a
    bound that assumed scalar hparams (P == 1) would let a wide population
    queue gigabyte-scale device→host ferries per dispatch.
    """
    if cfg.algo.get("iters_per_block"):
        iters_per_block = int(cfg.algo.iters_per_block)
    else:
        intervals = []
        if cfg.metric.log_level > 0 and cfg.metric.log_every > 0:
            intervals.append(int(cfg.metric.log_every))
        if cfg.checkpoint.every > 0:
            intervals.append(int(cfg.checkpoint.every))
        interval = min(intervals) if intervals else cfg.algo.total_steps
        iters_per_block = max(1, int(interval) // policy_steps_per_iter)
    iters_per_block = max(1, min(iters_per_block, total_iters))
    if ferry_episodes:
        T = int(cfg.algo.rollout_steps)
        num_envs = int(cfg.env.num_envs)
        ferry_rows = max(1, T * num_envs * max(1, int(population_size)))
        iters_per_block = max(1, min(iters_per_block, FERRY_ELEMS_BOUND // ferry_rows))
    return iters_per_block


class AnakinBlockCache:
    """Per-block-length compile cache for the fused block.

    A run dispatches at most two distinct block lengths — the body length and
    the final remainder — and each compiled program is registered as the same
    tracecheck hot path, so the fused block must NEVER retrace past its own
    first compile. ``builder(n_iters)`` returns the jitted block for one
    length; the population driver passes its own builder (same contract, the
    member axis and traced hparams change the program, not the cache rule).
    """

    def __init__(self, builder, name: str, program: str = ""):
        self._builder = builder
        self._name = name
        self._program = program or name  # what the blocks are dispatched and registered with the recorder as
        self._fns: Dict[int, Any] = {}

    def __call__(self, n_iters: int):
        if n_iters not in self._fns:
            fn = self._builder(n_iters)
            self._fns[n_iters] = _RegisteredBlock(tracecheck.instrument(fn, name=self._name), fn, self.program_name(n_iters))
        return self._fns[n_iters]

    def program_name(self, n_iters: int) -> str:
        """The name a block of ``n_iters`` iterations is dispatched and registered under."""
        return f"{self._program}/{n_iters}"

    def __len__(self) -> int:
        return len(self._fns)


class _RegisteredBlock:
    """A jitted block whose executable the recorder can be asked for
    (``profiler.register_program``): its scope table joins a device trace's
    instruction names to the block's region names. The block is called as the
    ``jax.jit`` it is (``call``: the same, instrumented). Right after its first
    call ``fn`` is lowered once more for that call's abstract arguments, in the
    same context, which JAX answers from the trace and the lowering it has just
    made; the text, when asked for, is that lowering's executable, which is the
    call's: no second compile (``tests/test_algos/test_ppo_anakin_lm.py``)."""

    def __init__(self, call, fn, name: str):
        self._call, self._fn, self._name, self._lowered = call, fn, name, None

    def as_text(self) -> str:
        return self._lowered.compile().as_text()

    def __call__(self, *args):
        if self._lowered is not None:
            return self._call(*args)
        from sheeprl_tpu.utils import profiler

        # the arguments are donated: their shapes are taken before the call; an uncommitted one (a fresh key) is
        # lowered with no sharding of its own, as the call lowers it
        avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding if x.committed else None), args
        )
        out = self._call(*args)
        self._lowered = self._fn.lower(*avals)
        profiler.register_program(self._name, self)
        return out


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.fault import DivergenceSentinel, load_resume_state

    # algo.population.size > 1 turns the Anakin main into the vmapped
    # population driver (one dispatch trains the whole population); the
    # dedicated algo=ppo_anakin_population entry point lands there directly.
    pop_cfg = cfg.algo.get("population") or {}
    if int(pop_cfg.get("size") or 1) > 1:
        from sheeprl_tpu.algos.ppo.ppo_anakin_population import population_main

        return population_main(fabric, cfg)
    if pop_cfg.get("hparams"):
        warnings.warn(
            "algo.population.hparams is configured but algo.population.size is 1: the sweep is "
            "IGNORED and this trains one member at the run config's scalars. Set "
            "algo.population.size=P (or algo=ppo_anakin_population) to train the population.",
            UserWarning,
        )

    if jax.process_count() > 1:  # pragma: no cover - single-host subsystem
        raise NotImplementedError(
            "ppo_anakin ferries block metrics from a single controller; use the host-loop `algo=ppo` "
            "for multi-host runs."
        )
    from sheeprl_tpu.utils import profiler as recorder
    from sheeprl_tpu.utils.profiler import TraceProfiler

    # set-up up to the first `iter` (utils/profiler.py): env, agent, optimizer state, block builder
    setup_span = recorder.span("setup.build", parent=recorder.ROOT).start()

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)
    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    # Pure-JAX environment (the whole point: no host env in the hot path)
    if not is_jax_env(cfg.env.id):
        from sheeprl_tpu.envs.jax_envs import JAX_ENV_REGISTRY

        raise ValueError(
            f"algo=ppo_anakin requires a pure-JAX environment; '{cfg.env.id}' is not registered "
            f"(available: {sorted(JAX_ENV_REGISTRY)}). Use algo=ppo for host-loop training."
        )
    env_kwargs: Dict[str, Any] = {}
    if cfg.env.max_episode_steps and cfg.env.max_episode_steps > 0:
        env_kwargs["max_episode_steps"] = int(cfg.env.max_episode_steps)
    # algo.lm set: the policy is a language model and the env the token MDP
    # (ppo_anakin_lm.py builds both and the block; the loop below is shared)
    lm_cfg = cfg.algo.get("lm")
    if lm_cfg:
        env_kwargs.update(
            vocab_size=int(lm_cfg.vocab_held or lm_cfg.vocab_size), prompt_len=int(cfg.env.prompt_len),
            response_len=int(cfg.algo.rollout_steps),
        )
    jenv = make_jax_env(cfg.env.id, **env_kwargs)

    cnn_keys = list(cfg.algo.cnn_keys.encoder or [])
    mlp_keys = list(cfg.algo.mlp_keys.encoder or [])
    if cnn_keys or len(mlp_keys) != 1:
        raise ValueError(
            "ppo_anakin supports exactly one vector observation key (the classic-control JaxEnvs); got "
            f"cnn={cnn_keys} mlp={mlp_keys}"
        )
    obs_key = mlp_keys[0]
    observation_space = gym.spaces.Dict({obs_key: jenv.observation_space})

    is_continuous = isinstance(jenv.action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(jenv.action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        jenv.action_space.shape
        if is_continuous
        else (jenv.action_space.nvec.tolist() if is_multidiscrete else [jenv.action_space.n])
    )

    if lm_cfg:
        from sheeprl_tpu.algos.ppo import ppo_anakin_lm

        agent, params = ppo_anakin_lm.build_lm_agent(fabric, cfg, jenv, state["agent"] if state is not None else None)
        player = None  # no greedy test episode for a token policy
    else:
        agent, params, player = build_agent(
            fabric, actions_dim, is_continuous, cfg, observation_space,
            state["agent"] if state is not None else None,
        )

    from sheeprl_tpu.optim.builders import build_optimizer

    lr0 = float(cfg.algo.optimizer.lr)
    tx = optax.inject_hyperparams(
        lambda learning_rate: build_optimizer(
            {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
        )
    )(learning_rate=lr0)
    opt_state = tx.init(params)
    if state is not None:
        opt_state = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, state["optimizer"])
    opt_state = fabric.put_replicated(opt_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Envs sharded over the mesh (the Anakin layout: params replicated,
    # environments split across devices)
    num_envs = int(cfg.env.num_envs)
    world = fabric.world_size
    if num_envs % world != 0:
        raise ValueError(f"env.num_envs ({num_envs}) must be divisible by the number of devices ({world})")
    local_envs = num_envs // world
    T = int(cfg.algo.rollout_steps)

    # Counters (same convention as the host loop: policy steps advance by
    # num_envs per env step regardless of mesh size)
    policy_steps_per_iter = int(num_envs * T)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_step = state["iter_num"] * policy_steps_per_iter if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    train_step = 0
    last_train = 0
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"]

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    ferry_episodes = cfg.metric.log_level > 0
    iters_per_block = resolve_iters_per_block(cfg, total_iters, policy_steps_per_iter, ferry_episodes)

    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True))
    sentinel = DivergenceSentinel(sentinel_cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    rng = jax.random.PRNGKey(cfg.seed)
    rng, env_reset_key, rollout_root = jax.random.split(rng, 3)
    if state is not None and state.get("rng") is not None:
        rng = jnp.asarray(state["rng"])  # continue the killed run's stream
    # committed-replicated up front so the per-block eager split yields keys
    # already placed on the mesh (an uncommitted key would be replicated
    # implicitly INSIDE the guarded block dispatch)
    rng = fabric.put_replicated(rng)

    benv = BatchedJaxEnv(jenv, num_envs)
    # the env's dynamics constants, staged replicated ONCE and passed traced
    # into every block call (same buffer each call: stable jit cache key)
    env_params = fabric.put_replicated(jenv.default_params())
    env_state, first_obs = jax.jit(benv.reset)(env_reset_key, env_params)
    env_sharding = fabric.data_sharding
    env_state = jax.device_put(env_state, env_sharding)
    obs = jax.device_put(first_obs, env_sharding)
    ep_ret = jax.device_put(jnp.zeros((num_envs,), jnp.float32), env_sharding)
    ep_len = jax.device_put(jnp.zeros((num_envs,), jnp.int32), env_sharding)
    env_keys = jax.device_put(jax.random.split(rollout_root, world), env_sharding)

    minibatch = int(cfg.algo.per_rank_batch_size)
    if lm_cfg:
        build_block = lambda n_iters: ppo_anakin_lm.make_anakin_lm_block(  # noqa: E731
            agent, tx, cfg, fabric.mesh, benv, local_envs, n_iters, ferry_episodes=ferry_episodes, guard=guard,
        )
        get_block_fn = AnakinBlockCache(build_block, name="ppo_anakin_lm.block", program=ppo_anakin_lm.PROGRAM_NAME)
        minibatches = local_envs // minibatch  # of whole sequences
    else:
        build_block = lambda n_iters: make_anakin_block(  # noqa: E731
            agent, tx, cfg, fabric.mesh, benv, local_envs, n_iters, obs_key, ferry_episodes=ferry_episodes, guard=guard,
        )
        get_block_fn = AnakinBlockCache(build_block, name="ppo_anakin.block")
        minibatches = max(1, -(-T * local_envs // minibatch))
    grad_steps_per_iter = int(cfg.algo.update_epochs) * minibatches
    # the language-model block takes the gradient steps it may run an iteration as an input (all of them, here)
    block_extra = (fabric.put_replicated(jnp.asarray(grad_steps_per_iter, jnp.int32)),) if lm_cfg else ()

    lr = lr0
    clip_coef = float(cfg.algo.clip_coef)
    ent_coef = float(cfg.algo.ent_coef)

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir)
    setup_span.stop()

    iter_num = start_iter - 1
    while iter_num < total_iters:
        block_iters = min(iters_per_block, total_iters - iter_num)
        block_fn = get_block_fn(block_iters)
        profiler.tick(iter_num + 1)

        rng, train_key = jax.random.split(rng)
        # loss coefficients staged with ONE explicit replicated put each —
        # left uncommitted they would be replicated across the mesh
        # implicitly inside the guarded dispatch
        clip_arr = fabric.put_replicated(jnp.asarray(clip_coef, dtype=jnp.float32))
        ent_arr = fabric.put_replicated(jnp.asarray(ent_coef, dtype=jnp.float32))
        # host spans (utils/profiler.py): one `iter` per block, holding the block's dispatch
        # (named after the program it runs) and the wait for its metrics
        with timer("Time/train_time", SumMetric), recorder.span(
            "iter", parent=recorder.ROOT, iter_num=iter_num + 1, policy_step=policy_step,
            grad_steps=block_iters * grad_steps_per_iter,
        ) as iter_span:
            with recorder.span("burst.dispatch", program=get_block_fn.program_name(block_iters)):
                params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, metrics = block_fn(
                    params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_key,
                    clip_arr, ent_arr, env_params, *block_extra,
                )
            metrics = jax.device_get(metrics)
            # what the block counted of itself, for the record's readers (a block without the counter sets none)
            iter_span.set(**{k: int(np.sum(metrics[k])) for k in ("moe_compact_calls", "moe_compactable_calls",
                                                                    "moe_bias_moved", "moe_bias_movable")
                             if k in metrics})
            if "rollout_cache_bytes" in metrics:  # a size, the same every iteration of the block
                iter_span.set(rollout_cache_bytes=int(np.max(metrics["rollout_cache_bytes"])))

        # Host-side bookkeeping for the fused block, iteration by iteration
        # (same counters/cadence the host loop maintains per iteration)
        tripped = False
        for i in range(block_iters):
            iter_num += 1
            policy_step += policy_steps_per_iter
            train_step += 1
            if guard:
                # keep observing past a trip: counters stay accurate and a
                # streak spanning the whole block still reads as one streak
                tripped = sentinel.observe(metrics["bad"][i]) or tripped
            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/policy_loss", metrics["pg"][i])
                aggregator.update("Loss/value_loss", metrics["v"][i])
                aggregator.update("Loss/entropy_loss", metrics["ent"][i])
            if cfg.metric.log_level > 0:
                done_mask = np.asarray(metrics["ep_done"][i])
                if done_mask.any():
                    rets = np.asarray(metrics["ep_ret"][i])
                    lens = np.asarray(metrics["ep_len"][i])
                    ts, envs_idx = np.nonzero(done_mask)
                    for t_i, e_i in zip(ts, envs_idx):
                        if aggregator and "Rewards/rew_avg" in aggregator:
                            aggregator.update("Rewards/rew_avg", rets[t_i, e_i])
                        if aggregator and "Game/ep_len_avg" in aggregator:
                            aggregator.update("Game/ep_len_avg", lens[t_i, e_i])
                        print(f"Rank-0: policy_step={policy_step}, reward_env_{e_i}={rets[t_i, e_i]}")

        if tripped:
            def _rollback(good):
                nonlocal params, opt_state, rng
                params = fabric.put_replicated(
                    jax.tree.map(lambda t, s: jnp.asarray(s), params, good["agent"])
                )
                opt_state = fabric.put_replicated(
                    jax.tree.map(
                        lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, good["optimizer"]
                    )
                )
                if good.get("rng") is not None:
                    # committed-replicated like the launch-time staging: an
                    # uncommitted key would re-enter the guarded dispatch as
                    # an implicit transfer + sharding-level recompile
                    rng = fabric.put_replicated(jnp.asarray(good["rng"]))

            sentinel.recover(ckpt_dir, _rollback)

        if cfg.metric.log_level > 0:
            logger.log_dict({"Info/learning_rate": lr, "Info/clip_coef": clip_coef, "Info/ent_coef": ent_coef}, policy_step)
            if guard and sentinel.total_skipped:
                logger.log_dict({"Fault/skipped_updates": sentinel.total_skipped}, policy_step)
            if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                if aggregator and not aggregator.disabled:
                    logger.log_dict(aggregator.compute(), policy_step)
                    aggregator.reset()
                if not timer.disabled:
                    timer_metrics = timer.compute()
                    if timer_metrics.get("Time/train_time", 0) > 0:
                        logger.log_dict(
                            {
                                "Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"],
                                "Time/sps_env_interaction": (policy_step - last_log) / timer_metrics["Time/train_time"],
                            },
                            policy_step,
                        )
                    timer.reset()
                last_log = policy_step
                last_train = train_step

        # Annealing at block granularity (identical when annealing is off)
        if cfg.algo.anneal_lr:
            lr = polynomial_decay(iter_num, initial=lr0, final=0.0, max_decay_steps=total_iters, power=1.0)
            # staged replicated like the initial opt_state: an uncommitted
            # scalar here would flip the input's committed-ness next call
            # (sharding-level cache miss) and transfer inside the dispatch
            opt_state.hyperparams["learning_rate"] = fabric.put_replicated(jnp.asarray(lr, dtype=jnp.float32))
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "scheduler": None,
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng": rng,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state)

    profiler.close()
    if fabric.is_global_zero and cfg.algo.run_test and player is not None:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import register_model

        from sheeprl_tpu.algos.ppo.utils import log_models

        register_model(fabric, log_models, cfg, {"agent": params})
    logger.close()


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


def audit_anakin_setup(spec: AuditMesh, pop_size: int = 1):
    """Tiny CartPole Anakin context on the audit mesh: agent + env avals
    staged EXACTLY like the driver (envs sharded over ``dp`` — under the
    member axis when ``pop_size > 1``). Shared with the population twin."""
    import optax as _optax

    from sheeprl_tpu.algos.ppo.agent import PPOAgent
    from sheeprl_tpu.algos.ppo.ppo import _abstract_like
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from jax.sharding import NamedSharding

    mesh = spec.build()
    num_envs = 2 * spec.devices
    cfg = compose(
        [
            "exp=ppo_anakin",
            "env.id=CartPole-v1",
            f"env.num_envs={num_envs}",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=8",
            "algo.update_epochs=1",
        ]
    )
    agent = PPOAgent(
        actions_dim=(2,),
        is_continuous=False,
        cnn_keys=(),
        mlp_keys=("state",),
        encoder_cfg=dict(cfg.algo.encoder),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
    )
    params = agent.init(jax.random.PRNGKey(0), {"state": jnp.zeros((num_envs, 4), jnp.float32)})
    tx = _optax.inject_hyperparams(
        lambda learning_rate: build_optimizer(
            {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
        )
    )(learning_rate=float(cfg.algo.optimizer.lr))
    opt_state = tx.init(params)

    jenv = make_jax_env("CartPole-v1")
    benv = BatchedJaxEnv(jenv, num_envs)
    rep = NamedSharding(mesh, P())
    defaults = jenv.default_params()
    env_params_a = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((pop_size,) if pop_size > 1 else (), jnp.result_type(x), sharding=rep),
        defaults,
    )
    if pop_size > 1:
        env_sh = NamedSharding(mesh, P(None, "dp"))
        env_state_avals, obs_avals = jax.eval_shape(
            jax.vmap(benv.reset), jax.random.split(jax.random.PRNGKey(1), pop_size)
        )
        stack = lambda x: jax.ShapeDtypeStruct((pop_size, *jnp.shape(x)), jnp.result_type(x), sharding=rep)
        params_a = jax.tree.map(stack, params)
        opt_a = jax.tree.map(stack, opt_state)
        ep_ret = jax.ShapeDtypeStruct((pop_size, num_envs), jnp.float32, sharding=env_sh)
        ep_len = jax.ShapeDtypeStruct((pop_size, num_envs), jnp.int32, sharding=env_sh)
        env_keys = jax.ShapeDtypeStruct((pop_size, spec.devices, 2), jnp.uint32, sharding=env_sh)
    else:
        env_sh = NamedSharding(mesh, P("dp"))
        env_state_avals, obs_avals = jax.eval_shape(benv.reset, jax.random.PRNGKey(1))
        params_a = _abstract_like(params, rep)
        opt_a = _abstract_like(opt_state, rep)
        ep_ret = jax.ShapeDtypeStruct((num_envs,), jnp.float32, sharding=env_sh)
        ep_len = jax.ShapeDtypeStruct((num_envs,), jnp.int32, sharding=env_sh)
        env_keys = jax.ShapeDtypeStruct((spec.devices, 2), jnp.uint32, sharding=env_sh)
    reshard = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=env_sh)
    return {
        "cfg": cfg,
        "agent": agent,
        "tx": tx,
        "mesh": mesh,
        "benv": benv,
        "num_envs": num_envs,
        "local_envs": num_envs // spec.devices,
        "rep": rep,
        "env_sh": env_sh,
        "params": params_a,
        "opt_state": opt_a,
        "env_state": jax.tree.map(reshard, env_state_avals),
        "obs": jax.tree.map(reshard, obs_avals),
        "ep_ret": ep_ret,
        "ep_len": ep_len,
        "env_keys": env_keys,
        "env_params": env_params_a,
    }


@register_audit_programs("ppo_anakin.block")
def _audit_programs(spec: AuditMesh):
    s = audit_anakin_setup(spec)
    iters = 2
    fn = make_anakin_block(
        s["agent"], s["tx"], s["cfg"], s["mesh"], s["benv"], s["local_envs"], iters,
        "state", ferry_episodes=True, guard=True,
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=s["rep"])
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=s["rep"])
    yield AuditProgram(
        name="ppo_anakin.block",
        fn=fn,
        args=(
            s["params"], s["opt_state"], s["env_state"], s["obs"], s["ep_ret"], s["ep_len"],
            s["env_keys"], key, scalar, scalar, s["env_params"],
        ),
        source=__name__,
        donate_argnums=(0, 1, 2, 3, 4, 5, 6),
        feedback_outputs=(0, 1, 2, 3, 4, 5, 6),
        out_decl={0: P(), 1: P(), 2: P("dp"), 3: P("dp"), 4: P("dp"), 5: P("dp"), 6: P("dp")},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )
