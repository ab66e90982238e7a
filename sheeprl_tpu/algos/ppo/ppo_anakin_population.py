"""PPO Anakin — vmapped POPULATION training: P (seed, hyperparameter,
scenario) members in ONE jitted dispatch.

``ppo_anakin`` fuses pure-JAX envs + rollout + GAE + optimization into one
jitted ``shard_map`` block, but one process trains one run: a P-member sweep
pays P× dispatch overhead and P× compiles while the chip idles between tiny
per-member matmuls. Podracer's Anakin design (arXiv 2104.06272) is exactly
"``vmap`` the entire agent over a population axis" — this module does that to
the whole fused block:

- per-member param / optimizer / env-state pytrees stacked on axis 0, envs
  sharded over ``dp`` UNDER the population axis (each device holds
  ``P × num_envs/D`` environments);
- per-member hyperparameters (``lr``, ``clip_coef``, ``ent_coef``, ``gamma``,
  ``gae_lambda``) carried as TRACED ``(P,)`` arrays — one compile serves every
  member, and the host-side annealing staircase broadcasts per-member as a
  traced fraction;
- per-member RNG streams split from one root key (init, env reset, rollout
  and train streams all member-indexed);
- per-member block metrics (losses + an in-graph fitness scalar) ferried out
  once per block for selection and ``Population/*`` reporting;
- an OPTIONAL in-graph PBT step at block granularity
  (``algo.population.pbt``): truncation selection — the bottom-q members copy
  the top-q members' params+optimizer state and inherit perturbed
  hyperparameters — fully deterministic under the population key and
  ``lax.cond``-gated, so sweep-only runs pay nothing.

Sweep specification (``algo.population.hparams.*``): each entry is a constant
(broadcast), a list of ``choices``, or a ``{low, high, log}`` range.
``sweep=grid`` takes the cartesian product of the choices (must equal
``size``); ``sweep=random`` draws per member, deterministically from
``cfg.seed``.

SCENARIO matrix (``algo.population.env_params.*``): the same spec schema
applied to the env's dynamics-constants pytree
(``JaxEnv.default_params()`` fields — gravity, masses, lengths, the
TimeLimit bound, ...). The resolved ``(P,)``-stacked params pytree rides
next to ``hparams`` as a TRACED block input and the population block vmaps
over it: one compiled dispatch steps P distinct env variants, and the
per-member ``fit`` output becomes per-SCENARIO fitness. ``sweep=grid``
takes one cartesian product across hparams AND env params (joint size must
equal ``size``); ``sweep=random`` keys each env param's stream by
``(seed, "env_params.<name>")`` so adding a param — env or hparam — never
reshuffles another's draws. PBT moves a member's scenario only when
``algo.population.pbt.perturb_env_params=true`` (default off: selection
copies weights INTO a scenario, it must not silently mutate the scenario a
member is being scored on).

Counter semantics: ``algo.total_steps`` / ``policy_step`` count PER-MEMBER
env steps (identical to a single ``ppo_anakin`` run at the same config), so
log/checkpoint cadence and learning curves stay comparable; aggregate
throughput is P× the reported per-member rate. Checkpoints hold the WHOLE
population (member-indexed leaves in one manifest entry) plus every RNG
stream and the per-member hyperparameters; ``resume_from=latest`` restores
all of it.
"""

from __future__ import annotations

import copy
import itertools
import os
import zlib
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.ppo.agent import build_agent
from sheeprl_tpu.algos.ppo.ppo_anakin import (
    AnakinBlockCache,
    make_anakin_local_block,
    resolve_iters_per_block,
)
from sheeprl_tpu.algos.ppo.utils import test
from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, is_jax_env, make_jax_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

__all__ = [
    "main",
    "population_main",
    "make_population_block",
    "resolve_sweep",
    "resolve_matrix",
    "resolve_pbt",
    "HPARAM_KEYS",
    "PBTConfig",
]

#: hyperparameters that may vary per member (everything else is shared —
#: member programs must stay shape/structure-identical under vmap)
HPARAM_KEYS = ("lr", "clip_coef", "ent_coef", "gamma", "gae_lambda")

#: post-perturbation clamp: discount-style hparams must stay in (0, 1)
_PERTURB_BOUNDS = {"gamma": (1e-3, 0.9999), "gae_lambda": (1e-3, 1.0)}


class PBTConfig(NamedTuple):
    """Resolved in-graph PBT parameters (static: part of the compiled block)."""

    num_copy: int  # q — bottom-q members copy top-q members
    perturb: Tuple[str, ...]  # hparam names perturbed on copy
    factors: Tuple[float, ...]  # multiplicative perturbation choices
    #: env-param fields inherited + perturbed on copy; EMPTY means the env
    #: params never move (default: selection must not silently mutate the
    #: scenario a member is scored on — perturb_env_params=true opts in)
    env_perturb: Tuple[str, ...] = ()


def _base_hparams(cfg) -> Dict[str, float]:
    return {
        "lr": float(cfg.algo.optimizer.lr),
        "clip_coef": float(cfg.algo.clip_coef),
        "ent_coef": float(cfg.algo.ent_coef),
        "gamma": float(cfg.algo.gamma),
        "gae_lambda": float(cfg.algo.gae_lambda),
    }


def _spec_kind(spec: Any) -> Tuple[str, Any]:
    """Classify one sweep-spec entry: const | choices | range."""
    if isinstance(spec, (int, float)):
        return "const", float(spec)
    if isinstance(spec, (list, tuple)):
        return "choices", [float(v) for v in spec]
    if isinstance(spec, dict) or hasattr(spec, "keys"):
        if "choices" in spec:
            return "choices", [float(v) for v in spec["choices"]]
        if "low" in spec and "high" in spec:
            low, high = float(spec["low"]), float(spec["high"])
            log = bool(spec.get("log", False))
            if not (high >= low):
                raise ValueError(f"sweep range must have high >= low, got low={low} high={high}")
            if log and low <= 0:
                raise ValueError(f"log-uniform sweep range requires low > 0, got {low}")
            return "range", (low, high, log)
    raise ValueError(
        f"Unsupported sweep spec {spec!r}: expected a scalar, a list of choices, "
        "{choices: [...]}, or {low: .., high: .., log: bool}"
    )


def resolve_matrix(
    cfg, size: int, seed: int, env=None
) -> Tuple[Dict[str, np.ndarray], Tuple[str, ...], Dict[str, np.ndarray], Tuple[str, ...]]:
    """Jointly resolve ``algo.population.hparams`` AND
    ``algo.population.env_params`` into per-member ``(P,)`` arrays,
    deterministically under ``seed``.

    Returns ``(hparams, swept, env_params, env_swept)``: ``hparams`` maps
    every :data:`HPARAM_KEYS` entry to a ``(P,)`` float32 array, ``env_params``
    maps every field of ``env.default_params()`` to a ``(P,)`` array in the
    field's dtype (defaults broadcast; empty dict when ``env`` is ``None``),
    and the ``*swept`` tuples name the entries that actually vary (the
    default PBT perturbation sets).

    - ``sweep=grid``: ONE cartesian product across hparam and env-param
      ``choices`` axes — hparams first (``HPARAM_KEYS`` order), then env
      params in ``default_params()`` field order; the joint product must
      equal ``size`` exactly (ranges are rejected — a grid needs discrete
      points);
    - ``sweep=random``: each entry draws independently — choices uniformly,
      ranges uniform or log-uniform — from a stream keyed by ``(seed, name)``
      for hparams and ``(seed, "env_params.<name>")`` for env params, so the
      draw for one entry never shifts when another is added.

    Integer env-param fields (e.g. ``max_episode_steps``) round to the
    field's dtype after drawing.
    """
    pop_cfg = cfg.algo.get("population") or {}
    mode = str(pop_cfg.get("sweep", "grid")).lower()
    if mode not in ("grid", "random"):
        raise ValueError(f"algo.population.sweep must be 'grid' or 'random', got {mode!r}")
    spec_map = dict(pop_cfg.get("hparams") or {})
    unknown = sorted(set(spec_map) - set(HPARAM_KEYS))
    if unknown:
        raise ValueError(f"Unknown population hparam(s) {unknown}; supported: {list(HPARAM_KEYS)}")
    env_spec_map = dict(pop_cfg.get("env_params") or {})
    if env_spec_map and env is None:
        raise ValueError(
            "algo.population.env_params is configured but no pure-JAX env was provided to resolve "
            "its params pytree against; scenario sweeps need the JaxEnv instance"
        )

    base = _base_hparams(cfg)
    out = {k: np.full((size,), base[k], dtype=np.float32) for k in HPARAM_KEYS}
    env_out: Dict[str, np.ndarray] = {}
    env_dtypes: Dict[str, np.dtype] = {}
    env_fields: Tuple[str, ...] = ()
    if env is not None:
        defaults = env.default_params()
        env_fields = tuple(defaults._fields)
        unknown = sorted(set(env_spec_map) - set(env_fields))
        if unknown:
            raise ValueError(
                f"Unknown env param(s) {unknown} for '{env.id}'; "
                f"default_params() fields: {list(env_fields)}"
            )
        for f in env_fields:
            leaf = np.asarray(jax.device_get(getattr(defaults, f)))
            env_dtypes[f] = leaf.dtype
            env_out[f] = np.full((size,), leaf, dtype=leaf.dtype)

    def _env_cast(name: str, vals) -> np.ndarray:
        dt = env_dtypes[name]
        arr = np.asarray(vals, dtype=np.float64)
        return np.round(arr).astype(dt) if np.issubdtype(dt, np.integer) else arr.astype(dt)

    swept: List[str] = []
    env_swept: List[str] = []

    # one declared axis list spanning both spaces: hparams first (HPARAM_KEYS
    # order), then env params in field order — stable and seed-independent
    axes = [("hp", n, spec_map[n]) for n in HPARAM_KEYS if n in spec_map]
    axes += [("env", n, env_spec_map[n]) for n in env_fields if n in env_spec_map]

    if mode == "grid":
        grid_axes: List[Tuple[str, str, List[float]]] = []
        for space, name, spec in axes:
            kind, val = _spec_kind(spec)
            if kind == "const":
                if space == "hp":
                    out[name][:] = val
                else:
                    env_out[name][:] = _env_cast(name, val)
            elif kind == "range":
                raise ValueError(
                    f"sweep=grid cannot expand the range spec for '{name}'; list explicit choices "
                    "or use sweep=random"
                )
            else:
                grid_axes.append((space, name, val))
        if grid_axes:
            points = list(itertools.product(*(vals for _, _, vals in grid_axes)))
            if len(points) != size:
                raise ValueError(
                    f"sweep=grid: the cartesian product of choices has {len(points)} points "
                    f"({' x '.join(f'{n}[{len(v)}]' for _, n, v in grid_axes)}) but "
                    f"algo.population.size={size}; make them equal (hparam and env_params axes "
                    "share ONE grid)"
                )
            for i, point in enumerate(points):
                for (space, name, _), v in zip(grid_axes, point):
                    if space == "hp":
                        out[name][i] = v
                    else:
                        env_out[name][i] = _env_cast(name, v)
            swept = [n for s, n, _ in grid_axes if s == "hp"]
            env_swept = [n for s, n, _ in grid_axes if s == "env"]
    else:
        for space, name, spec in axes:
            kind, val = _spec_kind(spec)
            # stream keyed by (seed, name) — env params under an
            # "env_params." prefix so a field named like an hparam gets its
            # own stream: adding one entry never reshuffles another's draws,
            # and the draw is platform-independent
            stream = name if space == "hp" else f"env_params.{name}"
            rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, zlib.crc32(stream.encode())])
            if kind == "const":
                draw = None
            elif kind == "choices":
                draw = rng.choice(np.asarray(val, dtype=np.float64), size=size)
            else:
                low, high, log = val
                if log:
                    draw = np.exp(rng.uniform(np.log(low), np.log(high), size=size))
                else:
                    draw = rng.uniform(low, high, size=size)
            if space == "hp":
                if draw is None:
                    out[name][:] = val
                else:
                    out[name][:] = draw.astype(np.float32)
                    swept.append(name)
            else:
                if draw is None:
                    env_out[name][:] = _env_cast(name, val)
                else:
                    env_out[name][:] = _env_cast(name, draw)
                    env_swept.append(name)

    return out, tuple(swept), env_out, tuple(env_swept)


def resolve_sweep(cfg, size: int, seed: int) -> Tuple[Dict[str, np.ndarray], Tuple[str, ...]]:
    """Hparam-only view of :func:`resolve_matrix` (kept for callers that
    sweep no env params)."""
    hparams, swept, _, _ = resolve_matrix(cfg, size, seed, env=None)
    return hparams, swept


def resolve_pbt(
    cfg, size: int, swept: Tuple[str, ...], env_swept: Tuple[str, ...] = ()
) -> Tuple[Optional[PBTConfig], int]:
    """Resolve ``algo.population.pbt`` into the static :class:`PBTConfig`
    (or ``None`` when disabled) plus the host-side block cadence.

    ``perturb_env_params`` (default ``false``) gates whether selection also
    copies + perturbs the SWEPT env params: off, a replaced member keeps its
    scenario and only the weights/optimizer/hparams move (curriculum
    semantics); on, the scenario rides along like any other hyperparameter.
    """
    pbt_cfg = (cfg.algo.get("population") or {}).get("pbt") or {}
    if not bool(pbt_cfg.get("enabled", False)):
        return None, 0
    if size < 2:
        raise ValueError(f"PBT needs algo.population.size >= 2, got {size}")
    frac = float(pbt_cfg.get("truncation_frac", 0.25))
    if not 0.0 < frac <= 0.5:
        raise ValueError(f"algo.population.pbt.truncation_frac must be in (0, 0.5], got {frac}")
    q = max(1, int(size * frac))
    if 2 * q > size:
        raise ValueError(
            f"PBT truncation copies the top {q} over the bottom {q} members, but 2*{q} > size={size}; "
            "lower truncation_frac"
        )
    perturb = pbt_cfg.get("perturb")
    perturb = tuple(perturb) if perturb is not None else tuple(swept)
    unknown = sorted(set(perturb) - set(HPARAM_KEYS))
    if unknown:
        raise ValueError(f"Unknown pbt.perturb hparam(s) {unknown}; supported: {list(HPARAM_KEYS)}")
    factors = tuple(float(f) for f in (pbt_cfg.get("perturb_factors") or (0.8, 1.25)))
    if not factors or any(f <= 0 for f in factors):
        raise ValueError(f"pbt.perturb_factors must be positive multipliers, got {factors}")
    every = int(pbt_cfg.get("every_blocks", 1))
    if every < 1:
        raise ValueError(f"pbt.every_blocks must be >= 1, got {every}")
    env_perturb: Tuple[str, ...] = ()
    if bool(pbt_cfg.get("perturb_env_params", False)):
        env_perturb = tuple(env_swept)
    return PBTConfig(num_copy=q, perturb=perturb, factors=factors, env_perturb=env_perturb), every


def _with_lr(opt_state, lr):
    """Return ``opt_state`` with the injected learning-rate hyperparameter
    replaced (the per-member lr rides INSIDE the stacked optimizer state, so
    ``optax.inject_hyperparams`` applies it per member under vmap)."""
    hp = dict(opt_state.hyperparams)
    hp["learning_rate"] = lr
    return opt_state._replace(hyperparams=hp)


def make_pbt_step(pop_size: int, pbt: PBTConfig):
    """Build the in-graph truncation-selection step.

    ``(params, opt_state, hparams, env_params, fitness, key) -> (params,
    opt_state, hparams, env_params)``: members are ranked by fitness (stable
    argsort — equal fitness preserves member order, so an all-identical
    population maps onto itself); the bottom-q members copy the top-q
    members' params AND optimizer state and inherit their hyperparameters,
    multiplied — for the configured ``perturb`` set — by a factor drawn per
    (member, hparam) from ``perturb_factors`` under ``key``. ``env_params``
    passes through UNTOUCHED unless ``pbt.env_perturb`` names fields
    (``perturb_env_params=true``): those are inherited and perturbed exactly
    like hparams (integer fields round to their dtype, clamped >= 1).
    Everything is a gather/where on the member axis: shapes are static, the
    step is deterministic under the key, and it compiles once inside the
    block dispatch's ``lax.cond``.
    """
    q = int(pbt.num_copy)
    factors = jnp.asarray(pbt.factors, dtype=jnp.float32)

    def pbt_step(operand):
        params, opt_state, hparams, env_params, fitness, key = operand
        order = jnp.argsort(-fitness, stable=True)  # descending fitness
        src = order[:q]
        dst = order[pop_size - q:]
        member_map = jnp.arange(pop_size).at[dst].set(src)
        replaced = jnp.zeros((pop_size,), bool).at[dst].set(True)

        def take(x):
            return jnp.take(x, member_map, axis=0)

        params = jax.tree.map(take, params)
        opt_state = jax.tree.map(take, opt_state)
        new_hparams = {}
        for i, name in enumerate(HPARAM_KEYS):
            h = take(hparams[name])  # inherit the source member's value
            if name in pbt.perturb:
                fkey = jax.random.fold_in(key, i)
                f = factors[jax.random.randint(fkey, (pop_size,), 0, factors.shape[0])]
                h = h * f
                if name in _PERTURB_BOUNDS:
                    lo, hi = _PERTURB_BOUNDS[name]
                    h = jnp.clip(h, lo, hi)
            new_hparams[name] = jnp.where(replaced, h, hparams[name])
        if pbt.env_perturb:
            # the scenario rides along: swept env params inherit + perturb;
            # the rest are population-constant so a gather is a no-op
            new_fields = {}
            for j, name in enumerate(type(env_params)._fields):
                h = getattr(env_params, name)
                if name not in pbt.env_perturb:
                    new_fields[name] = h
                    continue
                taken = take(h)
                fkey = jax.random.fold_in(key, len(HPARAM_KEYS) + j)
                f = factors[jax.random.randint(fkey, (pop_size,), 0, factors.shape[0])]
                if jnp.issubdtype(h.dtype, jnp.integer):
                    p = jnp.maximum(jnp.round(taken.astype(jnp.float32) * f), 1.0).astype(h.dtype)
                else:
                    p = taken * f
                new_fields[name] = jnp.where(replaced, p, h)
            env_params = type(env_params)(**new_fields)
        return params, opt_state, new_hparams, env_params

    return pbt_step


def make_population_block(
    agent,
    tx,
    cfg,
    mesh,
    benv,
    local_envs: int,
    iters_per_block: int,
    obs_key: str,
    pop_size: int,
    ferry_episodes: bool = True,
    guard: bool = False,
    pbt: Optional[PBTConfig] = None,
):
    """Build the jitted population dispatch: ``vmap`` of the per-device fused
    block over the leading member axis, wrapped in ONE ``shard_map`` over
    ``dp``, followed by the ``lax.cond``-gated PBT selection step.

    Signature of the returned function::

        (params, opt_state, env_state, obs, ep_ret, ep_len, env_keys,
         train_keys, hparams, env_params, anneal, pbt_gate, pbt_key)
        -> (params, opt_state, env_state, obs, ep_ret, ep_len, env_keys,
            hparams, env_params, fitness, metrics)

    where every member-stacked pytree has leading dim P, ``hparams`` is the
    dict of ``(P,)`` traced hyperparameter arrays, ``env_params`` the
    ``(P,)``-stacked env dynamics-constants pytree (the SCENARIO axis — each
    member's envs step its own slice), ``anneal`` is the traced ``(3,)``
    [lr, clip, ent] staircase fraction broadcast over members, ``pbt_gate``
    a traced bool and ``fitness`` the ``(P,)`` per-member (= per-scenario)
    block fitness. Env-carrying arrays are sharded ``P(None, "dp")`` — envs
    split across devices UNDER the population axis — params/optimizer/env
    params replicated. The gate, the hparams, the env params and the keys
    are all TRACED: one compile serves every member, every scenario, every
    annealing step and both PBT branches.
    """
    local_block = make_anakin_local_block(
        agent, tx, cfg, benv, local_envs, iters_per_block, obs_key,
        ferry_episodes=ferry_episodes, guard=guard, population=True,
    )
    if pop_size == 1:
        # vmap over a size-1 axis is element-wise application by definition —
        # lower it as exactly that, so the P=1 population program is the
        # single-run program BIT-for-bit. Under a real vmap XLA emits batched
        # reductions whose accumulation order drifts from the unbatched ones
        # at ulp level; unrolling keeps the parity guarantee the tests assert
        # (and P=1 runs pay zero batching overhead).
        def vblock(*args):
            out = local_block(*jax.tree.map(lambda x: x[0], args))
            return jax.tree.map(lambda x: x[None], out)

    else:
        vblock = jax.vmap(local_block)

    env_sharded = P(None, "dp")
    metric_specs = {"pg": P(), "v": P(), "ent": P(), "fit": P()}
    if guard:
        metric_specs["bad"] = P()
    if ferry_episodes:
        ep_spec = P(None, None, None, "dp")
        metric_specs.update(ep_done=ep_spec, ep_ret=ep_spec, ep_len=ep_spec)
    shard_block = shard_map(
        vblock,
        mesh=mesh,
        in_specs=(
            P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded,
            P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded, metric_specs),
        check_vma=False,
    )
    pbt_step = make_pbt_step(pop_size, pbt) if pbt is not None else None

    def dispatch(
        params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_keys,
        hparams, env_params, anneal, pbt_gate, pbt_key,
    ):
        lr = hparams["lr"] * anneal[0]
        clip_coef = hparams["clip_coef"] * anneal[1]
        ent_coef = hparams["ent_coef"] * anneal[2]
        opt_state = _with_lr(opt_state, lr)
        params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, metrics = shard_block(
            params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_keys,
            clip_coef, ent_coef, hparams["gamma"], hparams["gae_lambda"], env_params,
        )
        fitness = metrics["fit"].mean(axis=1)  # (P,): mean per-iteration fitness over the block
        if pbt_step is not None:
            params, opt_state, hparams, env_params = jax.lax.cond(
                pbt_gate,
                pbt_step,
                lambda op: (op[0], op[1], op[2], op[3]),
                (params, opt_state, hparams, env_params, fitness, pbt_key),
            )
        return params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, hparams, env_params, fitness, metrics

    # Pin the env-carried outputs to the SAME sharding the driver stages the
    # call-1 inputs with. Left to inference, the outer jit canonicalizes the
    # shard_map's P(None, "dp") outputs (e.g. to P() on small meshes) — an
    # EQUIVALENT placement but a different C++ jit-cache key, so the second
    # block call (fed by call 1's outputs) silently recompiled the whole
    # program: one abstract signature, two compiles, no tracing-cache miss.
    from jax.sharding import NamedSharding

    env_out = NamedSharding(mesh, env_sharded)
    # fed-back replicated outputs (params/opt/hparams) are pinned too — no
    # fed-back output may carry a compiler-chosen cache key (graft-audit
    # AUD002); fitness/metrics are host-consumed and stay unconstrained
    rep_out = NamedSharding(mesh, P())
    out_shardings = (
        rep_out, rep_out, env_out, env_out, env_out, env_out, env_out, rep_out, rep_out, None, None,
    )
    return jax.jit(dispatch, donate_argnums=(0, 1, 2, 3, 4, 5, 6), out_shardings=out_shardings)


def population_main(fabric, cfg: Dict[str, Any]):
    """The population driver body (shared by ``algo=ppo_anakin_population``
    and ``algo=ppo_anakin algo.population.size=P``)."""
    from sheeprl_tpu.fault import DivergenceSentinel, load_resume_state

    if jax.process_count() > 1:  # pragma: no cover - single-host subsystem
        raise NotImplementedError(
            "ppo_anakin_population ferries block metrics from a single controller; use the host-loop "
            "`algo=ppo` for multi-host runs."
        )

    pop_cfg = cfg.algo.get("population") or {}
    pop_size = int(pop_cfg.get("size") or 1)
    if pop_size < 1:
        raise ValueError(f"algo.population.size must be >= 1, got {pop_size}")
    share_init = bool(pop_cfg.get("share_init", False))

    # A population run triggered through `algo=ppo_anakin population.size=P`
    # writes population-layout checkpoints (member-stacked leaves); stamp the
    # population algo name BEFORE the log dir / saved config are derived so
    # eval / serve / resume resolve the population-aware entry points. The
    # root_dir / exp_name / run_name interpolations were already resolved at
    # compose time, so any component spelled from the pre-stamp algo name is
    # rewritten too (custom names that don't embed it are left alone).
    old_name = str(cfg.algo.name)
    cfg.algo.name = "ppo_anakin_population"
    if old_name != cfg.algo.name:
        for key in ("root_dir", "exp_name", "run_name"):
            val = str(cfg.get(key) or "")
            if old_name in val:
                cfg[key] = val.replace(old_name, cfg.algo.name)

    initial_ent_coef = copy.deepcopy(cfg.algo.ent_coef)
    initial_clip_coef = copy.deepcopy(cfg.algo.clip_coef)
    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)
        if state is not None and int(state.get("population_size", pop_size)) != pop_size:
            raise ValueError(
                f"Resume checkpoint holds a population of {state.get('population_size')} members but "
                f"algo.population.size={pop_size}; the whole population resumes together"
            )

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    if not is_jax_env(cfg.env.id):
        from sheeprl_tpu.envs.jax_envs import JAX_ENV_REGISTRY

        raise ValueError(
            f"algo=ppo_anakin_population requires a pure-JAX environment; '{cfg.env.id}' is not "
            f"registered (available: {sorted(JAX_ENV_REGISTRY)}). Use algo=ppo for host-loop training."
        )
    env_kwargs: Dict[str, Any] = {}
    if cfg.env.max_episode_steps and cfg.env.max_episode_steps > 0:
        env_kwargs["max_episode_steps"] = int(cfg.env.max_episode_steps)
    jenv = make_jax_env(cfg.env.id, **env_kwargs)

    cnn_keys = list(cfg.algo.cnn_keys.encoder or [])
    mlp_keys = list(cfg.algo.mlp_keys.encoder or [])
    if cnn_keys or len(mlp_keys) != 1:
        raise ValueError(
            "ppo_anakin_population supports exactly one vector observation key (the classic-control "
            f"JaxEnvs); got cnn={cnn_keys} mlp={mlp_keys}"
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

    agent, single_params, player = build_agent(fabric, actions_dim, is_continuous, cfg, observation_space, None)

    # Per-member RNG streams, all split from one root key
    root = jax.random.PRNGKey(cfg.seed)
    root, env_reset_root, rollout_root, member_root, pop_root = jax.random.split(root, 5)

    # Per-member params: independent inits per member key (share_init=True
    # broadcasts one init instead — a pure hparam sweep over one seed)
    if state is not None:
        stacked_params = jax.tree.map(jnp.asarray, state["agent"])
    elif share_init:
        stacked_params = jax.tree.map(lambda x: jnp.broadcast_to(x, (pop_size, *x.shape)), single_params)
    else:
        obs_dim = int(np.prod(jenv.observation_space.shape))
        dummy_obs = {obs_key: jnp.zeros((1, obs_dim), dtype=jnp.float32)}
        init_keys = jax.random.split(jax.random.fold_in(root, 0), pop_size)
        stacked_params = jax.jit(jax.vmap(lambda k: agent.init(k, dummy_obs)))(init_keys)
    params = fabric.put_replicated(stacked_params)

    # Sweep + scenario-matrix resolution (deterministic per seed) — or the
    # checkpointed values: resume NEVER re-resolves the matrix (PBT may have
    # rewritten it, and an edited sweep config must not silently remap a
    # running population onto different scenarios)
    hparams_np, swept, env_params_np, env_swept = resolve_matrix(cfg, pop_size, int(cfg.seed), env=jenv)
    if env_swept:
        # re-make with the swept set declared: constructor kwargs that shadow
        # a swept env param fail loudly instead of training every scenario on
        # the constructor value (see make_jax_env)
        jenv = make_jax_env(cfg.env.id, swept_params=env_swept, **env_kwargs)
    if state is not None and state.get("hparams") is not None:
        hparams_np = {k: np.asarray(v, dtype=np.float32) for k, v in state["hparams"].items()}
    if state is not None and state.get("env_params") is not None:
        env_params_np = {k: np.asarray(v) for k, v in state["env_params"].items()}
    pbt, pbt_every = resolve_pbt(cfg, pop_size, swept, env_swept)
    hparams = fabric.put_replicated({k: jnp.asarray(v) for k, v in hparams_np.items()})
    # the (P,)-stacked scenario pytree: one slice per member, TRACED through
    # the block so every scenario shares the single compile
    _env_defaults = jenv.default_params()
    env_params = fabric.put_replicated(
        type(_env_defaults)(**{f: jnp.asarray(env_params_np[f]) for f in _env_defaults._fields})
    )

    from sheeprl_tpu.optim.builders import build_optimizer

    lr0 = float(cfg.algo.optimizer.lr)
    tx = optax.inject_hyperparams(
        lambda learning_rate: build_optimizer(
            {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
        )
    )(learning_rate=lr0)
    opt_state = jax.jit(jax.vmap(tx.init))(params)
    if state is not None:
        opt_state = jax.tree.map(
            lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, state["optimizer"]
        )
    opt_state = fabric.put_replicated(opt_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)
        axes_desc = list(swept) + [f"env_params.{n}" for n in env_swept]
        print(f"Population: {pop_size} members, sweep over {axes_desc or 'nothing (seed-only)'}")
        for m in range(pop_size):
            line = ", ".join(f"{k}={hparams_np[k][m]:.6g}" for k in HPARAM_KEYS)
            if env_swept:
                line += ", " + ", ".join(f"{k}={env_params_np[k][m]:.6g}" for k in env_swept)
            print(f"  member {m}: {line}")

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Envs: (P, num_envs) global — num_envs per member, env axis sharded over
    # the mesh under the population axis
    num_envs = int(cfg.env.num_envs)
    world = fabric.world_size
    if num_envs % world != 0:
        raise ValueError(f"env.num_envs ({num_envs}) must be divisible by the number of devices ({world})")
    local_envs = num_envs // world
    T = int(cfg.algo.rollout_steps)

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

    ferry_episodes = cfg.metric.log_level > 0
    iters_per_block = resolve_iters_per_block(
        cfg, total_iters, policy_steps_per_iter, ferry_episodes, population_size=pop_size
    )

    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True))
    sentinel = DivergenceSentinel(sentinel_cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    # Member train streams + the population (PBT/perturbation) stream
    member_rngs = jax.random.split(member_root, pop_size)
    pop_key = pop_root
    if state is not None and state.get("rng") is not None:
        member_rngs = jnp.asarray(state["rng"])  # (P, 2): continue every member's stream
    if state is not None and state.get("pop_key") is not None:
        pop_key = jnp.asarray(state["pop_key"])
    member_rngs = fabric.put_replicated(member_rngs)
    pop_key = fabric.put_replicated(pop_key)

    benv = BatchedJaxEnv(jenv, num_envs)
    reset_keys = jax.random.split(env_reset_root, pop_size)
    # vmap over (member key, member scenario): each member's envs start under
    # its own env params
    env_state, first_obs = jax.jit(jax.vmap(benv.reset))(reset_keys, env_params)
    env_sharding = fabric.sharding(None, "dp")
    env_state = jax.device_put(env_state, env_sharding)
    obs = jax.device_put(first_obs, env_sharding)
    ep_ret = jax.device_put(jnp.zeros((pop_size, num_envs), jnp.float32), env_sharding)
    ep_len = jax.device_put(jnp.zeros((pop_size, num_envs), jnp.int32), env_sharding)
    env_keys = jax.device_put(
        jax.vmap(lambda k: jax.random.split(k, world))(jax.random.split(rollout_root, pop_size)),
        env_sharding,
    )

    get_block_fn = AnakinBlockCache(
        lambda n_iters: make_population_block(
            agent, tx, cfg, fabric.mesh, benv, local_envs, n_iters, obs_key,
            pop_size, ferry_episodes=ferry_episodes, guard=guard, pbt=pbt,
        ),
        name="ppo_anakin_pop.block",
    )

    split_members = jax.jit(lambda keys: jnp.swapaxes(jax.vmap(jax.random.split)(keys), 0, 1))

    # Annealing staircase fractions — on resume, seed them where the
    # uninterrupted run would stand (the loop recomputes them from iter_num
    # AFTER each block, so a killed run restarting at 1.0 would train the
    # whole first post-resume block at the fully unannealed lr/clip/ent)
    done_iters = start_iter - 1
    lr_frac = (
        polynomial_decay(done_iters, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_lr and done_iters > 0
        else 1.0
    )
    clip_frac = (
        polynomial_decay(done_iters, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_clip_coef and done_iters > 0
        else 1.0
    )
    ent_frac = (
        polynomial_decay(done_iters, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_ent_coef and done_iters > 0
        else 1.0
    )

    from sheeprl_tpu.utils.profiler import TraceProfiler

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir)

    # fitness restored so a resume of an already-finished run still tests /
    # registers the checkpointed best member, not member 0; block_num
    # restored so the PBT every_blocks cadence continues where it left off
    fitness_np = (
        np.asarray(state["fitness"], np.float32)
        if state is not None and state.get("fitness") is not None
        else np.zeros((pop_size,), np.float32)
    )
    block_num = int(state.get("block_num", 0)) if state is not None else 0
    iter_num = start_iter - 1
    while iter_num < total_iters:
        block_iters = min(iters_per_block, total_iters - iter_num)
        block_fn = get_block_fn(block_iters)
        profiler.tick(iter_num + 1)
        block_num += 1

        member_rngs, train_keys = split_members(member_rngs)
        pop_key, pbt_key = jax.random.split(pop_key)
        gate = pbt is not None and (block_num % pbt_every == 0)
        # per-block host values (annealing staircase, PBT gate) staged with
        # ONE explicit replicated put each — left uncommitted they would be
        # replicated across the mesh implicitly inside the guarded dispatch
        anneal = fabric.put_replicated(jnp.asarray([lr_frac, clip_frac, ent_frac], dtype=jnp.float32))
        gate_arr = fabric.put_replicated(jnp.asarray(gate))
        with timer("Time/train_time", SumMetric):
            (
                params, opt_state, env_state, obs, ep_ret, ep_len, env_keys,
                hparams, env_params, fitness, metrics,
            ) = block_fn(
                params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_keys,
                hparams, env_params, anneal, gate_arr, pbt_key,
            )
            metrics = jax.device_get(metrics)
            fitness_np = np.asarray(jax.device_get(fitness))

        # Host-side bookkeeping, iteration by iteration (same counters and
        # cadence as the single-run Anakin main; losses reported as the
        # population mean, selection metrics under Population/*)
        tripped = False
        for i in range(block_iters):
            iter_num += 1
            policy_step += policy_steps_per_iter
            train_step += 1
            if guard:
                tripped = sentinel.observe(metrics["bad"][:, i].sum()) or tripped
            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/policy_loss", metrics["pg"][:, i].mean())
                aggregator.update("Loss/value_loss", metrics["v"][:, i].mean())
                aggregator.update("Loss/entropy_loss", metrics["ent"][:, i].mean())

        best = int(fitness_np.argmax())
        if cfg.metric.log_level > 0:
            # Rewards/* track the BEST member's completed episodes so the
            # headline curve is the sweep's deliverable (per-member detail
            # rides Population/*)
            done_mask = np.asarray(metrics["ep_done"][best])
            if done_mask.any():
                rets = np.asarray(metrics["ep_ret"][best])
                lens = np.asarray(metrics["ep_len"][best])
                its, ts, envs_idx = np.nonzero(done_mask)
                for i_i, t_i, e_i in zip(its, ts, envs_idx):
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", rets[i_i, t_i, e_i])
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", lens[i_i, t_i, e_i])

        if tripped:
            def _rollback(good):
                nonlocal params, opt_state, member_rngs, hparams, env_params, pop_key, fitness_np
                params = fabric.put_replicated(jax.tree.map(lambda t, s: jnp.asarray(s), params, good["agent"]))
                opt_state = fabric.put_replicated(
                    jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opt_state, good["optimizer"])
                )
                if good.get("rng") is not None:
                    member_rngs = fabric.put_replicated(jnp.asarray(good["rng"]))
                if good.get("hparams") is not None:
                    hparams = fabric.put_replicated({k: jnp.asarray(v) for k, v in good["hparams"].items()})
                if good.get("env_params") is not None:
                    # the scenario matrix rolls back with the weights (PBT
                    # with perturb_env_params may have moved it since)
                    env_params = fabric.put_replicated(
                        type(env_params)(**{f: jnp.asarray(good["env_params"][f]) for f in type(env_params)._fields})
                    )
                if good.get("pop_key") is not None:
                    pop_key = fabric.put_replicated(jnp.asarray(good["pop_key"]))
                # the diverged block's fitness (possibly NaN) must not drive
                # Population/* reporting, checkpointed best_member, or the
                # final best-member selection — fall back to the last good
                # checkpoint's fitness (zeros if it predates the first block)
                fitness_np = (
                    np.asarray(good["fitness"], np.float32)
                    if good.get("fitness") is not None
                    else np.zeros((pop_size,), np.float32)
                )

            sentinel.recover(ckpt_dir, _rollback)
            best = int(fitness_np.argmax())

        if cfg.metric.log_level > 0:
            ranks = np.argsort(np.argsort(-fitness_np))  # rank 0 = best
            pop_metrics = {
                "Population/fitness_best": float(fitness_np.max()),
                "Population/fitness_median": float(np.median(fitness_np)),
                "Population/fitness_worst": float(fitness_np.min()),
                "Population/best_member": best,
            }
            if ferry_episodes:
                ep_done = np.asarray(metrics["ep_done"])  # (P, iters, T, num_envs)
                ep_rets = np.asarray(metrics["ep_ret"])
                member_ret = np.full((pop_size,), np.nan, np.float32)
                for m in range(pop_size):
                    if ep_done[m].any():
                        member_ret[m] = ep_rets[m][ep_done[m]].mean()
                if np.isfinite(member_ret).any():
                    pop_metrics["Population/return_best"] = float(np.nanmax(member_ret))
                    pop_metrics["Population/return_median"] = float(np.nanmedian(member_ret))
            for m in range(pop_size):
                pop_metrics[f"Population/member_{m}/fitness"] = float(fitness_np[m])
                pop_metrics[f"Population/member_{m}/rank"] = int(ranks[m])
            if gate:
                # PBT may have rewritten the hparams: surface the live values
                live_h = {k: np.asarray(v) for k, v in jax.device_get(hparams).items()}
                for m in range(pop_size):
                    for k in HPARAM_KEYS:
                        pop_metrics[f"Population/member_{m}/{k}"] = float(live_h[k][m])
                if env_swept:
                    # ... and the live scenario (moves only under
                    # perturb_env_params=true; logged either way so the
                    # per-member fitness always reads against its scenario)
                    live_e = jax.device_get(env_params)
                    for m in range(pop_size):
                        for k in env_swept:
                            pop_metrics[f"Population/member_{m}/env_{k}"] = float(np.asarray(getattr(live_e, k))[m])
            logger.log_dict(pop_metrics, policy_step)
            logger.log_dict(
                {
                    "Info/learning_rate": lr0 * lr_frac,
                    "Info/clip_coef": float(initial_clip_coef) * clip_frac,
                    "Info/ent_coef": float(initial_ent_coef) * ent_frac,
                },
                policy_step,
            )
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
                                "Time/sps_env_interaction_aggregate": (
                                    (policy_step - last_log) * pop_size / timer_metrics["Time/train_time"]
                                ),
                            },
                            policy_step,
                        )
                    timer.reset()
                last_log = policy_step
                last_train = train_step

        # Annealing at block granularity: ONE traced fraction broadcast over
        # the per-member base values (identical staircase to the single run)
        if cfg.algo.anneal_lr:
            lr_frac = polynomial_decay(iter_num, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_clip_coef:
            clip_frac = polynomial_decay(iter_num, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)
        if cfg.algo.anneal_ent_coef:
            ent_frac = polynomial_decay(iter_num, initial=1.0, final=0.0, max_decay_steps=total_iters, power=1.0)

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
                "rng": member_rngs,
                "pop_key": pop_key,
                "hparams": hparams,
                # the scenario matrix, saved as a plain field dict (dtypes
                # preserved) so resume/rollback/eval/serve restore it WITHOUT
                # re-resolving the sweep
                "env_params": {f: getattr(env_params, f) for f in type(env_params)._fields},
                "fitness": fitness_np,
                "population_size": pop_size,
                "best_member": best,
                "block_num": block_num,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state)

    profiler.close()
    best = int(fitness_np.argmax())
    best_params = jax.tree.map(lambda x: x[best], params)
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, best_params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import register_model

        from sheeprl_tpu.algos.ppo.utils import log_models

        register_model(fabric, log_models, cfg, {"agent": best_params})
    logger.close()


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    return population_main(fabric, cfg)


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


@register_audit_programs("ppo_anakin_pop.block")
def _audit_programs(spec: AuditMesh):
    from sheeprl_tpu.algos.ppo.ppo_anakin import audit_anakin_setup

    pop_size = 2
    s = audit_anakin_setup(spec, pop_size=pop_size)
    rep = s["rep"]
    train_keys = jax.ShapeDtypeStruct((pop_size, 2), jnp.uint32, sharding=rep)
    hparams = {
        k: jax.ShapeDtypeStruct((pop_size,), jnp.float32, sharding=rep) for k in HPARAM_KEYS
    }
    anneal = jax.ShapeDtypeStruct((3,), jnp.float32, sharding=rep)
    gate = jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep)
    pbt_key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    args = (
        s["params"], s["opt_state"], s["env_state"], s["obs"], s["ep_ret"], s["ep_len"],
        s["env_keys"], train_keys, hparams, s["env_params"], anneal, gate, pbt_key,
    )
    out_decl = {
        0: P(), 1: P(), 2: P(None, "dp"), 3: P(None, "dp"), 4: P(None, "dp"),
        5: P(None, "dp"), 6: P(None, "dp"), 7: P(), 8: P(),
    }
    fn = make_population_block(
        s["agent"], s["tx"], s["cfg"], s["mesh"], s["benv"], s["local_envs"], 1,
        "state", pop_size, ferry_episodes=True, guard=True, pbt=None,
    )
    yield AuditProgram(
        name="ppo_anakin_pop.block",
        fn=fn,
        args=args,
        source=__name__,
        donate_argnums=(0, 1, 2, 3, 4, 5, 6),
        feedback_outputs=(0, 1, 2, 3, 4, 5, 6, 7, 8),
        out_decl=out_decl,
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )
    # the PBT-armed twin: the lax.cond selection step (hparam + env-param
    # inherit/perturb) is part of the compiled program and must satisfy the
    # same sharding/donation/feedback contracts on both branches
    pbt = PBTConfig(num_copy=1, perturb=("lr",), factors=(0.8, 1.25), env_perturb=("length",))
    fn_pbt = make_population_block(
        s["agent"], s["tx"], s["cfg"], s["mesh"], s["benv"], s["local_envs"], 1,
        "state", pop_size, ferry_episodes=True, guard=True, pbt=pbt,
    )
    yield AuditProgram(
        name="ppo_anakin_pop.block[pbt]",
        fn=fn_pbt,
        args=args,
        source=__name__,
        donate_argnums=(0, 1, 2, 3, 4, 5, 6),
        feedback_outputs=(0, 1, 2, 3, 4, 5, 6, 7, 8),
        out_decl=out_decl,
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )
