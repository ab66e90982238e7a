"""SAC — coupled training (reference: ``sheeprl/algos/sac/sac.py:33-420``).

TPU-native structure:

- the env loop runs on host with a jitted actor forward per step;
- each iteration the ``Ratio`` governor grants G gradient steps
  (reference: ``sac.py:299-314``); the batch for all G steps is sampled once
  ``(G, B)`` and the WHOLE G-step optimization — critic TD update, target EMA,
  actor update, entropy-coefficient update — is a single jitted ``shard_map``
  + ``lax.scan`` over the mesh: minibatches enter sharded on ``dp`` along the
  batch axis, gradients are ``pmean``-ed (DDP semantics, incl. the reference's
  explicit alpha-grad all-reduce, ``sac.py:72``) and the scan removes all
  per-minibatch dispatch overhead;
- step accounting treats the (single) process as world-size 1 — devices shard
  the batch, not the envs — so replay-ratio bookkeeping matches the reference
  at ``world_size=1`` regardless of mesh size (same convention as PPO).
"""

from __future__ import annotations

import copy
import os
import time
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.sac.agent import SACAgent, build_agent
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.analysis.tracecheck import tracecheck
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer, put_packed
from sheeprl_tpu.data.ring import pack_burst_blob
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, resolve_hybrid_player, save_configs

__all__ = ["main", "make_train_step", "make_resident_train_step", "restore_train_state"]


def restore_train_state(fabric, good, params, aopt, copt, lopt, rng):
    """Rebuild the live SAC train state from a rollback checkpoint payload
    (the divergence sentinel's recover callback body, shared by the coupled
    mains and ``sac_sebulba``). Returns the replicated replacements; ``rng``
    passes through unchanged when the checkpoint carries no stream."""
    params = fabric.put_replicated(jax.tree.map(lambda t, s: jnp.asarray(s), params, good["agent"]))
    cast = lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s
    aopt = fabric.put_replicated(jax.tree.map(cast, aopt, good["actor_optimizer"]))
    copt = fabric.put_replicated(jax.tree.map(cast, copt, good["qf_optimizer"]))
    lopt = fabric.put_replicated(jax.tree.map(cast, lopt, good["alpha_optimizer"]))
    if good.get("rng") is not None:
        rng = jnp.asarray(good["rng"])
    return params, aopt, copt, lopt, rng


def make_train_step(agent: SACAgent, actor_tx, critic_tx, alpha_tx, cfg, mesh, donate: bool = True, guard: bool = False):
    """Build the fully-jitted G-gradient-step update (see module docstring).

    Inputs at call time: ``data`` pytree shaped ``(G, B, ...)`` with the batch
    axis sharded over ``dp``; ``ema_flag`` a 0/1 scalar (the reference applies
    the EMA inside every minibatch of an iteration when
    ``iter % (target_network_frequency // policy_steps_per_iter + 1) == 0``,
    ``sac.py:55-57``).

    ``guard=True``: a gradient step whose critic/actor/alpha grads are
    non-finite leaves the whole train state (incl. the target-critic EMA)
    untouched, and an eighth output counts the skipped steps for the
    divergence sentinel."""
    gamma = float(cfg.algo.gamma)
    target_entropy = agent.target_entropy

    def minibatch_step(carry, xs):
        params, aopt, copt, lopt, ema_flag = carry
        old = (params, aopt, copt, lopt)
        batch, key = xs
        k_next, k_actor = jax.random.split(key)
        obs = batch["observations"]
        next_obs = batch["next_observations"]

        # -- critic update (reference train(): sac.py:45-53)
        td_target = agent.next_target_q(params, next_obs, batch["rewards"], batch["terminated"], gamma, k_next)
        td_target = jax.lax.stop_gradient(td_target)

        def c_loss(cp):
            q = agent.q_values(cp, obs, batch["actions"])
            return critic_loss(q, td_target, agent.critic.n)

        qf_loss, cgrads = jax.value_and_grad(c_loss)(params["critic"])
        cgrads = pmean_grads(cgrads, "dp")
        cupd, copt = critic_tx.update(cgrads, copt, params["critic"])
        params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}

        # -- target EMA (reference: sac.py:55-57)
        params = {**params, "target_critic": agent.ema(params["critic"], params["target_critic"], ema_flag)}

        # -- actor update (reference: sac.py:59-67)
        alpha = jax.lax.stop_gradient(jnp.exp(params["log_alpha"]))

        def a_loss(ap):
            actions, logp = agent.sample_action(ap, obs, k_actor)
            q = agent.q_values(params["critic"], obs, actions)
            min_q = jnp.min(q, axis=-1, keepdims=True)
            return policy_loss(alpha, logp, min_q), logp

        (actor_loss, logp), agrads = jax.value_and_grad(a_loss, has_aux=True)(params["actor"])
        agrads = pmean_grads(agrads, "dp")
        aupd, aopt = actor_tx.update(agrads, aopt, params["actor"])
        params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        # -- entropy coefficient (reference: sac.py:69-75 incl. grad all-reduce)
        def l_loss(la):
            return entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)

        alpha_loss, lgrads = jax.value_and_grad(l_loss)(params["log_alpha"])
        lgrads = pmean_grads(lgrads, "dp")
        lupd, lopt = alpha_tx.update(lgrads, lopt, params["log_alpha"])
        params = {**params, "log_alpha": optax.apply_updates(params["log_alpha"], lupd)}

        if guard:
            from sheeprl_tpu.ops import finite_guard, guarded_select

            ok = finite_guard((cgrads, agrads, lgrads, qf_loss, actor_loss, alpha_loss))
            # losses are per-device: all-reduce the verdict so every device
            # takes the same branch and replicated params never desync
            ok = jax.lax.pmin(ok.astype(jnp.int32), "dp").astype(bool)
            params, aopt, copt, lopt = guarded_select(ok, (params, aopt, copt, lopt), old)
            return (params, aopt, copt, lopt, ema_flag), (
                qf_loss, actor_loss, alpha_loss, 1.0 - ok.astype(jnp.float32)
            )
        return (params, aopt, copt, lopt, ema_flag), (qf_loss, actor_loss, alpha_loss)

    def local_train(params, aopt, copt, lopt, data, key, ema_flag):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        n_steps = jax.tree.leaves(data)[0].shape[0]
        keys = jax.random.split(key, n_steps)
        carry = (params, aopt, copt, lopt, ema_flag)
        carry, losses = jax.lax.scan(minibatch_step, carry, (data, keys))
        params, aopt, copt, lopt, _ = carry
        if guard:
            qf, al, ll, bad = losses
            qf, al, ll = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), (qf, al, ll))
            return params, aopt, copt, lopt, qf, al, ll, bad.sum()
        qf, al, ll = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, aopt, copt, lopt, qf, al, ll

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(None, "dp"), P(), P()),
        out_specs=(P(),) * (8 if guard else 7),
        check_vma=False,
    )
    # See ppo.make_train_step: the decoupled player still reads old snapshots.
    # Output placements pinned (all replicated) — fed-back train state must
    # never carry a compiler-chosen cache key (graft-audit AUD002 / PR 8).
    from jax.sharding import NamedSharding

    return jax.jit(
        shard_train,
        donate_argnums=(0, 1, 2, 3) if donate else (),
        out_shardings=NamedSharding(mesh, P()),
    )


def make_burst_train_step(
    agent: SACAgent,
    actor_tx,
    critic_tx,
    alpha_tx,
    cfg,
    mesh,
    capacity: int,
    n_envs: int,
    stage_max: int,
    grad_chunk: int,
    dims: "Dict[str, int] | None" = None,
):
    """Device-resident-replay burst update (TPU-native; no reference
    counterpart — the reference host-samples every iteration).

    One dispatch (a) appends up to ``stage_max`` fresh transitions into a
    ring buffer that LIVES ON DEVICE, (b) draws ``grad_chunk`` uniform
    minibatches from it with device RNG, and (c) runs the same
    critic/EMA/actor/alpha updates as :func:`make_train_step` as one scan.

    Rationale: every dispatch whose inputs depend on the previous update's
    outputs waits for it, and host-side sampling copies every minibatch
    host→device (~1.3 GB for the reference SAC benchmark). Batching K iterations' grants into one dispatch divides
    the round-trips by K, and on-device sampling cuts host→device traffic to
    the raw transition stream (~5 MB). Same sampling distribution as
    ``ReplayBuffer.sample(sample_next_obs=False)``: uniform over the valid
    ``(position, env)`` grid.

    The staged transitions are appended *before* the chunk's minibatches are
    drawn, so late minibatches in a burst can see transitions the reference
    would only expose next iteration — the usual one-burst staleness trade.
    """
    gamma = float(cfg.algo.gamma)
    target_entropy = agent.target_entropy
    n_dev = mesh.devices.size

    def minibatch_step(carry, xs):
        params, aopt, copt, lopt, rb = carry
        old = (params, aopt, copt, lopt)
        key, ema_flag, valid = xs
        ema_flag = ema_flag * valid
        k_idx, k_env, k_next, k_actor = jax.random.split(key, 4)
        # On-device uniform sample over the valid (position, env) grid.
        # valid_n rides in the carry-free closure inputs via rb["valid_n"].
        B = int(cfg.algo.per_rank_batch_size) // n_dev
        pos_idx = jax.random.randint(k_idx, (B,), 0, rb["valid_n"])
        env_idx = jax.random.randint(k_env, (B,), 0, n_envs)
        batch = {
            k: rb[k][pos_idx, env_idx] for k in ("observations", "next_observations", "actions", "rewards", "terminated")
        }

        td_target = agent.next_target_q(params, batch["next_observations"], batch["rewards"], batch["terminated"], gamma, k_next)
        td_target = jax.lax.stop_gradient(td_target)

        def c_loss(cp):
            q = agent.q_values(cp, batch["observations"], batch["actions"])
            return critic_loss(q, td_target, agent.critic.n)

        qf_loss, cgrads = jax.value_and_grad(c_loss)(params["critic"])
        cgrads = pmean_grads(cgrads, "dp")
        cupd, copt = critic_tx.update(cgrads, copt, params["critic"])
        params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}
        params = {**params, "target_critic": agent.ema(params["critic"], params["target_critic"], ema_flag)}

        alpha = jax.lax.stop_gradient(jnp.exp(params["log_alpha"]))

        def a_loss(ap):
            actions, logp = agent.sample_action(ap, batch["observations"], k_actor)
            q = agent.q_values(params["critic"], batch["observations"], actions)
            return policy_loss(alpha, logp, jnp.min(q, axis=-1, keepdims=True)), logp

        (actor_loss, logp), agrads = jax.value_and_grad(a_loss, has_aux=True)(params["actor"])
        agrads = pmean_grads(agrads, "dp")
        aupd, aopt = actor_tx.update(agrads, aopt, params["actor"])
        params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        def l_loss(la):
            return entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)

        alpha_loss, lgrads = jax.value_and_grad(l_loss)(params["log_alpha"])
        lgrads = pmean_grads(lgrads, "dp")
        lupd, lopt = alpha_tx.update(lgrads, lopt, params["log_alpha"])
        params = {**params, "log_alpha": optax.apply_updates(params["log_alpha"], lupd)}

        # Ungranted (padding) steps are no-ops: a burst is dispatched with a
        # fixed-length scan, `valid` marks the Ratio-granted prefix.
        params, aopt, copt, lopt = jax.tree.map(
            lambda n, o: jnp.where(valid > 0, n, o), (params, aopt, copt, lopt), old
        )
        return (params, aopt, copt, lopt, rb), (qf_loss, actor_loss, alpha_loss)

    def local_train(params, aopt, copt, lopt, rb, staged, pos, count, valid_n, key, ema_flags, valid):
        # Ring append with wrap-around; rows past `count` target index
        # `capacity` and are dropped by the scatter.
        idx = (pos + jnp.arange(stage_max)) % capacity
        idx = jnp.where(jnp.arange(stage_max) < count, idx, capacity)
        rb = {k: rb[k].at[idx].set(staged[k], mode="drop") for k in rb}
        rb["valid_n"] = valid_n
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        keys = jax.random.split(key, grad_chunk)
        carry = (params, aopt, copt, lopt, rb)
        carry, losses = jax.lax.scan(minibatch_step, carry, (keys, ema_flags, valid))
        params, aopt, copt, lopt, rb = carry
        del rb["valid_n"]
        qf, al, ll = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), losses)
        return params, aopt, copt, lopt, rb, qf, al, ll

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    if dims is None:
        return jax.jit(shard_train, donate_argnums=(4,))

    # Packed single-upload variant (same rationale as the Dreamer ring's
    # packed burst, data/ring.py): the job's ~10 separate host arrays each
    # paid per-transfer latency on the trainer thread; one uint8 blob pays
    # it once per flush.
    from sheeprl_tpu.data.ring import make_layout, unpack_burst_blob

    spec = [(k, (stage_max, n_envs, d), np.float32) for k, d in dims.items()]
    spec += [
        ("__pos__", (), np.int32),
        ("__count__", (), np.int32),
        ("__valid_n__", (), np.int32),
        ("__key__", (2,), np.uint32),
        ("__flags__", (grad_chunk,), np.float32),
        ("__valid__", (grad_chunk,), np.float32),
    ]
    layout = make_layout(spec)

    def packed_train(params, aopt, copt, lopt, rb, blob):
        u = unpack_burst_blob(blob, layout)
        return shard_train(
            params, aopt, copt, lopt, rb,
            {k: u[k] for k in dims},
            u["__pos__"], u["__count__"], u["__valid_n__"],
            u["__key__"], u["__flags__"], u["__valid__"],
        )

    return jax.jit(packed_train, donate_argnums=(4,)), layout


def make_resident_train_step(
    agent: SACAgent,
    actor_tx,
    critic_tx,
    alpha_tx,
    cfg,
    mesh,
    drb,
    grad_max: int,
    guard: bool = False,
    donate: bool = True,
    append: bool = True,
):
    """Fused append + in-graph sample + G-step update against a
    :class:`~sheeprl_tpu.replay.DeviceReplayBuffer` (the ``buffer.
    device_resident`` path; see ``howto/device_replay.md``).

    ``append=False`` builds the TRAIN-ONLY variant for the decoupled
    (Sebulba) topology: appends ride the replay buffer's own
    :meth:`~sheeprl_tpu.replay.DeviceReplayBuffer.make_append_step` program
    (fed by actor threads), and this step's ``blob`` is the small control
    blob from :meth:`~sheeprl_tpu.replay.DeviceReplayBuffer.make_ctl_job`
    (``__flags__``/``__valid__``/``__beta__`` only) — sampling, the key
    stream, and the PER tree still advance in-graph exactly as in the fused
    form (see ``howto/async_offpolicy.md``).

    One dispatch per env step does ALL of: append the staged transition row
    into the HBM ring (donated in-place scatter), draw every granted
    minibatch with device RNG — uniform over the valid ``(position, env)``
    grid, or proportional via the in-graph sum-tree when
    ``buffer.priority.enabled`` — and run the critic/EMA/actor/alpha updates
    as one scan. The write head, train-key stream, and PER tree live on
    device inside the replay state, so nothing round-trips to the host.

    Signature of the returned jitted fn::

        fn(params, aopt, copt, lopt, rb_state, blob)
            -> (params, aopt, copt, lopt, rb_state, qf, actor, alpha, skipped)

    ``blob`` is the packed flush from ``drb.make_job`` carrying the staged
    row, the per-step EMA flags, the granted-step valid mask, and the PER
    beta; ``skipped`` counts guard-rejected steps (0 when ``guard=False``).
    """
    from sheeprl_tpu.data.ring import unpack_burst_blob
    from sheeprl_tpu.ops.kernels import sumtree_sample
    from sheeprl_tpu.replay import sumtree as st

    gamma = float(cfg.algo.gamma)
    target_entropy = agent.target_entropy
    n_dev = mesh.devices.size
    capacity = drb.capacity
    n_envs = drb.n_envs
    e_local = drb.local_envs
    prioritized = drb.prioritized
    per_alpha = drb.per_alpha
    per_eps = drb.per_eps
    B = int(cfg.algo.per_rank_batch_size) // n_dev
    layout = drb.layout if append else drb.ctl_layout

    def minibatch_step(carry, xs, storage, vld, beta):
        # Padding steps beyond the granted chunk skip EVERYTHING via
        # lax.cond — sampling, losses, optimizer updates, and (crucially)
        # any params/opts select traffic (an unconditional jnp.where over
        # the train state costs ~1 ms/step of pure memory traffic on CPU).
        key, ema_flag, valid = xs

        def _run(carry):
            return _train_minibatch(carry, key, ema_flag, storage, vld, beta)

        def _skip(carry):
            zeros = jnp.float32(0.0)
            return carry, (zeros, zeros, zeros, zeros)

        return jax.lax.cond(valid > 0, _run, _skip, carry)

    def _train_minibatch(carry, key, ema_flag, storage, vld, beta, batch=None):
        params, aopt, copt, lopt, tree, max_p = carry
        old = (params, aopt, copt, lopt, tree, max_p)

        if batch is None:
            # -- in-graph sample (replay/indices semantics: uniform over the
            # valid grid — next-obs is stored explicitly, so no head
            # exclusion, exactly like the host buffer with
            # sample_next_obs=False)
            k_a, k_b, k_next, k_actor = jax.random.split(key, 4)
            if prioritized:
                u = jax.random.uniform(k_a, (B,))
                # fused descent + importance weights (ops.kernels registry;
                # lax backend reproduces the old two-pass st.sample +
                # st.importance_weights graph bit-for-bit)
                leaf, w = sumtree_sample(tree, u, vld * n_envs, beta)
                pos_idx = leaf // n_envs
                env_idx = leaf % n_envs
                w = w / jnp.maximum(jax.lax.pmax(w.max(), "dp"), 1e-12)
            else:
                pos_idx = jax.random.randint(k_a, (B,), 0, jnp.maximum(vld, 1))
                env_idx = jax.random.randint(k_b, (B,), 0, e_local)
                w = jnp.ones((B,), jnp.float32)
            batch = {
                k: storage[k][pos_idx, env_idx]
                for k in ("observations", "next_observations", "actions", "rewards", "terminated")
            }
        else:
            # pre-gathered variant: the batch arrives through the scan xs
            k_next, k_actor = jax.random.split(key)
            w = jnp.ones((jax.tree.leaves(batch)[0].shape[0],), jnp.float32)

        td_target = agent.next_target_q(
            params, batch["next_observations"], batch["rewards"], batch["terminated"], gamma, k_next
        )
        td_target = jax.lax.stop_gradient(td_target)

        def c_loss(cp):
            q = agent.q_values(cp, batch["observations"], batch["actions"])
            err2 = (q - td_target) ** 2
            # IS-weighted per-sample MSE (reduces to loss.critic_loss at w=1)
            return jnp.sum(jnp.mean(w[:, None] * err2, axis=0)), q

        (qf_loss, q_vals), cgrads = jax.value_and_grad(c_loss, has_aux=True)(params["critic"])
        cgrads = pmean_grads(cgrads, "dp")
        cupd, copt = critic_tx.update(cgrads, copt, params["critic"])
        params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}
        params = {**params, "target_critic": agent.ema(params["critic"], params["target_critic"], ema_flag)}

        alpha = jax.lax.stop_gradient(jnp.exp(params["log_alpha"]))

        def a_loss(ap):
            actions, logp = agent.sample_action(ap, batch["observations"], k_actor)
            q = agent.q_values(params["critic"], batch["observations"], actions)
            return policy_loss(alpha, logp, jnp.min(q, axis=-1, keepdims=True)), logp

        (actor_loss, logp), agrads = jax.value_and_grad(a_loss, has_aux=True)(params["actor"])
        agrads = pmean_grads(agrads, "dp")
        aupd, aopt = actor_tx.update(agrads, aopt, params["actor"])
        params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        def l_loss(la):
            return entropy_loss(la, jax.lax.stop_gradient(logp), target_entropy)

        alpha_loss, lgrads = jax.value_and_grad(l_loss)(params["log_alpha"])
        lgrads = pmean_grads(lgrads, "dp")
        lupd, lopt = alpha_tx.update(lgrads, lopt, params["log_alpha"])
        params = {**params, "log_alpha": optax.apply_updates(params["log_alpha"], lupd)}

        if prioritized:
            # |TD| → new priorities; the tree is replicated, so every device
            # applies the SAME update: all-gather the per-device leaf/prio
            # shards before the set+rebuild
            td_abs = jnp.mean(jnp.abs(jax.lax.stop_gradient(q_vals) - td_target), axis=-1)
            new_prio = jnp.power(td_abs + per_eps, per_alpha)
            leaf_all = jax.lax.all_gather(leaf, "dp").reshape(-1)
            prio_all = jax.lax.all_gather(new_prio, "dp").reshape(-1)
            tree = st.update(tree, leaf_all, prio_all)
            max_p = jnp.maximum(max_p, jax.lax.pmax(new_prio.max(), "dp"))

        skipped = jnp.float32(0.0)
        if guard:
            from sheeprl_tpu.ops import finite_guard, guarded_select

            ok = finite_guard((cgrads, agrads, lgrads, qf_loss, actor_loss, alpha_loss))
            ok = jax.lax.pmin(ok.astype(jnp.int32), "dp").astype(bool)
            params, aopt, copt, lopt, tree, max_p = guarded_select(
                ok, (params, aopt, copt, lopt, tree, max_p), old
            )
            skipped = 1.0 - ok.astype(jnp.float32)

        return (params, aopt, copt, lopt, tree, max_p), (qf_loss, actor_loss, alpha_loss, skipped)

    if not prioritized and not drb.shard_envs:
        # Pre-gathered variant (replicated storage + uniform sampling — the
        # common case): uniform draws are carry-independent, so ALL (G, B)
        # indices are drawn and gathered ONCE in the outer jit. The ring
        # never crosses the shard_map boundary (whose replicated outputs
        # cost a full-storage copy per dispatch), donation aliases it in
        # place, and the sharded scan consumes the exact (G, B)-sharded
        # data layout the host path's train step uses.
        def pre_step(carry, xs):
            batch, key, ema_flag, valid = xs

            def _run(c):
                return _train_minibatch(c, key, ema_flag, None, None, None, batch=batch)

            def _skip(c):
                zeros = jnp.float32(0.0)
                return c, (zeros, zeros, zeros, zeros)

            return jax.lax.cond(valid > 0, _run, _skip, carry)

        def pre_local_train(params, aopt, copt, lopt, data, key, flags, valid):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            keys = jax.random.split(key, grad_max)
            carry = (params, aopt, copt, lopt, jnp.zeros((2,), jnp.float32), jnp.ones((), jnp.float32))
            carry, outs = jax.lax.scan(pre_step, carry, (data, keys, flags, valid))
            params, aopt, copt, lopt = carry[:4]
            qf, al, ll, skipped = outs
            denom = jnp.maximum(valid.sum(), 1.0)
            qf, al, ll = jax.tree.map(
                lambda x: jax.lax.pmean((x * valid).sum() / denom, "dp"), (qf, al, ll)
            )
            return params, aopt, copt, lopt, qf, al, ll, skipped.sum()

        pre_shard = shard_map(
            pre_local_train,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(None, "dp"), P(), P(), P()),
            out_specs=(P(),) * 8,
            check_vma=False,
        )

        def packed_pre(params, aopt, copt, lopt, rb_state, blob):
            u = unpack_burst_blob(blob, layout)
            storage = rb_state["storage"]
            if append:
                staged = {k: u[k] for k in drb.specs}
                count = u["__count__"]
                # append: one in-place scatter; count==0 targets row
                # `capacity` and is dropped (backlog-drain dispatch)
                idx = jnp.where(count > 0, rb_state["pos"], capacity)
                storage = {k: storage[k].at[idx].set(staged[k][0], mode="drop") for k in storage}
                new_pos = (rb_state["pos"] + count) % capacity
                new_vld = jnp.minimum(rb_state["valid"] + count, capacity)
            else:
                new_pos = rb_state["pos"]
                new_vld = rb_state["valid"]
            state_key, sub = jax.random.split(rb_state["key"])
            k_pos, k_env, k_scan = jax.random.split(sub, 3)
            shape = (grad_max, B * n_dev)
            pos_idx = jax.random.randint(k_pos, shape, 0, jnp.maximum(new_vld, 1))
            env_idx = jax.random.randint(k_env, shape, 0, n_envs)
            data = {
                k: storage[k][pos_idx, env_idx]
                for k in ("observations", "next_observations", "actions", "rewards", "terminated")
            }
            params, aopt, copt, lopt, qf, al, ll, skipped = pre_shard(
                params, aopt, copt, lopt, data, k_scan, u["__flags__"], u["__valid__"]
            )
            new_state = {"storage": storage, "pos": new_pos, "valid": new_vld, "key": state_key}
            return params, aopt, copt, lopt, new_state, qf, al, ll, skipped

        # Everything here is replicated (this branch requires an unsharded
        # ring); pin the fed-back outputs' placements — graft-audit AUD002.
        from jax.sharding import NamedSharding

        return jax.jit(
            packed_pre,
            donate_argnums=(0, 1, 2, 3, 4) if donate else (4,),
            out_shardings=NamedSharding(mesh, P()),
        )

    def local_train(params, aopt, copt, lopt, storage, pos, vld, state_key, tree, max_p,
                    staged, count, flags, valid, beta):
        if append:
            # -- append: one in-place scatter; count==0 (backlog-drain
            # dispatch) targets row `capacity` and is dropped
            idx = jnp.where(count > 0, pos, capacity)
            storage = {k: storage[k].at[idx].set(staged[k][0], mode="drop") for k in storage}
            new_pos = (pos + count) % capacity
            new_vld = jnp.minimum(vld + count, capacity)
            if prioritized:
                # fresh transitions enter at the running max priority
                leaves = pos * n_envs + jnp.arange(n_envs, dtype=jnp.int32)
                prio = jnp.where(count > 0, max_p, st.get(tree, leaves))
                tree = st.update(tree, leaves, prio)
        else:
            # decoupled topology: the append rode its own dispatch
            new_pos, new_vld = pos, vld

        state_key, sub = jax.random.split(state_key)
        step_keys = jax.random.split(jax.random.fold_in(sub, jax.lax.axis_index("dp")), grad_max)
        carry = (params, aopt, copt, lopt, tree, max_p)
        carry, outs = jax.lax.scan(
            lambda c, xs: minibatch_step(c, xs, storage, new_vld, beta),
            carry,
            (step_keys, flags, valid),
        )
        params, aopt, copt, lopt, tree, max_p = carry
        qf, al, ll, skipped = outs
        denom = jnp.maximum(valid.sum(), 1.0)
        qf, al, ll = jax.tree.map(
            lambda x: jax.lax.pmean((x * valid).sum() / denom, "dp"), (qf, al, ll)
        )
        return (params, aopt, copt, lopt, storage, new_pos, new_vld, state_key, tree, max_p,
                qf, al, ll, skipped.sum())

    storage_spec = P(None, "dp") if drb.shard_envs else P()
    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), storage_spec, P(), P(), P(), P(), P(),
                  storage_spec, P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), storage_spec, P(), P(), P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )

    def packed(params, aopt, copt, lopt, rb_state, blob):
        u = unpack_burst_blob(blob, layout)
        # append=False ships no transition segments: an empty staged pytree
        # and a zero count make the scatter a statically-skipped branch
        staged = {k: u[k] for k in drb.specs} if append else {}
        count = u["__count__"] if append else jnp.zeros((), jnp.int32)
        tree = rb_state.get("tree", jnp.zeros((2,), jnp.float32))
        max_p = rb_state.get("max_p", jnp.ones((), jnp.float32))
        (params, aopt, copt, lopt, storage, pos, vld, key, tree, max_p, qf, al, ll, skipped
         ) = shard_train(
            params, aopt, copt, lopt,
            rb_state["storage"], rb_state["pos"], rb_state["valid"], rb_state["key"], tree, max_p,
            staged, count, u["__flags__"], u["__valid__"], u["__beta__"],
        )
        new_state = {"storage": storage, "pos": pos, "valid": vld, "key": key}
        if prioritized:
            new_state["tree"] = tree
            new_state["max_p"] = max_p
        return params, aopt, copt, lopt, new_state, qf, al, ll, skipped

    # Pin every fed-back output's placement — the env-sharded ring storage is
    # EXACTLY the PR 8 shape (donated, sharded, fed back every step): left to
    # inference, jit may canonicalize it to an equivalent placement with a
    # different C++ jit-cache key and silently recompile on the next dispatch
    # (graft-lint GL008 / graft-audit AUD002).
    from jax.sharding import NamedSharding

    rep_out = NamedSharding(mesh, P())
    state_out: Dict[str, Any] = {
        "storage": NamedSharding(mesh, storage_spec),
        "pos": rep_out,
        "valid": rep_out,
        "key": rep_out,
    }
    if prioritized:
        state_out.update(tree=rep_out, max_p=rep_out)
    return jax.jit(
        packed,
        donate_argnums=(0, 1, 2, 3, 4) if donate else (4,),
        out_shardings=(rep_out, rep_out, rep_out, rep_out, state_out) + (rep_out,) * 4,
    )


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.fault import load_resume_state
    from sheeprl_tpu.optim.builders import build_optimizer

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    envs = vectorize_env(cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train")
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}."
            )
    if cfg.metric.log_level > 0:
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)

    agent, params, player = build_agent(
        fabric, cfg, observation_space, action_space, state["agent"] if state is not None else None
    )

    critic_tx = build_optimizer(cfg.algo.critic.optimizer)
    actor_tx = build_optimizer(cfg.algo.actor.optimizer)
    alpha_tx = build_optimizer(cfg.algo.alpha.optimizer)
    copt = critic_tx.init(params["critic"])
    aopt = actor_tx.init(params["actor"])
    lopt = alpha_tx.init(params["log_alpha"])
    if state is not None:
        aopt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, aopt, state["actor_optimizer"])
        copt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, copt, state["qf_optimizer"])
        lopt = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, lopt, state["alpha_optimizer"])
    aopt, copt, lopt = (fabric.put_replicated(o) for o in (aopt, copt, lopt))

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Local data (reference: sac.py:183-199)
    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=("observations",),
    )
    resident_restore = None  # a DeviceReplayState checkpointed by the resident path
    if state is not None and cfg.buffer.checkpoint:
        from sheeprl_tpu.replay import DeviceReplayState

        if isinstance(state["rb"], list):
            rb = state["rb"][0]
        elif isinstance(state["rb"], ReplayBuffer):
            rb = state["rb"]
        elif isinstance(state["rb"], DeviceReplayState):
            resident_restore = state["rb"]
            # fill the host buffer too, so a resume that lands on the host
            # path (spillover, knob flipped off, hybrid burst) keeps the data
            from sheeprl_tpu.replay.device_buffer import restore_host_buffer

            restore_host_buffer(resident_restore, rb, fill_missing={"truncated": ((1,), np.uint8)})
        else:
            raise RuntimeError(f"Cannot restore the replay buffer from {type(state['rb'])}")

    # Counters (reference: sac.py:201-226; single-process world — see module docstring)
    last_train = 0
    train_step = 0
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

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    batch_size = int(cfg.algo.per_rank_batch_size)
    if batch_size % fabric.world_size != 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) must be divisible by the number of devices ({fabric.world_size})"
        )
    # TPU-native overlap: when the trainer mesh lives on an accelerator, the
    # env-side policy runs on the host CPU from a params snapshot refreshed
    # every `refresh_every` iterations (double-buffered, so the snapshot
    # transfer overlaps the env loop and the host never blocks on the device
    # queue or on a per-step action pull). The device params
    # stay the source of truth; actions are one snapshot stale, the same
    # trade the reference's decoupled topology makes (`sac_decoupled.py`).
    hp_cfg = cfg.algo.get("hybrid_player") or {}
    hp_enabled = resolve_hybrid_player(hp_cfg, fabric.mesh)
    hp_refresh = max(1, int(hp_cfg.get("refresh_every", 64)))
    host_actor_params = None
    host_rng = None
    _host_sample = None
    last_refresh = 0
    if hp_enabled:
        from sheeprl_tpu.utils.burst import HostSnapshot

        # SAC's actor is tiny, so the packed snapshot stays full-precision
        # (the Dreamer harness narrows to bf16 where the wire is the cost).
        snapshot = HostSnapshot(lambda p: p["actor"], params, wire_dtype=jnp.float32)
        host_actor_params = snapshot.pull(params)
        host_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 17), snapshot.host_device)
        _host_sample = jax.jit(lambda ap, o, k: agent.sample_action(ap, o, k)[0])

    # Burst training (TPU-native, see make_burst_train_step): dispatch the
    # accumulated Ratio grants every `train_every` iterations against a
    # device-resident replay mirror instead of shipping host samples each
    # iteration. `auto` turns it on together with the hybrid player.
    train_every = hp_cfg.get("train_every", "auto")
    if isinstance(train_every, str):
        train_every = (64 if hp_enabled else 1) if train_every == "auto" else int(train_every)
    train_every = max(1, int(train_every))
    burst_mode = hp_enabled and train_every > 1
    if burst_mode and cfg.buffer.sample_next_obs:
        warnings.warn("buffer.sample_next_obs is not supported by burst training; disabling the burst path.")
        burst_mode = False
    ema_modulus = int(cfg.algo.critic.target_network_frequency) // policy_steps_per_iter + 1

    # Divergence sentinel: in-graph guard on the plain train path (the burst
    # path dispatches from a trainer thread and keeps its own valid-mask
    # no-op machinery; its guard integration is future work).
    from sheeprl_tpu.fault import DivergenceSentinel

    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True)) and not burst_mode
    sentinel = DivergenceSentinel(sentinel_cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    # Donation would invalidate the params buffers while a host snapshot
    # transfer is still in flight; SAC params are tiny, so keep them.
    train_fn = None
    burst_fn = None
    resident_fn = None
    obs_dim = int(sum(np.prod(observation_space[k].shape) for k in cfg.algo.mlp_keys.encoder))
    act_dim = int(np.prod(action_space.shape))

    # Device-resident replay (howto/device_replay.md): the HBM ring +
    # in-graph sampling makes sample+train ONE dispatch per env step. The
    # hybrid burst path is already device-resident (and asynchronous), so
    # the knob targets the standard coupled topology only; capacities beyond
    # the HBM budget spill over to the host buffer path below.
    resident_mode = False
    drb = None
    resident_specs = {
        "observations": ((obs_dim,), jnp.float32),
        "next_observations": ((obs_dim,), jnp.float32),
        "actions": ((act_dim,), jnp.float32),
        "rewards": ((1,), jnp.float32),
        "terminated": ((1,), jnp.float32),
    }
    per_cfg = cfg.buffer.get("priority") or {}
    prioritized = bool(per_cfg.get("enabled", False))
    if not burst_mode:
        from sheeprl_tpu.replay import resolve_device_resident

        resident_mode, shard_envs, resident_reason = resolve_device_resident(
            cfg.buffer.get("device_resident", False),
            resident_specs,
            buffer_size,
            int(cfg.env.num_envs),
            fabric.world_size,
            float(cfg.buffer.get("hbm_budget_gb", 4.0)),
            prioritized,
        )
        if resident_mode and cfg.buffer.sample_next_obs:
            warnings.warn(
                "buffer.sample_next_obs stores no explicit next observation; the device-resident "
                "ring needs one — falling back to the host buffer path."
            )
            resident_mode = False
        if cfg.metric.log_level > 0 and cfg.buffer.get("device_resident", False):
            print(f"Replay: device_resident={resident_mode} ({resident_reason})")
    if burst_mode:
        grad_chunk = max(1, int(round(cfg.algo.replay_ratio * policy_steps_per_iter * train_every)))
        # Sized from the CONFIGURED warmup, not the resume-shifted
        # `learning_starts` (which has start_iter added on resume) — the
        # staging buffer only ever holds transitions since the last flush.
        base_learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
        stage_max = min(base_learning_starts + 2 * train_every + 1, buffer_size)
        dims = {
            "observations": obs_dim, "next_observations": obs_dim,
            "actions": act_dim, "rewards": 1, "terminated": 1,
        }
        burst_fn, burst_layout = make_burst_train_step(
            agent, actor_tx, critic_tx, alpha_tx, cfg, fabric.mesh,
            capacity=buffer_size, n_envs=int(cfg.env.num_envs), stage_max=stage_max, grad_chunk=grad_chunk,
            dims=dims,
        )
        # The flat transition ring, zeroed ON the device (a host zeros +
        # device_put would build it in host memory first). Its own `.at[].set`
        # append and gather address `(capacity, n_envs, d)` rows: it does not
        # share the sequence ring's stored view (`data/ring.py:ring_cell`).
        rb_dev = jax.jit(
            lambda: {k: jnp.zeros((buffer_size, int(cfg.env.num_envs), d), jnp.float32) for k, d in dims.items()},
            out_shardings={k: fabric.replicated for k in dims},
        )()
        dev_pos, dev_total = 0, 0
        if state is not None and cfg.buffer.checkpoint and not rb.empty:
            # Mirror the restored host buffer onto the device ring.
            for k in rb_dev:
                host = np.asarray(rb.buffer[k], dtype=np.float32).reshape(buffer_size, int(cfg.env.num_envs), -1)
                rb_dev[k] = fabric.put_replicated(jnp.asarray(host))
            dev_pos, dev_total = rb._pos, (buffer_size if rb.full else rb._pos)
        staged: list = []
        ema_backlog: list = []

        # The burst dispatch blocks its caller for the upload and, with the
        # queue full, for the device, so it runs on a trainer thread (shared machinery, `utils/burst.py`):
        # the env loop hands staged transitions over a bounded queue and
        # keeps stepping with the previous snapshot; the thread owns the
        # params/opt/ring futures and refreshes the host policy snapshot
        # once per burst.
        from sheeprl_tpu.utils.burst import TrainerThread

        def _burst_step(carry, job):
            params_, aopt_, copt_, lopt_, rb_dev_ = carry
            params_, aopt_, copt_, lopt_, rb_dev_, qf_l, a_l, al_l = burst_fn(
                params_, aopt_, copt_, lopt_, rb_dev_, job
            )
            return (params_, aopt_, copt_, lopt_, rb_dev_), (qf_l, a_l, al_l)

        trainer = TrainerThread(
            _burst_step,
            (params, aopt, copt, lopt, rb_dev),
            # refresh_async: the packed pull would otherwise block this
            # trainer thread for a wire round-trip per burst (single-caller
            # contract holds — only the trainer thread calls it).
            on_step=lambda carry, _m: snapshot.refresh_async(carry[0]),
            supervisor_cfg=(cfg.get("fault") or {}).get("supervisor"),
        )
        # refresh pulls ride the trainer's supervisor (restart ladder instead
        # of a silently frozen host policy on a dead one-shot pull thread)
        snapshot.attach_supervisor(trainer.supervisor)

        def _flush_burst():
            """Ship the staged transitions + up to one grant chunk to the
            trainer thread (padded scan steps are no-ops via the valid
            mask)."""
            nonlocal rng, dev_pos, dev_total, cumulative_per_rank_gradient_steps, train_step
            count = len(staged)
            pad = stage_max - count
            if count:
                staged_arr = {
                    k: np.concatenate(
                        [np.stack([t[k] for t in staged])]
                        + ([np.zeros((pad,) + staged[0][k].shape, np.float32)] if pad else []),
                        axis=0,
                    )
                    for k in rb_dev
                }
            else:
                staged_arr = {
                    k: np.zeros((stage_max,) + tuple(v.shape[1:]), np.float32) for k, v in rb_dev.items()
                }
            staged.clear()
            dev_total = min(dev_total + count, buffer_size)
            chunk = min(grad_chunk, len(ema_backlog))
            flags = np.zeros((grad_chunk,), np.float32)
            valid = np.zeros((grad_chunk,), np.float32)
            flags[:chunk] = ema_backlog[:chunk]
            valid[:chunk] = 1.0
            with timer("Time/train_time", SumMetric):
                rng, train_key = jax.random.split(rng)
                values = dict(staged_arr)
                values["__pos__"] = np.asarray(dev_pos, np.int32)
                values["__count__"] = np.asarray(count, np.int32)
                values["__valid_n__"] = np.asarray(dev_total, np.int32)
                values["__key__"] = np.asarray(train_key, np.uint32)
                values["__flags__"] = flags
                values["__valid__"] = valid
                trainer.submit(pack_burst_blob(burst_layout, values))
                latest = trainer.metrics
                if aggregator and not aggregator.disabled and latest is not None:
                    qf_l, a_l, al_l = latest
                    aggregator.update("Loss/value_loss", qf_l)
                    aggregator.update("Loss/policy_loss", a_l)
                    aggregator.update("Loss/alpha_loss", al_l)
            dev_pos = (dev_pos + count) % buffer_size
            del ema_backlog[:chunk]
            if chunk > 0:
                cumulative_per_rank_gradient_steps += chunk
                train_step += 1
    elif resident_mode:
        from sheeprl_tpu.replay import DeviceReplayBuffer

        grad_max = max(1, int(np.ceil(cfg.algo.replay_ratio * policy_steps_per_iter)))
        drb = DeviceReplayBuffer(
            fabric,
            resident_specs,
            buffer_size,
            int(cfg.env.num_envs),
            prioritized=prioritized,
            per_alpha=float(per_cfg.get("alpha", 0.6)),
            per_eps=float(per_cfg.get("eps", 1e-6)),
            shard_envs=shard_envs,
            extra_spec=[
                ("__flags__", (grad_max,), np.float32),
                ("__valid__", (grad_max,), np.float32),
                ("__beta__", (), np.float32),
            ],
            seed=cfg.seed + 29,
        )
        if resident_restore is not None:
            drb.load_state_dict(resident_restore)
        elif state is not None and cfg.buffer.checkpoint and not rb.empty:
            # resumed from a host-buffer checkpoint: mirror it into HBM
            drb.load_host_buffer(rb)
        resident_fn = tracecheck.instrument(
            make_resident_train_step(
                agent, actor_tx, critic_tx, alpha_tx, cfg, fabric.mesh, drb, grad_max,
                guard=guard, donate=not hp_enabled,
            ),
            name="sac.resident_step",
        )
        ema_backlog = []
        per_beta0 = float(per_cfg.get("beta", 0.4))
    else:
        # warmup=2: the first post-learning-starts grant replays the prefill
        # backlog in one oversized (G, B) batch, a legitimate second
        # signature. budget=2: a fractional replay_ratio alternates between
        # adjacent grant sizes — a couple of shape variants are the contract,
        # anything past that is drift.
        train_fn = tracecheck.instrument(
            make_train_step(
                agent, actor_tx, critic_tx, alpha_tx, cfg, fabric.mesh, donate=not hp_enabled, guard=guard
            ),
            name="sac.train_step",
            warmup=2,
            budget=2,
        )
    data_sharding = NamedSharding(fabric.mesh, P(None, "dp"))

    rng = jax.random.PRNGKey(cfg.seed)
    if state is not None and state.get("rng") is not None:
        rng = jnp.asarray(state["rng"])  # continue the killed run's stream
    if burst_mode:
        # Host-resident key stream (threefry is platform-deterministic, so
        # the values are unchanged): the burst path consumes keys on the
        # host — action sampling on the CPU policy, key bytes packed into
        # the burst blob — and a device-resident key would cost a device
        # pull per flush. Burst mode only: the non-burst hybrid path still
        # feeds train_fn on the mesh, which rejects a CPU-committed key.
        rng = jax.device_put(rng, snapshot.host_device)
    mlp_keys = cfg.algo.mlp_keys.encoder

    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]

    cumulative_per_rank_gradient_steps = 0
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter

        # Swap in a finished off-thread snapshot; outside burst mode, also
        # start the next pull once the refresh period has elapsed (in burst
        # mode the trainer thread refreshes once per burst).
        if hp_enabled:
            fresh = snapshot.poll()
            if fresh is not None:
                host_actor_params = fresh
            if (
                not burst_mode
                and iter_num - last_refresh >= hp_refresh
                and iter_num > learning_starts
                and snapshot.refresh_async(params)
            ):
                last_refresh = iter_num

        with timer("Time/env_interaction_time", SumMetric):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
            elif hp_enabled:
                flat_obs = np.concatenate(
                    [np.asarray(obs[k], dtype=np.float32) for k in mlp_keys], axis=-1
                ).reshape(cfg.env.num_envs, -1)
                host_rng, subkey = jax.random.split(host_rng)
                actions = np.asarray(_host_sample(host_actor_params, flat_obs, subkey))
            else:
                jobs = prepare_obs(fabric, obs, mlp_keys=mlp_keys, num_envs=cfg.env.num_envs)
                rng, subkey = jax.random.split(rng)
                actions = np.asarray(player(params, jobs, subkey))
            next_obs, rewards, terminated, truncated, infos = envs.step(actions.reshape(envs.action_space.shape))
            rewards = np.asarray(rewards, dtype=np.float32).reshape(cfg.env.num_envs, -1)

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

        step_data["terminated"] = np.asarray(terminated, dtype=np.uint8).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.uint8).reshape(1, cfg.env.num_envs, -1)
        step_data["actions"] = np.asarray(actions, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["observations"] = np.concatenate(
            [np.asarray(obs[k], dtype=np.float32) for k in mlp_keys], axis=-1
        ).reshape(1, cfg.env.num_envs, -1)
        if not cfg.buffer.sample_next_obs:
            # Save the real next observation, patching truncated envs with
            # their final obs (reference: sac.py:278-287)
            real_next_obs = copy.deepcopy(next_obs)
            if "final_obs" in infos:
                for idx, final_obs in enumerate(infos["final_obs"]):
                    if final_obs is not None:
                        for k, v in final_obs.items():
                            real_next_obs[k][idx] = v
            step_data["next_observations"] = np.concatenate(
                [np.asarray(real_next_obs[k], dtype=np.float32) for k in mlp_keys], axis=-1
            ).reshape(1, cfg.env.num_envs, -1)
        step_data["rewards"] = rewards[np.newaxis]
        if resident_mode:
            # the HBM ring is the only storage tier — no host duplicate; it
            # is checkpointed directly (DeviceReplayState) below
            drb.add(step_data)
        else:
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        # Train (reference: sac.py:297-356)
        if burst_mode:
            # Stage the transition for the device ring; host rb stays the
            # checkpoint source of truth.
            staged.append({k: np.asarray(step_data[k][0], dtype=np.float32) for k in rb_dev})
            if iter_num >= learning_starts:
                granted = ratio(policy_step - prefill_steps + policy_steps_per_iter)
                ema_backlog.extend([1.0 if iter_num % ema_modulus == 0 else 0.0] * granted)
            # Dispatch one burst when a full grant chunk is queued, or flush
            # the staging area if it is about to overflow (low replay
            # ratios); padded scan steps are no-ops via the valid mask.
            while len(ema_backlog) >= grad_chunk or len(staged) >= stage_max - 1:
                _flush_burst()
                if len(ema_backlog) < grad_chunk:
                    break
        elif resident_mode:
            if iter_num >= learning_starts:
                granted = ratio(policy_step - prefill_steps + policy_steps_per_iter)
                ema_backlog.extend([1.0 if iter_num % ema_modulus == 0 else 0.0] * granted)
            # ONE dispatch per env step: append the staged row + run up to
            # grad_max granted steps sampled in-graph; extra append-free
            # dispatches drain any backlog a big first grant left behind.
            while True:
                chunk = min(grad_max, len(ema_backlog))
                flags = np.zeros((grad_max,), np.float32)
                valid_mask = np.zeros((grad_max,), np.float32)
                flags[:chunk] = ema_backlog[:chunk]
                valid_mask[:chunk] = 1.0
                if prioritized:
                    frac = min(1.0, policy_step / max(1, int(cfg.algo.total_steps)))
                    beta = per_beta0 + (1.0 - per_beta0) * frac  # anneal beta → 1
                else:
                    beta = 0.0
                # Device-resident replay path: ONE packed blob per step is
                # all the host ever does — sampling itself rides inside the
                # train dispatch (the host-side counterpart of the host
                # tier's sample+stage segment, for apples-to-apples timing).
                with timer("Time/replay_path_time", SumMetric):
                    blob = drb.make_job(
                        {"__flags__": flags, "__valid__": valid_mask, "__beta__": np.float32(beta)}
                    )
                with timer("Time/train_time", SumMetric):
                    t0 = time.perf_counter()
                    outs = resident_fn(params, aopt, copt, lopt, drb.state, blob)
                    params, aopt, copt, lopt, drb.state = outs[:5]
                    drb.note_dispatch_latency(time.perf_counter() - t0)
                del ema_backlog[:chunk]
                if chunk > 0:
                    qf_l, a_l, al_l = outs[5:8]
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Loss/value_loss", qf_l)
                        aggregator.update("Loss/policy_loss", a_l)
                        aggregator.update("Loss/alpha_loss", al_l)
                    cumulative_per_rank_gradient_steps += chunk
                    train_step += 1
                    if guard and sentinel.observe(outs[8]):
                        def _rollback_res(good):
                            nonlocal params, aopt, copt, lopt, rng
                            params, aopt, copt, lopt, rng = restore_train_state(
                                fabric, good, params, aopt, copt, lopt, rng
                            )

                        sentinel.recover(ckpt_dir, _rollback_res)
                if len(ema_backlog) < grad_max:
                    break
        elif iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step - prefill_steps + policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                # Host-side replay path: numpy sampling + staging to device.
                # Timed separately (Time/replay_path_time) because it is the
                # serialized host-in-the-loop segment the device-resident
                # buffer eliminates — BENCH_METRIC=replay reports throughput
                # against exactly this time.
                with timer("Time/replay_path_time", SumMetric):
                    sample = rb.sample(
                        batch_size=batch_size,
                        n_samples=per_rank_gradient_steps,
                        sample_next_obs=cfg.buffer.sample_next_obs,
                    )  # (G, B, ...)
                    # ONE packed sharded transfer for the whole sample dict
                    # (the PR-3 stager trick) instead of K per-key device_put
                    # dispatches
                    data = put_packed(sample, data_sharding, dtype=np.float32)
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    ema_flag = jnp.float32(1.0 if iter_num % ema_modulus == 0 else 0.0)
                    outs = train_fn(params, aopt, copt, lopt, data, train_key, ema_flag)
                    params, aopt, copt, lopt, qf_l, a_l, al_l = outs[:7]
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Loss/value_loss", qf_l)
                        aggregator.update("Loss/policy_loss", a_l)
                        aggregator.update("Loss/alpha_loss", al_l)
                cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                train_step += 1
                if guard and sentinel.observe(outs[7]):
                    def _rollback(good):
                        nonlocal params, aopt, copt, lopt, rng
                        params, aopt, copt, lopt, rng = restore_train_state(
                            fabric, good, params, aopt, copt, lopt, rng
                        )

                    sentinel.recover(ckpt_dir, _rollback)

        # Logging (reference: sac.py:358-392)
        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
            restarts = getattr(envs, "env_restarts", 0)
            if restarts:
                logger.log_dict({"Fault/env_restarts": restarts}, policy_step)
            if guard and sentinel.total_skipped:
                logger.log_dict({"Fault/skipped_updates": sentinel.total_skipped}, policy_step)
            if resident_mode:
                logger.log_dict(drb.metrics(), policy_step)
            if aggregator and not aggregator.disabled:
                logger.log_dict(aggregator.compute(), policy_step)
                aggregator.reset()
            if policy_step > 0:
                logger.log_dict(
                    {"Params/replay_ratio": cumulative_per_rank_gradient_steps / policy_step}, policy_step
                )
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

        # Checkpoint (reference: sac.py:394-420)
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            if burst_mode:
                # Latest trainer-thread handles (at most one burst stale).
                params, aopt, copt, lopt, _ = trainer.carry
            ckpt_state = {
                "agent": params,
                "qf_optimizer": copt,
                "actor_optimizer": aopt,
                "alpha_optimizer": lopt,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "rng": rng,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            replay_ckpt = None
            if cfg.buffer.checkpoint:
                # resident mode checkpoints the device ring itself (pulled to
                # host as a DeviceReplayState), tree and key stream included
                replay_ckpt = drb.state_dict() if resident_mode else rb
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=replay_ckpt,
            )

    if burst_mode:
        # Flush the tail: Ratio already counted any remaining grants, so they
        # must be executed (a reference run would have applied them).
        while staged or ema_backlog:
            _flush_burst()
        params, aopt, copt, lopt, _ = trainer.close()

    envs.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.algos.sac.utils import log_models
        from sheeprl_tpu.utils.mlflow import register_model

        register_model(fabric, log_models, cfg, {"agent": params})
    logger.close()


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


def audit_sac_setup(spec: AuditMesh, stage_rows: int = 1, grad_max: int = 2):
    """Tiny continuous-control SAC context on the audit mesh (shared with the
    ``sac_sebulba.*`` registrations): agent + optimizers + an env-sharded
    DeviceReplayBuffer, all with the driver's staging shardings."""
    from sheeprl_tpu.algos.ppo.ppo import _abstract_like
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.replay import DeviceReplayBuffer

    num_envs = 2 * spec.devices
    cfg = compose(
        [
            "exp=sac",
            "env=dummy",
            "env.id=continuous_dummy",
            f"env.num_envs={num_envs}",
            "algo.mlp_keys.encoder=[state]",
            "algo.per_rank_batch_size=8",
        ]
    )
    fabric = Fabric(devices=spec.devices, accelerator="cpu")
    obs_dim, act_dim = 4, 2
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, (obs_dim,), np.float32)})
    act_space = gym.spaces.Box(-1.0, 1.0, (act_dim,), np.float32)
    agent, params, player = build_agent(fabric, cfg, obs_space, act_space, None)
    actor_tx = build_optimizer(cfg.algo.actor.optimizer)
    critic_tx = build_optimizer(cfg.algo.critic.optimizer)
    alpha_tx = build_optimizer(cfg.algo.alpha.optimizer)
    aopt = actor_tx.init(params["actor"])
    copt = critic_tx.init(params["critic"])
    lopt = alpha_tx.init(params["log_alpha"])
    resident_specs = {
        "observations": ((obs_dim,), jnp.float32),
        "next_observations": ((obs_dim,), jnp.float32),
        "actions": ((act_dim,), jnp.float32),
        "rewards": ((1,), jnp.float32),
        "terminated": ((1,), jnp.float32),
    }
    drb = DeviceReplayBuffer(
        fabric,
        resident_specs,
        16,
        num_envs,
        shard_envs=True,
        stage_rows=stage_rows,
        extra_spec=[
            ("__flags__", (grad_max,), np.float32),
            ("__valid__", (grad_max,), np.float32),
            ("__beta__", (), np.float32),
        ],
        seed=29,
    )
    rep = fabric.replicated
    return {
        "cfg": cfg,
        "fabric": fabric,
        "mesh": fabric.mesh,
        "agent": agent,
        "player": player,
        "params": _abstract_like(params, rep),
        "aopt": _abstract_like(aopt, rep),
        "copt": _abstract_like(copt, rep),
        "lopt": _abstract_like(lopt, rep),
        "txs": (actor_tx, critic_tx, alpha_tx),
        "drb": drb,
        "grad_max": grad_max,
        "num_envs": num_envs,
        "obs_dim": obs_dim,
        "act_dim": act_dim,
        "rep": rep,
        # ring state avals keep each leaf's OWN committed sharding (storage
        # env-sharded, heads/key replicated)
        "rb_state": _abstract_like(drb.state),
        "key": jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        "scalar": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
    }


@register_audit_programs("sac.train_step", "sac.resident_step", "sac.rollout_step")
def _audit_programs(spec: AuditMesh):
    s = audit_sac_setup(spec)
    actor_tx, critic_tx, alpha_tx = s["txs"]
    G, B = 2, 8 * spec.devices
    data_sh = NamedSharding(s["mesh"], P(None, "dp"))
    data = {
        "observations": jax.ShapeDtypeStruct((G, B, s["obs_dim"]), jnp.float32, sharding=data_sh),
        "next_observations": jax.ShapeDtypeStruct((G, B, s["obs_dim"]), jnp.float32, sharding=data_sh),
        "actions": jax.ShapeDtypeStruct((G, B, s["act_dim"]), jnp.float32, sharding=data_sh),
        "rewards": jax.ShapeDtypeStruct((G, B, 1), jnp.float32, sharding=data_sh),
        "terminated": jax.ShapeDtypeStruct((G, B, 1), jnp.float32, sharding=data_sh),
    }
    train_fn = make_train_step(
        s["agent"], actor_tx, critic_tx, alpha_tx, s["cfg"], s["mesh"], donate=True, guard=True
    )
    yield AuditProgram(
        name="sac.train_step",
        fn=train_fn,
        args=(s["params"], s["aopt"], s["copt"], s["lopt"], data, s["key"], s["scalar"]),
        source=__name__,
        donate_argnums=(0, 1, 2, 3),
        feedback_outputs=(0, 1, 2, 3),
        out_decl={0: P(), 1: P(), 2: P(), 3: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )

    resident_fn = make_resident_train_step(
        s["agent"], actor_tx, critic_tx, alpha_tx, s["cfg"], s["mesh"], s["drb"], s["grad_max"],
        guard=True, donate=True, append=True,
    )
    blob = jax.ShapeDtypeStruct((s["drb"].layout.nbytes,), jnp.uint8, sharding=s["rep"])
    yield AuditProgram(
        name="sac.resident_step",
        fn=resident_fn,
        args=(s["params"], s["aopt"], s["copt"], s["lopt"], s["rb_state"], blob),
        source=__name__,
        donate_argnums=(0, 1, 2, 3, 4),
        # the ring state (output 4) carries MIXED placements (env-sharded
        # storage + replicated heads): the pin check covers it, the uniform
        # out_decl placement check covers the train state
        feedback_outputs=(0, 1, 2, 3, 4),
        out_decl={0: P(), 1: P(), 2: P(), 3: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )

    yield AuditProgram(
        name="sac.rollout_step",
        fn=s["player"]._sample.__wrapped__,
        args=(
            # the player samples on the ACTOR subtree of the params snapshot;
            # obs arrive as HOST arrays by contract (prepare_obs)
            s["params"]["actor"],
            jax.ShapeDtypeStruct((s["num_envs"], s["obs_dim"]), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
        source=__name__,
        mesh=s["mesh"],
        check_input_shardings=False,
    )
