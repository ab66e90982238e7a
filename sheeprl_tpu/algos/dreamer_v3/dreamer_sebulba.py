"""DreamerV3 — Sebulba-style decoupled actor/learner over the async
per-env-head device sequence ring (async model-based off-policy; no reference
counterpart).

This main fuses the two halves PR 6 deliberately left apart: the Sebulba
actor/learner pipeline (``parallel/pipeline.py``: bounded
:class:`RolloutQueue`, versioned :class:`ParamServer`, supervised actor
pools) and the Dreamer sequence ring (``data/ring.py`` ragged burst indices,
``replay/driver.py``). The piece that was missing — and the reason
``howto/async_offpolicy.md`` carried a deferral note — is the **ragged
per-env-head append**: Dreamer replay is per-env sequence columns whose
write heads advance raggedly (reset rows advance only the done envs), so N
concurrent actors cannot share the SAC ring's single scalar head. Here:

- **N supervised actor threads** (``algo.sebulba.num_actor_threads``; the
  PR 10 heartbeat-lease runtime via ``pipeline.supervised_actor_pool``) each
  step their own :class:`FastSyncVectorEnv` batch through a jitted
  RSSM-player program on newest-wins player snapshots from the
  :class:`ParamServer` — the recurrent/posterior carry stays ACTOR-side,
  threaded through the program, with episode-boundary re-init folded
  IN-GRAPH (a ``where``-merge of the params-derived initial states into rows
  flagged ``is_first``, so reset events never retrace). Every
  ``algo.sebulba.rollout_block`` env steps an actor packs its per-env
  sequence heads — regular all-env rows plus ragged reset rows — into ONE
  uint8 blob (:meth:`AsyncSequenceRing.pack_rows`, a pure function:
  concurrent writers never race) and hands it through the deadline-guarded
  queue;
- the **learner** (main thread) commits each blob with ONE donated ragged
  multi-head scatter dispatch into the HBM sequence ring (per-env write
  heads advance in-graph) and trains at its OWN ``Ratio``-governed
  replay-ratio cadence: each train dispatch samples its ``(T, B)`` windows
  in-graph against the LIVE per-env head validity (the
  ``SequentialReplayBuffer`` rule — a window never crosses its env's head)
  and scans the granted gradient steps, with the train-key stream riding the
  ring state on device.

Rate coupling is the same two instrumented mechanisms as ``sac_sebulba``:
queue back-pressure and the grad-steps-per-env-step governor
(``Pipeline/replay_ratio_actual`` is a logged gauge).

Fault wiring from day one: the in-graph divergence sentinel (a guarded
gradient step rolls back params/opts/moments on a non-finite verdict) with a
forced re-publish after recovery; ``on_checkpoint_coupled`` saves carrying
the ring (storage + per-env heads + device train-key) in the ``.rb`` sidecar
plus BOTH host RNG streams and the ``Ratio`` state;
``checkpoint.resume_from=latest``; chaos points on the actor step
(``dreamer_sebulba.actor{N}.step``) and both queue handoffs.

This unlocks the whole Dreamer family for the async economy — v1/v2/p2e
share the burst row layout, so their sebulba twins are config + carry-shape
work, not new machinery.
"""

from __future__ import annotations

import os
import queue as _queue
import threading
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import actor_sample, build_agent, extract_obs_masks
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments, prepare_obs, test
from sheeprl_tpu.analysis.lockstats import sync_lock
from sheeprl_tpu.analysis.tracecheck import tracecheck
from sheeprl_tpu.data.ring import pack_burst_blob
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.fault.inject import arm_from_cfg, fault_point
from sheeprl_tpu.parallel.pipeline import (
    ParamServer,
    PipelineStats,
    RolloutQueue,
    staleness_bound,
    supervised_actor_pool,
)
from sheeprl_tpu.utils.burst import DREAMER_METRIC_NAMES, dreamer_ring_keys
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs

__all__ = ["main", "make_act_step", "player_subset"]


def player_subset(params: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves the actor-side player needs (what the ParamServer
    publishes): encoder + recurrent/representation/transition models + the
    learnable initial recurrent state + the actor — decoders, critics and
    optimizer state never cross to the actor slice."""
    wm = params["world_model"]
    return {
        "world_model": {
            "encoder": wm["encoder"],
            "recurrent_model": wm["recurrent_model"],
            "representation_model": wm["representation_model"],
            "transition_model": wm["transition_model"],
            "initial_recurrent_state": wm["initial_recurrent_state"],
        },
        "actor": params["actor"],
    }


def make_act_step(world_model, actor):
    """Actor-side per-step program: the :class:`PlayerDV3` RSSM step with the
    episode-boundary re-init FOLDED IN — rows flagged ``is_first`` first
    ``where``-merge the params-derived initial states (and a zero action
    carry) over their recurrent/posterior columns, so a reset of ANY subset
    of envs is the same abstract signature as no reset at all (zero
    retraces; the same trick ``serve.sessions`` uses for fresh rows). The
    initial recurrent state re-derives from the LIVE published weights
    (``learnable_initial_recurrent_state``). Module-level so the graft-audit
    registry lowers the SAME program the actor threads dispatch."""
    rssm = world_model.rssm
    encoder = world_model.encoder

    def _act(params, obs, actions, rec, stoch, is_first, key):
        wmp = params["world_model"]
        n = actions.shape[0]
        rec0, stoch0 = rssm.get_initial_states(wmp, (n,))
        actions = jnp.where(is_first > 0, jnp.zeros_like(actions), actions)
        rec = jnp.where(is_first > 0, rec0, rec)
        stoch = jnp.where(is_first > 0, stoch0, stoch)
        emb = encoder.apply(wmp["encoder"], obs)
        rec = rssm.recurrent_model.apply(
            wmp["recurrent_model"], jnp.concatenate([stoch, actions], axis=-1), rec
        )
        k_repr, k_act = jax.random.split(key)
        _, stoch = rssm._representation(wmp, rec, emb, k_repr)
        acts, _ = actor_sample(
            actor,
            params["actor"],
            jnp.concatenate([stoch, rec], axis=-1),
            k_act,
            mask=extract_obs_masks(obs),
        )
        return acts, jnp.concatenate(acts, axis=-1), rec, stoch

    return _act


@register_algorithm(decoupled=True)
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.fault import DivergenceSentinel, load_resume_state
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.replay import AsyncSequenceRing, DeviceReplayState, resolve_device_resident

    if jax.process_count() > 1:  # pragma: no cover - single-host subsystem
        raise NotImplementedError(
            "dreamer_sebulba pipelines actor threads and the learner inside one controller; "
            "use the coupled `algo=dreamer_v3` for multi-host runs."
        )

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (same constraints as the coupled main)
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    # -- pipeline shape ------------------------------------------------------
    seb_cfg = cfg.algo.get("sebulba") or {}
    num_actors = max(1, int(seb_cfg.get("num_actor_threads", 2)))
    queue_depth = max(1, int(seb_cfg.get("queue_depth", 2)))
    publish_every = max(1, int(seb_cfg.get("publish_every", 1)))
    block = max(1, int(seb_cfg.get("rollout_block", 8)))
    actor_fabric, learner_fabric = fabric.partition(seb_cfg.get("actor_devices", "auto"))
    actor_devs = list(actor_fabric.devices)

    # -- envs: one vector batch per actor thread -----------------------------
    num_envs = int(cfg.env.num_envs)
    actor_envs = [
        vectorize_env(
            cfg, cfg.seed + a * num_envs, rank, log_dir if (rank == 0 and a == 0) else None, prefix="train"
        )
        for a in range(num_actors)
    ]
    action_space = actor_envs[0].single_action_space
    observation_space = actor_envs[0].single_observation_space

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape if is_continuous else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError("The CNN keys of the decoder must be contained in the encoder ones")
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError("The MLP keys of the decoder must be contained in the encoder ones")
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder
    cnn_keys = cfg.algo.cnn_keys.encoder

    # Model trees live replicated on the LEARNER mesh; actors receive
    # versioned snapshots of the player subtree on their own slice.
    world_model, actor, critic, params, player = build_agent(
        learner_fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if state is not None else None,
        state["actor"] if state is not None else None,
        state["critic"] if state is not None else None,
        state["target_critic"] if state is not None else None,
    )

    txs = {
        "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
        "actor": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
    }
    opts = {
        "world": txs["world"].init(params["world_model"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
    }
    if state is not None:
        opts = jax.tree.map(lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s, opts, state["optimizers"])
    opts = learner_fabric.put_replicated(opts)

    moments_state = init_moments()
    if state is not None:
        moments_state = jax.tree.map(jnp.asarray, state["moments"])
    moments_state = learner_fabric.put_replicated(moments_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        # actors and the learner tick at their own cadence — no rank sync
        aggregator = build_aggregator(cfg.metric.aggregator, rank_independent=True)

    # -- counters (coupled-loop conventions; see dreamer_v3.py) --------------
    # One consumed regular row = one "iteration" = num_envs policy steps; the
    # ring spans num_actors * num_envs env columns.
    ring_envs = num_actors * num_envs
    last_train = 0
    train_step = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_step = state["iter_num"] * num_envs if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(num_envs)
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

    batch_size = int(cfg.algo.per_rank_batch_size)
    seq_len = int(cfg.algo.per_rank_sequence_length)
    if batch_size % learner_fabric.world_size != 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) must be divisible by the number of learner "
            f"devices ({learner_fabric.world_size}); adjust fabric.devices/algo.sebulba.actor_devices"
        )

    # -- async sequence ring on the learner sub-mesh -------------------------
    ring_keys = dreamer_ring_keys(
        observation_space, cfg.algo.cnn_keys.encoder, cfg.algo.mlp_keys.encoder, actions_dim, with_is_first=True
    )
    buffer_size = max(cfg.buffer.size // ring_envs, seq_len) if not cfg.dry_run else max(2 * block, seq_len)
    # a block stages at most `block` regular rows + `block` ragged reset
    # rows; a ring too small to hold one worst-case block is a CONFIG error
    # surfaced here by name — truncating stage_rows instead would crash an
    # actor mid-block at the first reset-heavy rollout and loop the
    # supervisor's restart ladder into the same crash
    stage_rows = 2 * block
    if stage_rows > buffer_size:
        raise ValueError(
            f"the sequence ring holds {buffer_size} rows per env column (buffer.size={cfg.buffer.size} "
            f"over {ring_envs} env columns) but one rollout block can stage up to {stage_rows} rows "
            f"(2 x algo.sebulba.rollout_block={block}); raise buffer.size or lower rollout_block"
        )
    # The ring IS the storage tier of this topology — no host twin to spill
    # to, so an over-budget ring is a hard named error, not an OOM at the
    # first append. The estimate uses the SEQUENCE shape (per-env heads +
    # validity working set + the gathered f32 sample window, not just rows).
    use_device, _, resident_reason = resolve_device_resident(
        True,
        ring_keys,
        buffer_size,
        ring_envs,
        learner_fabric.world_size,
        float(cfg.buffer.get("hbm_budget_gb", 4.0)),
        allow_shard=False,  # sequence-ring programs are replicated
        sequence={"seq_len": seq_len, "batch_size": batch_size},
    )
    if not use_device:
        raise RuntimeError(
            f"dreamer_sebulba streams sequence heads straight into the device-resident ring, but {resident_reason}. "
            "Lower buffer.size, raise buffer.hbm_budget_gb, or run the coupled `algo=dreamer_v3`."
        )
    if cfg.metric.log_level > 0:
        print(f"Replay: async device sequence ring, {ring_envs} env columns ({resident_reason})")

    ring = AsyncSequenceRing(
        learner_fabric,
        ring_keys,
        capacity=buffer_size,
        n_envs=ring_envs,
        local_envs=num_envs,
        seq_len=seq_len,
        stage_rows=stage_rows,
        seed=cfg.seed + 31,
    )
    ring.instrument_append("dreamer_sebulba.append")
    if state is not None and cfg.buffer.checkpoint and state.get("rb") is not None:
        rb_state = state["rb"][0] if isinstance(state["rb"], list) else state["rb"]
        if isinstance(rb_state, DeviceReplayState):
            ring.load_state_dict(rb_state)
        else:
            raise RuntimeError(
                f"dreamer_sebulba can only resume its own sequence-ring checkpoints, got {type(rb_state)}"
            )

    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True))
    sentinel = DivergenceSentinel(sentinel_cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    # -- jitted programs: append (committed above) + append-free train -------
    # grad_max sizes ONE train dispatch's scan: the steady-state grant of a
    # whole consumed block (bigger backlogs drain over several dispatches)
    grad_max = max(1, int(np.ceil(cfg.algo.replay_ratio * num_envs * block)))
    train_fn, ctl_layout = make_train_step(
        world_model, actor, critic, cfg, learner_fabric.mesh, actions_dim, is_continuous, txs,
        ring={
            "capacity": buffer_size,
            "n_envs": ring_envs,
            "grad_chunk": grad_max,
            "seq_len": seq_len,
            "batch_size": batch_size,
            "ring_keys": ring_keys,
            "decoupled": True,
        },
        guard=guard,
    )
    train_fn = tracecheck.instrument(train_fn, name="dreamer_sebulba.train_step")
    metric_names = DREAMER_METRIC_NAMES + (("Fault/skipped_fraction",) if guard else ())

    # -- RNG streams ---------------------------------------------------------
    # the train-key stream lives ON DEVICE inside the ring state (checkpointed
    # with it); actor_rng_base seeds the per-actor exploration streams, and
    # rng_train reserves the family checkpoint schema's host "rng" slot (no
    # host-side training draw consumes it here — the in-ring device stream
    # owns them — but resume/rollback carry it so the layout matches the
    # coupled main's)
    rng_train = jax.random.PRNGKey(cfg.seed)
    actor_rng_base = jax.random.PRNGKey(cfg.seed + 2)
    if state is not None and state.get("rng") is not None:
        rng_train = jnp.asarray(state["rng"])
    if state is not None and state.get("actor_rng") is not None:
        actor_rng_base = jnp.asarray(state["actor_rng"])

    # -- pipeline plumbing ---------------------------------------------------
    stats = PipelineStats()
    rollout_q = RolloutQueue(queue_depth, stats=stats)
    param_server = ParamServer(player_subset(params), publish_every=publish_every, stats=stats)
    param_server.publish(player_subset(params))  # version 1 = initial/restored weights
    supervisor, _handoff_deadline = supervised_actor_pool(
        (cfg.get("fault") or {}).get("supervisor"), "dreamer-sebulba-actors", stats
    )
    arm_from_cfg(cfg)  # deterministic chaos drills (no-op unless fault.chaos armed)
    bound = staleness_bound(queue_depth, num_actors, publish_every)
    prefill_publishes = int(
        np.ceil(cfg.algo.replay_ratio * cfg.algo.learning_starts / max(1, publish_every * grad_max))
    )

    # shared prefill account: actors act randomly until the GLOBAL number of
    # produced env-step rows passes learning_starts (coupled-loop semantics)
    produced_lock = sync_lock("dreamer_sebulba.produced_lock")
    produced = {"iters": start_iter - 1}

    # -- actor-side jitted program -------------------------------------------
    # RSSM player step with in-graph episode re-init; per-step keys are
    # pre-split on the host once per block (host obs by contract)
    rec_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    stoch_flat = int(cfg.algo.world_model.stochastic_size) * int(cfg.algo.world_model.discrete_size)
    act_dim_sum = int(np.sum(actions_dim))
    act_fn = tracecheck.instrument(
        jax.jit(make_act_step(world_model, actor)), name="dreamer_sebulba.act",
        warmup=num_actors + 1, transfer_guard=False,
    )

    def actor_fn(aid: int, ctx) -> None:
        from sheeprl_tpu.replay import SeqBlobWriter

        envs = actor_envs[aid]  # slot re-homed with FRESH envs before a restart
        chaos_point = f"dreamer_sebulba.actor{aid}.step"  # hoisted off the step loop
        env_offset = aid * num_envs
        try:
            device = actor_devs[aid % len(actor_devs)]
            # fold the generation in so a restarted actor explores a fresh
            # stream instead of replaying its predecessor's draws
            rng = jax.random.fold_in(jax.random.fold_in(actor_rng_base, aid), ctx.generation)
            obs = envs.reset(seed=cfg.seed + aid * num_envs)[0]

            # write-through blob staging: each step's row is written ONCE,
            # straight into the upload bytes (no row dicts, no pack copy);
            # +4 covers the blob held while blocked in the back-pressured put
            writer = SeqBlobWriter(ring, env_offset, slots=queue_depth + 4)
            ones_mask = np.ones(num_envs, np.int32)

            # staged-row bookkeeping (the coupled loop's discipline: row t =
            # (obs_t, action_t, reward_{t-1}, terminated_{t-1}, is_first_t))
            prev_rewards = np.zeros((num_envs, 1), np.float32)
            prev_term = np.zeros((num_envs, 1), np.float32)
            is_first_vec = np.ones((num_envs, 1), np.float32)

            # actor-side policy carry: zeros + a sticky first-flag, consumed
            # by the act program's in-graph init merge (a restart or an env
            # reset re-derives the initial states from the live snapshot).
            # Staged COMMITTED on the actor device up front: the act program
            # returns committed carries, and a numpy→committed flip on call 2
            # would key a fresh C++ jit-cache entry (one silent recompile).
            actions_carry: Any = jax.device_put(np.zeros((num_envs, act_dim_sum), np.float32), device)
            rec_carry: Any = jax.device_put(np.zeros((num_envs, rec_size), np.float32), device)
            stoch_carry: Any = jax.device_put(np.zeros((num_envs, stoch_flat), np.float32), device)
            policy_first = np.ones((num_envs, 1), np.float32)

            ep_infos: list = []
            while not ctx.cancelled:
                # newest-READY-wins: never block a whole rollout block on the
                # learner's in-flight train scan materializing its outputs
                version, actor_params = param_server.pull(device, prefer_ready=True)
                _keys = jax.device_get(jax.random.split(rng, block + 1))
                rng, step_keys = _keys[0], _keys[1:]
                for t in range(block):
                    if ctx.cancelled:
                        return
                    ctx.beat()  # renew the heartbeat lease: silent == hung
                    fault_point(chaos_point)  # chaos: kill/hang-at-step
                    with produced_lock:
                        produced["iters"] += 1
                        my_iter = produced["iters"]
                    if my_iter <= learning_starts and state is None:
                        real_actions = actions = np.array(envs.action_space.sample())
                        if not is_continuous:
                            acts2d = actions.reshape(num_envs, len(actions_dim))
                            actions = np.concatenate(
                                [np.eye(d, dtype=np.float32)[acts2d[:, i]] for i, d in enumerate(actions_dim)],
                                axis=-1,
                            )
                    else:
                        jobs = prepare_obs(actor_fabric, obs, cnn_keys=cnn_keys, num_envs=num_envs)
                        acts_parts, actions_carry, rec_carry, stoch_carry = act_fn(
                            actor_params, jobs, actions_carry, rec_carry, stoch_carry,
                            policy_first, step_keys[t],
                        )
                        policy_first = np.zeros((num_envs, 1), np.float32)
                        # ONE pipelined device pull for every action head (a
                        # per-head np.asarray would pay one blocking round
                        # trip each); the concat carry stays on device
                        host_parts = jax.device_get(acts_parts)
                        actions = np.concatenate(host_parts, axis=-1)
                        if is_continuous:
                            real_actions = actions
                        else:
                            real_actions = np.stack([p.argmax(axis=-1) for p in host_parts], axis=-1)

                    # regular all-envs row, written straight into the blob
                    row = writer.row(ones_mask)
                    for k in obs_keys:
                        row[k][...] = obs[k]
                    row["actions"][...] = np.asarray(actions, np.float32).reshape(num_envs, -1)
                    row["rewards"][...] = prev_rewards
                    row["terminated"][...] = prev_term
                    row["is_first"][...] = is_first_vec

                    next_obs, rewards, terminated, truncated, infos = envs.step(
                        real_actions.reshape(envs.action_space.shape)
                    )
                    dones = np.logical_or(terminated, truncated).astype(np.uint8)
                    is_first_vec = np.zeros((num_envs, 1), np.float32)

                    if cfg.metric.log_level > 0 and "final_info" in infos:
                        ep_info = infos["final_info"]
                        if isinstance(ep_info, dict) and "episode" in ep_info:
                            mask = np.asarray(
                                ep_info.get("_episode", np.ones_like(np.asarray(ep_info["episode"]["r"]), dtype=bool))
                            ).reshape(-1)
                            rews = np.asarray(ep_info["episode"]["r"]).reshape(-1)
                            lens = np.asarray(ep_info["episode"]["l"]).reshape(-1)
                            for e in np.nonzero(mask)[0]:
                                ep_infos.append((float(rews[e]), float(lens[e])))

                    obs = next_obs
                    prev_rewards = clip_rewards_fn(np.asarray(rewards, np.float32).reshape(num_envs, 1))
                    prev_term = np.asarray(terminated, np.float32).reshape(num_envs, 1)

                    dones_idxes = dones.nonzero()[0].tolist()
                    if dones_idxes:
                        # ragged reset row: only the done envs advance their
                        # heads, carrying the TERMINAL obs (the final_obs
                        # patch) — non-done cells stay stale-but-masked
                        mask = np.zeros(num_envs, np.int32)
                        mask[dones_idxes] = 1
                        rrow = writer.row(mask)
                        final_obs = infos.get("final_obs") if "final_obs" in infos else None
                        for e in dones_idxes:
                            fo = final_obs[e] if final_obs is not None else None
                            for k in obs_keys:
                                rrow[k][e] = np.asarray(fo[k] if fo is not None else next_obs[k][e])
                        rrow["actions"][dones_idxes] = 0.0
                        rrow["rewards"][dones_idxes] = prev_rewards[dones_idxes]
                        rrow["terminated"][dones_idxes] = prev_term[dones_idxes]
                        rrow["is_first"][dones_idxes] = 0.0
                        # reset the already-inserted step bookkeeping
                        prev_rewards[dones_idxes] = 0.0
                        prev_term[dones_idxes] = 0.0
                        is_first_vec[dones_idxes] = 1.0
                        policy_first[dones_idxes] = 1.0

                if ctx.cancelled:
                    # cancelled at the block boundary: the queue's fast path
                    # would accept a stale blob — never ship one
                    return
                # ship + stage on the actor thread: the learner only ever sees
                # a committed device blob (its critical path has no host copy)
                blob_bytes, local_counts = writer.ship()
                env_counts = np.zeros(ring_envs, np.int64)
                env_counts[env_offset : env_offset + num_envs] = local_counts
                blob = learner_fabric.put_replicated(blob_bytes)
                item = {
                    "blob": blob,
                    "env_counts": env_counts,
                    "steps": block,
                    "version": version,
                    "ep_infos": ep_infos,
                }
                ep_infos = []
                # ctx doubles as the stop flag; beat while back-pressured so
                # a stalled-but-healthy actor is never mistaken for hung
                if not rollout_q.put(item, stop_event=ctx, beat=ctx.beat):
                    return
        finally:  # crashes propagate to the supervisor (restart/degrade/abort)
            try:
                envs.close()
            except Exception:
                pass

    def _rehome_actor(aid: int, ctx) -> None:
        # State re-homing before a restart: the replacement acts on FRESH
        # envs with a zeroed policy carry (sticky first-flags re-init it
        # in-graph from a fresh ParamServer snapshot at its loop top).
        actor_envs[aid] = vectorize_env(cfg, cfg.seed + aid * num_envs, rank, None, prefix="train")

    for a in range(num_actors):
        supervisor.spawn(
            name=f"dreamer-sebulba-actor-{a}",
            target=partial(actor_fn, a),
            on_restart=partial(_rehome_actor, a),
        )

    # -- learner loop --------------------------------------------------------
    # the cum counter must be staged COMMITTED like its peers: an uncommitted
    # scalar flips committed-ness after the first dispatch returns it pinned,
    # which keys a fresh C++ jit-cache entry = one silent full recompile
    carry = (params, opts, moments_state, learner_fabric.put_replicated(jnp.int32(0)))
    iter_num = start_iter - 1
    grant_backlog = 0
    cumulative_grad_steps = 0

    def _checkpoint_state(it: int) -> Dict[str, Any]:
        p = carry[0]
        return {
            "world_model": p["world_model"],
            "actor": p["actor"],
            "critic": p["critic"],
            "target_critic": p["target_critic"],
            "optimizers": carry[1],
            "moments": carry[2],
            "ratio": ratio.state_dict(),
            "iter_num": it,
            "batch_size": batch_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": rng_train,
            "actor_rng": actor_rng_base,
        }

    try:
        while iter_num < total_iters:
            # one supervision pass per learner tick: restart crashed/hung
            # actors (re-homed on fresh envs), degrade past the budget, abort
            # with a typed error at zero survivors — never a silent spin
            supervisor.check()
            try:
                item = rollout_q.get(timeout=0.5, deadline_s=_handoff_deadline(), diagnose=supervisor.describe)
            except _queue.Empty:
                continue
            steps = int(item["steps"])
            stats.observe_staleness(param_server.version - item["version"])
            # -- append: ONE donated ragged multi-head scatter dispatch. This
            # is the WHOLE replay path on the learner's critical path
            # (packing + the host→device transfer rode the actor thread;
            # window sampling is inside the train dispatch).
            with timer("Time/replay_path_time", SumMetric):
                ring.append(item["blob"])
                ring.note_append(item["env_counts"], item["blob"].nbytes)
            stats.add("env_steps", steps * num_envs)

            # -- grant accounting: identical to the coupled loop, one Ratio
            # call per consumed regular env-step row
            for _ in range(steps):
                iter_num += 1
                policy_step += policy_steps_per_iter
                if iter_num >= learning_starts:
                    grant_backlog += ratio(policy_step - prefill_steps * policy_steps_per_iter)

            # -- train at the learner's own cadence: drain the granted
            # backlog in grad_max-sized scans, windows sampled in-graph with
            # per-env head validity; the grant gate holds while any env is
            # still shorter than a sample window
            while grant_backlog > 0 and ring.ready():
                chunk = min(grad_max, grant_backlog)
                validmask = np.zeros((grad_max,), np.float32)
                validmask[:chunk] = 1.0
                ctl = learner_fabric.put_replicated(
                    pack_burst_blob(ctl_layout, {"__validmask__": validmask})
                )
                with timer("Time/train_time", SumMetric):
                    carry, new_key, metrics = train_fn(carry, ring.state, ctl)
                    ring.set_key(new_key)
                grant_backlog -= chunk
                cumulative_grad_steps += chunk
                stats.add("grad_steps", chunk)
                train_step += 1
                param_server.maybe_publish(train_step, player_subset(carry[0]))
                if aggregator and not aggregator.disabled:
                    for name, value in zip(metric_names, metrics):
                        if name in aggregator:
                            aggregator.update(name, value)
                if guard and sentinel.observe(float(metrics[-1]) * chunk):
                    def _rollback(good):
                        nonlocal carry, rng_train
                        p = learner_fabric.put_replicated(
                            jax.tree.map(
                                lambda t, s: jnp.asarray(s),
                                carry[0],
                                {
                                    "world_model": good["world_model"],
                                    "actor": good["actor"],
                                    "critic": good["critic"],
                                    "target_critic": good["target_critic"],
                                },
                            )
                        )
                        cast = lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s
                        o = learner_fabric.put_replicated(jax.tree.map(cast, carry[1], good["optimizers"]))
                        m = learner_fabric.put_replicated(jax.tree.map(cast, carry[2], good["moments"]))
                        carry = (p, o, m, carry[3])
                        if good.get("rng") is not None:
                            rng_train = jnp.asarray(good["rng"])

                    sentinel.recover(ckpt_dir, _rollback)
                    # actors must never keep acting on diverged weights
                    param_server.publish(player_subset(carry[0]))

            for i, (ep_rew, ep_len) in enumerate(item["ep_infos"]):
                if aggregator and not aggregator.disabled:
                    if "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                if cfg.metric.log_level > 0:
                    print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

            # -- logging -----------------------------------------------------
            if cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or iter_num >= total_iters
            ):
                if aggregator and not aggregator.disabled:
                    logger.log_dict(aggregator.compute(), policy_step)
                    aggregator.reset()
                pipe_metrics = stats.snapshot()
                pipe_metrics["Pipeline/queue_depth"] = rollout_q.qsize()
                pipe_metrics.update(supervisor.metrics("Pipeline/", "actor"))
                logger.log_dict(pipe_metrics, policy_step)
                logger.log_dict(ring.metrics(), policy_step)
                if guard and sentinel.total_skipped:
                    logger.log_dict({"Fault/skipped_updates": sentinel.total_skipped}, policy_step)
                if policy_step > 0:
                    logger.log_dict(
                        {"Params/replay_ratio": cumulative_grad_steps / policy_step}, policy_step
                    )
                if not timer.disabled:
                    timer_metrics = timer.compute()
                    if timer_metrics.get("Time/train_time", 0) > 0:
                        logger.log_dict(
                            {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                            policy_step,
                        )
                    timer.reset()
                last_log = policy_step
                last_train = train_step

            # -- checkpoint (learner-side; ring state rides the rb sidecar) --
            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                iter_num >= total_iters and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
                fabric.call(
                    "on_checkpoint_coupled",
                    ckpt_path=ckpt_path,
                    state=_checkpoint_state(iter_num),
                    replay_buffer=ring.state_dict() if cfg.buffer.checkpoint else None,
                )
    finally:
        # supervised shutdown: stop, drain, join under the configured budget;
        # a hung actor is logged and abandoned BY NAME, never silently leaked
        pool_metrics = supervisor.metrics("Pipeline/", "actor")  # pre-shutdown pool state
        supervisor.request_stop()
        rollout_q.drain()
        supervisor.join()

    if os.environ.get("SHEEPRL_SEBULBA_DEBUG"):  # pipeline-balance dump for bench/test tuning
        print(
            "DREAMER_SEBULBA_STATS",
            {
                **stats.snapshot(),
                **pool_metrics,
                "staleness_max": stats.max_staleness_seen,
                "policy_steps": policy_step,
                "grad_steps": cumulative_grad_steps,
                "prefill_policy_steps": prefill_steps * policy_steps_per_iter,
            },
        )
    if stats.max_staleness_seen > 2 * bound + prefill_publishes:  # pragma: no cover - invariant guard
        warnings.warn(
            f"Pipeline params staleness reached {stats.max_staleness_seen} publishes "
            f"(steady-state bound {bound} + prefill transient {prefill_publishes}): actors "
            "cannot keep up with the learner — raise algo.sebulba.num_actor_threads or "
            "publish_every."
        )

    params_live = carry[0]
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params_live, fabric, cfg, log_dir, greedy=False, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(
            fabric,
            log_models,
            cfg,
            {
                "world_model": params_live["world_model"],
                "actor": params_live["actor"],
                "critic": params_live["critic"],
                "target_critic": params_live["target_critic"],
                "moments": carry[2],
            },
        )
    logger.close()


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from jax.sharding import PartitionSpec as P  # noqa: E402

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


@register_audit_programs(
    "dreamer_sebulba.train_step", "dreamer_sebulba.act", "dreamer_sebulba.append"
)
def _audit_programs(spec: AuditMesh):
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import audit_dreamer_setup
    from sheeprl_tpu.algos.ppo.ppo import _abstract_like
    from sheeprl_tpu.data.ring import build_seq_append_step, ring_cell

    s = audit_dreamer_setup(spec)
    local_envs, num_actors = s["n_envs"], 2
    ring_envs = local_envs * num_actors
    stage_rows = 4
    rep = s["rep"]
    state_abs = {
        "storage": {
            k: jax.ShapeDtypeStruct((s["capacity"], ring_envs) + ring_cell(shape), dtype, sharding=rep)
            for k, (shape, dtype) in s["ring_keys"].items()
        },
        "pos": jax.ShapeDtypeStruct((ring_envs,), jnp.int32, sharding=rep),
        "valid": jax.ShapeDtypeStruct((ring_envs,), jnp.int32, sharding=rep),
        "key": jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
    }

    # learner: append-free governed train step over the async sequence ring
    # (NOTHING donated — storage/heads pass through, the carry is published)
    train_fn, ctl_layout = make_train_step(
        s["world_model"], s["actor"], s["critic"], s["cfg"], s["mesh"], s["actions_dim"], False,
        s["txs"],
        ring={
            "capacity": s["capacity"], "n_envs": ring_envs, "grad_chunk": s["grad_chunk"],
            "seq_len": s["seq_len"], "batch_size": s["batch"], "ring_keys": s["ring_keys"],
            "decoupled": True,
        },
    )
    ctl_blob = jax.ShapeDtypeStruct((ctl_layout.nbytes,), jnp.uint8, sharding=rep)
    yield AuditProgram(
        name="dreamer_sebulba.train_step",
        fn=train_fn,
        args=(s["carry"], state_abs, ctl_blob),
        source=__name__,
        feedback_outputs=(0, 1),
        out_decl={0: P(), 1: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )

    # ring writer: the donated ragged multi-head scatter
    append_fn, append_layout = build_seq_append_step(
        s["mesh"], s["ring_keys"], s["capacity"], ring_envs, local_envs, stage_rows
    )
    append_blob = jax.ShapeDtypeStruct((append_layout.nbytes,), jnp.uint8, sharding=rep)
    yield AuditProgram(
        name="dreamer_sebulba.append",
        fn=append_fn,
        args=(state_abs, append_blob),
        source=__name__,
        donate_argnums=(0,),
        feedback_outputs=(0,),
        out_decl={0: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )

    # actor: the RSSM player step with in-graph episode re-init (host
    # obs/keys by contract)
    act_fn = jax.jit(make_act_step(s["world_model"], s["actor"]))
    subset = _abstract_like(player_subset(s["params"]), rep)
    rec_size = int(s["cfg"].algo.world_model.recurrent_model.recurrent_state_size)
    stoch_flat = int(s["cfg"].algo.world_model.stochastic_size) * int(s["cfg"].algo.world_model.discrete_size)
    act_sum = int(np.sum(s["actions_dim"]))
    obs_abs = {
        "rgb": jax.ShapeDtypeStruct((local_envs, 64, 64, 3), jnp.float32),
        "state": jax.ShapeDtypeStruct((local_envs, 4), jnp.float32),
    }
    yield AuditProgram(
        name="dreamer_sebulba.act",
        fn=act_fn,
        args=(
            subset,
            obs_abs,
            jax.ShapeDtypeStruct((local_envs, act_sum), jnp.float32),
            jax.ShapeDtypeStruct((local_envs, rec_size), jnp.float32),
            jax.ShapeDtypeStruct((local_envs, stoch_flat), jnp.float32),
            jax.ShapeDtypeStruct((local_envs, 1), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        ),
        source=__name__,
        mesh=s["mesh"],
        check_input_shardings=False,
    )
