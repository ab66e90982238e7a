"""Dreamer-V3 — coupled training (reference: ``sheeprl/algos/dreamer_v3/dreamer_v3.py``).

TPU-native structure (SURVEY §3.3):

- the T-step dynamic-learning loop and the H-step imagination loop — Python
  loops in the reference (``dreamer_v3.py:131-145, 234-240``) — are two
  ``lax.scan``s inside ONE jitted gradient step;
- each granted gradient step runs: target-critic EMA gate → world-model
  update (reconstruction loss) → actor update (imagination re-run inside the
  actor grad so reparameterized/straight-through gradients flow) → critic
  update (two-hot log-prob vs λ-returns + target-critic regularizer);
- ``Moments`` percentile normalization gathers λ-returns across the ``dp``
  mesh axis (``lax.all_gather`` — the reference's ``fabric.all_gather``,
  ``utils.py:56-62``) and its EMA state rides the scan carry;
- the G granted steps scan inside a single ``shard_map`` over the mesh with
  the batch axis sharded on ``dp`` and gradient ``pmean``s reproducing the
  reference's per-module DDP.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Optional, Sequence

import gymnasium as gym  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.algos.dreamer_v3.agent import (
    Actor,
    PlayerDV3,
    WorldModel,
    actor_dists,
    actor_sample,
    build_agent,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    compute_lambda_values,
    init_moments,
    moments_update,
    prepare_obs,
    test,
)
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer, put_packed
from sheeprl_tpu.data.ring import build_burst_train_step, ring_append_rows, ring_sample_windows
from sheeprl_tpu.distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.parallel.comm import pmean_grads
from sheeprl_tpu.envs.factory import vectorize_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric, build_aggregator
from sheeprl_tpu.utils import profiler as recorder
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, resolve_hybrid_player, save_configs

__all__ = ["main", "make_train_step", "ring_append_rows", "ring_sample_windows"]


def make_train_step(
    world_model: WorldModel,
    actor: Actor,
    critic,
    cfg,
    mesh,
    actions_dim: Sequence[int],
    is_continuous: bool,
    txs: Dict[str, Any],
    ring: Optional[Dict[str, Any]] = None,
    guard: bool = False,
):
    """Build the fully-jitted G-step Dreamer update (see module docstring).

    With ``ring`` (TPU-native burst mode, no reference counterpart) the
    returned function owns a DEVICE-RESIDENT sequence ring instead of taking
    host-sampled ``(G, T, B, ...)`` data: one dispatch appends the staged
    transitions (per-env write heads — reset rows advance only the done
    envs, mirroring ``EnvIndependentReplayBuffer``'s ragged adds) and runs
    ``ring["grad_chunk"]`` gradient steps, drawing each step's
    ``(T, B)`` windows on device with the `SequentialReplayBuffer` validity
    rule (windows never cross an env's write head). Pixels stay uint8 in
    HBM and only raw transitions ride host→device: one upload and one
    dispatch per burst instead of one per gradient step plus the full
    replay batch traffic.

    ``ring`` keys: capacity, n_envs, grad_chunk, seq_len, batch_size (the
    ring/staged array shapes and dtypes are implied by the arguments).
    """
    rssm = world_model.rssm
    wm_cfg = cfg.algo.world_model
    cnn_enc = list(cfg.algo.cnn_keys.encoder)
    mlp_enc = list(cfg.algo.mlp_keys.encoder)
    cnn_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_dec = list(cfg.algo.mlp_keys.decoder)
    stoch_state_size = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    target_update_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    tau = float(cfg.algo.critic.tau)
    moments_cfg = cfg.algo.actor.moments
    split_sizes = np.cumsum(np.asarray(actions_dim[:-1], dtype=np.int64)).tolist()

    def gradient_step(carry, xs):
        params, opts, moments_state, cum = carry
        # snapshot BEFORE the target-critic EMA below so a guarded skip
        # undoes the whole step (shallow dict copy: values are replaced,
        # never mutated, by the updates that follow)
        old = (params, dict(opts), moments_state) if guard else None
        batch, key = xs  # batch: (T, B_local, ...)
        k_dyn, k_img = jax.random.split(key)

        # -- target-critic EMA gate (reference: dreamer_v3.py:676-682)
        with jax.named_scope("target.ema"):
            tau_eff = jnp.where(cum == 0, 1.0, tau)
            mix = jnp.where(cum % target_update_freq == 0, tau_eff, 0.0)
            params = {
                **params,
                "target_critic": jax.tree.map(
                    lambda c, t: mix * c + (1.0 - mix) * t, params["critic"], params["target_critic"]
                ),
            }

        # Region names (utils.profiler.REGIONS): a scope only writes op_name
        # metadata, which the optimized executable keeps per instruction.
        with jax.named_scope("wm.encoder"):
            batch_obs = {k: batch[k] / 255.0 - 0.5 for k in cnn_enc}
            batch_obs.update({k: batch[k] for k in mlp_enc})
        with jax.named_scope("wm.dynamics"):
            is_first = batch["is_first"].at[0].set(1.0)
            batch_actions = jnp.concatenate(
                [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
            )

        # -- world-model update (reference train(): dreamer_v3.py:92-196)
        def wm_loss_fn(wmp):
            with jax.named_scope("wm.encoder"):
                embedded = world_model.encoder.apply(wmp["encoder"], batch_obs)
            with jax.named_scope("wm.dynamics"):
                recs, posts, post_logits, prior_logits = rssm.dynamic_rollout(
                    wmp, embedded, batch_actions, is_first, k_dyn
                )
                latents = jnp.concatenate([posts, recs], axis=-1)
            with jax.named_scope("wm.decoder"):
                recon = world_model.decode(wmp, latents)
            with jax.named_scope("wm.heads"):
                po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec}
                po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec})
                pr = TwoHotEncodingDistribution(
                    world_model.reward_model.apply(wmp["reward_model"], latents), dims=1
                )
                pc = Independent(
                    BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], latents)), 1
                )
                continue_targets = 1 - batch["terminated"]
                rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                    po,
                    batch_obs,
                    pr,
                    batch["rewards"],
                    prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size),
                    post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size),
                    float(wm_cfg.kl_dynamic),
                    float(wm_cfg.kl_representation),
                    float(wm_cfg.kl_free_nats),
                    float(wm_cfg.kl_regularizer),
                    pc,
                    continue_targets,
                    float(wm_cfg.continue_scale_factor),
                )
            aux = (recs, posts, post_logits, prior_logits, kl, state_loss, reward_loss, observation_loss, continue_loss)
            return rec_loss, aux

        (rec_loss, wm_aux), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(params["world_model"])
        recs, posts, post_logits, prior_logits, kl, state_loss, reward_loss, observation_loss, continue_loss = wm_aux
        with jax.named_scope("wm.optim"):
            wm_grads = pmean_grads(wm_grads, "dp")
            wupd, opts["world"] = txs["world"].update(wm_grads, opts["world"], params["world_model"])
            params = {**params, "world_model": optax.apply_updates(params["world_model"], wupd)}

        # -- behaviour learning (reference: dreamer_v3.py:198-301)
        wmp = params["world_model"]
        T, B = batch["actions"].shape[:2]
        with jax.named_scope("behaviour.imagination"):
            prior0 = jax.lax.stop_gradient(posts).reshape(T * B, stoch_state_size)
            rec0 = jax.lax.stop_gradient(recs).reshape(T * B, recurrent_state_size)
            true_continue = (1 - batch["terminated"]).reshape(1, T * B, 1)

        def actor_loss_fn(ap, mstate):
            with jax.named_scope("behaviour.imagination"):
                latent0 = jnp.concatenate([prior0, rec0], axis=-1)
                k0, k_scan = jax.random.split(k_img)
                a0 = jnp.concatenate(actor_sample(actor, ap, jax.lax.stop_gradient(latent0), k0)[0], axis=-1)

                def img_step(carry, k):
                    prior, rec, act = carry
                    k_prior, k_act = jax.random.split(k)
                    prior, rec = rssm.imagination(wmp, prior, rec, act, k_prior)
                    latent = jnp.concatenate([prior, rec], axis=-1)
                    new_act = jnp.concatenate(
                        actor_sample(actor, ap, jax.lax.stop_gradient(latent), k_act)[0], axis=-1
                    )
                    return (prior, rec, new_act), (latent, new_act)

                _, (latents, acts) = jax.lax.scan(
                    img_step, (prior0, rec0, a0), jax.random.split(k_scan, horizon)
                )
                traj = jnp.concatenate([latent0[None], latents], axis=0)  # (H+1, TB, L)
                imagined_actions = jnp.concatenate([a0[None], acts], axis=0)

            with jax.named_scope("behaviour.returns"):
                values = TwoHotEncodingDistribution(critic.apply(params["critic"], traj), dims=1).mean
                rewards = TwoHotEncodingDistribution(
                    world_model.reward_model.apply(wmp["reward_model"], traj), dims=1
                ).mean
                continues = Independent(
                    BernoulliSafeMode(logits=world_model.continue_model.apply(wmp["continue_model"], traj)), 1
                ).mode
                continues = jnp.concatenate([true_continue, continues[1:]], axis=0)

                lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * gamma, lmbda)
                discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)

                new_mstate, offset, invscale = moments_update(
                    mstate,
                    lambda_values,
                    decay=float(moments_cfg.decay),
                    max_=float(moments_cfg.max),
                    percentile_low=float(moments_cfg.percentile.low),
                    percentile_high=float(moments_cfg.percentile.high),
                    axis_name="dp",
                )
                normed_lambda = (lambda_values - offset) / invscale
                normed_baseline = (values[:-1] - offset) / invscale
                advantage = normed_lambda - normed_baseline

            with jax.named_scope("behaviour.heads"):
                policies = actor_dists(actor, actor.apply(ap, jax.lax.stop_gradient(traj)))
                if is_continuous:
                    objective = advantage
                else:
                    act_parts = (
                        jnp.split(imagined_actions, split_sizes, axis=-1)
                        if len(actions_dim) > 1
                        else [imagined_actions]
                    )
                    logprob = jnp.stack(
                        [
                            p.log_prob(jax.lax.stop_gradient(a))[..., None][:-1]
                            for p, a in zip(policies, act_parts)
                        ],
                        axis=-1,
                    ).sum(-1)
                    objective = logprob * jax.lax.stop_gradient(advantage)
                try:
                    entropy = ent_coef * jnp.stack([p.entropy() for p in policies], axis=-1).sum(-1)
                except NotImplementedError:  # e.g. TanhNormal (reference: dreamer_v3.py:293-296)
                    entropy = jnp.zeros(traj.shape[:-1], dtype=traj.dtype)
                policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
            aux = (
                jax.lax.stop_gradient(traj),
                jax.lax.stop_gradient(lambda_values),
                discount,
                new_mstate,
            )
            return policy_loss, aux

        (policy_loss, (traj_sg, lambda_sg, discount, moments_state)), actor_grads = jax.value_and_grad(
            actor_loss_fn, has_aux=True
        )(params["actor"], moments_state)
        with jax.named_scope("behaviour.optim"):
            actor_grads = pmean_grads(actor_grads, "dp")
            aupd, opts["actor"] = txs["actor"].update(actor_grads, opts["actor"], params["actor"])
            params = {**params, "actor": optax.apply_updates(params["actor"], aupd)}

        # -- critic update (reference: dreamer_v3.py:303-323)
        def critic_loss_fn(cp):
            with jax.named_scope("behaviour.heads"):
                qv = TwoHotEncodingDistribution(critic.apply(cp, traj_sg[:-1]), dims=1)
                target_values = TwoHotEncodingDistribution(
                    critic.apply(params["target_critic"], traj_sg[:-1]), dims=1
                ).mean
                vloss = -qv.log_prob(lambda_sg) - qv.log_prob(jax.lax.stop_gradient(target_values))
                return jnp.mean(vloss * discount[:-1, ..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        with jax.named_scope("behaviour.optim"):
            critic_grads = pmean_grads(critic_grads, "dp")
            cupd, opts["critic"] = txs["critic"].update(critic_grads, opts["critic"], params["critic"])
            params = {**params, "critic": optax.apply_updates(params["critic"], cupd)}

        with jax.named_scope("wm.heads"):
            post_ent = Independent(
                OneHotCategorical(
                    logits=post_logits.reshape(*post_logits.shape[:-1], stochastic_size, discrete_size)
                ), 1
            ).entropy().mean()
            prior_ent = Independent(
                OneHotCategorical(
                    logits=prior_logits.reshape(*prior_logits.shape[:-1], stochastic_size, discrete_size)
                ), 1
            ).entropy().mean()
        metrics = (
            rec_loss, observation_loss, reward_loss, state_loss, continue_loss,
            kl, post_ent, prior_ent, policy_loss, value_loss,
        )
        if guard:
            from sheeprl_tpu.ops import finite_guard, guarded_select

            ok = finite_guard((wm_grads, actor_grads, critic_grads, rec_loss, policy_loss, value_loss))
            # losses are per-device: all-reduce the verdict so every device
            # takes the same branch and replicated params never desync
            ok = jax.lax.pmin(ok.astype(jnp.int32), "dp").astype(bool)
            params, opts, moments_state = guarded_select(ok, (params, opts, moments_state), old)
            # a skipped step did not happen: EMA/moments cadence keeps phase
            return (params, opts, moments_state, cum + ok.astype(jnp.int32)), (
                *metrics,
                1.0 - ok.astype(jnp.float32),
            )
        return (params, opts, moments_state, cum + 1), metrics

    if ring is None:
        def local_train(params, opts, moments_state, data, key, cum0):
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            n_steps = jax.tree.leaves(data)[0].shape[0]
            keys = jax.random.split(key, n_steps)
            (params, opts, moments_state, _), metrics = jax.lax.scan(
                gradient_step, (params, opts, moments_state, cum0), (data, keys)
            )
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x.mean(), "dp"), metrics)
            return params, opts, moments_state, metrics

        shard_train = shard_map(
            local_train,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(None, None, "dp"), P(), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(shard_train, donate_argnums=(0, 1, 2))

    # Decoupled (Sebulba) variant: append-free governed train step over the
    # async sequence ring (per-env heads live ON DEVICE, advanced by the
    # ragged append program) — returns ``(jitted_fn, ctl_layout)``.
    if ring.get("decoupled"):
        from sheeprl_tpu.data.ring import build_seq_train_step

        return build_seq_train_step(gradient_step, mesh, ring)

    # Burst variant: carry = (params, opts, moments_state, cum); the ring
    # machinery (append, on-device window sampling, granted-chunk scan) is
    # shared with Dreamer-V1/V2 in ``data/ring.py``.
    return build_burst_train_step(gradient_step, mesh, ring)


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    from sheeprl_tpu.fault import load_resume_state
    from sheeprl_tpu.optim.builders import build_optimizer

    rank = fabric.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_resume_state(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference: dreamer_v3.py:369-372)
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name)
    logger = get_logger(cfg, log_dir, rank)
    if fabric.is_global_zero:
        logger.log_hyperparams(cfg)
    print(f"Log dir: {log_dir}")

    # Environment setup via the factory: FastSyncVectorEnv hot path +
    # RestartOnException resilience (reference: dreamer_v3.py:374-399)
    envs = vectorize_env(
        cfg, cfg.seed, rank, log_dir if rank == 0 else None, prefix="train", restart_on_exception=True
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

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
    if cfg.metric.log_level > 0:
        print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
        print("Decoder CNN keys:", cfg.algo.cnn_keys.decoder)
        print("Decoder MLP keys:", cfg.algo.mlp_keys.decoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder

    world_model, actor, critic, params, player = build_agent(
        fabric,
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
    opts = fabric.put_replicated(opts)

    moments_state = init_moments()
    if state is not None:
        moments_state = jax.tree.map(jnp.asarray, state["moments"])
    moments_state = fabric.put_replicated(moments_state)

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = build_aggregator(cfg.metric.aggregator)

    # Local data (reference: dreamer_v3.py:479-496)
    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
        obs_keys=tuple(obs_keys),
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    resident_restore = None  # a DeviceReplayState checkpointed by the resident path
    if state is not None and cfg.buffer.checkpoint:
        from sheeprl_tpu.replay import DeviceReplayState

        if isinstance(state["rb"], list):
            rb = state["rb"][0]
        elif isinstance(state["rb"], EnvIndependentReplayBuffer):
            rb = state["rb"]
        elif isinstance(state["rb"], DeviceReplayState):
            resident_restore = state["rb"]
        else:
            raise RuntimeError(f"Cannot restore the replay buffer from {type(state['rb'])}")

    # Counters (single-process world — same convention as PPO/SAC)
    train_step = 0
    last_train = 0
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
    seq_len = int(cfg.algo.per_rank_sequence_length)
    if batch_size % fabric.world_size != 0:
        raise ValueError(
            f"per_rank_batch_size ({batch_size}) must be divisible by the number of devices ({fabric.world_size})"
        )
    rng = jax.random.PRNGKey(cfg.seed)
    if state is not None and state.get("rng") is not None:
        rng = jnp.asarray(state["rng"])  # continue the killed run's stream
    cnn_keys = cfg.algo.cnn_keys.encoder
    mlp_keys = cfg.algo.mlp_keys.encoder

    # TPU-native overlap (same design as SAC's `hybrid_player`): the policy
    # runs on the host CPU from a packed bf16 params snapshot, replay lives
    # in a device-resident uint8 sequence ring, and Ratio grants are
    # dispatched in bursts on a trainer thread. This removes the per-step
    # action pull (one device→host sync per env step) and the per-grant
    # replay-batch upload (batch 16 x seq 64 of 64x64
    # pixels is ~12.6 MB per gradient step).
    hp_cfg = cfg.algo.get("hybrid_player") or {}
    burst_mode = resolve_hybrid_player(hp_cfg, fabric.mesh)

    # Device-resident replay on the coupled topology (howto/device_replay.md):
    # the sequence ring lives in HBM (pixels stay uint8), windows are sampled
    # in-graph, and every env step dispatches ONE fused append+train program.
    # The hybrid burst path is already device-resident (and asynchronous), so
    # it takes precedence; capacities beyond the HBM budget spill back to the
    # host (memmap-capable) buffer below.
    resident_mode = False
    resident_driver = None
    if not burst_mode:
        from sheeprl_tpu.replay import resolve_device_resident
        from sheeprl_tpu.utils.burst import dreamer_ring_keys

        resident_ring_keys = dreamer_ring_keys(
            observation_space, cfg.algo.cnn_keys.encoder, cfg.algo.mlp_keys.encoder,
            actions_dim, with_is_first=True,
        )
        resident_mode, _, resident_reason = resolve_device_resident(
            cfg.buffer.get("device_resident", False),
            resident_ring_keys,
            buffer_size,
            int(cfg.env.num_envs),
            fabric.world_size,
            float(cfg.buffer.get("hbm_budget_gb", 4.0)),
            allow_shard=False,  # the sequence-ring burst program is replicated
            # per-env-head sequence shape: heads + validity working set + the
            # gathered f32 sample window, not just flat rows
            sequence={"seq_len": seq_len, "batch_size": batch_size},
        )
        if cfg.metric.log_level > 0 and cfg.buffer.get("device_resident", False):
            print(f"Replay: device_resident={resident_mode} ({resident_reason})")
    if resident_restore is not None and not resident_mode:
        # resident checkpoint resumed onto a non-resident path (knob flipped
        # off, spillover, or hybrid-burst precedence): fill the host per-env
        # buffers so the collected experience survives the crossover
        from sheeprl_tpu.replay import restore_host_env_buffer

        restore_host_env_buffer(
            resident_restore, rb, fill_missing={"truncated": ((1,), np.float32)}
        )

    # The host replay mirror only matters for checkpoints once the device
    # ring owns sampling; without it every pixel transition would be stored
    # twice (HBM ring + host RAM/memmap). The resident ring checkpoints
    # itself (DeviceReplayState), so it never needs the mirror.
    host_mirror = (not burst_mode and not resident_mode) or (burst_mode and bool(cfg.buffer.checkpoint))

    # Divergence sentinel on the host-sampled train path (the burst trainer
    # thread keeps its own metric plumbing; its guard is future work, and the
    # resident burst program shares that in-graph machinery).
    from sheeprl_tpu.fault import DivergenceSentinel

    sentinel_cfg = (cfg.get("fault") or {}).get("sentinel") or {}
    guard = bool(sentinel_cfg.get("enabled", True)) and not burst_mode and not resident_mode
    sentinel = DivergenceSentinel(sentinel_cfg)
    ckpt_dir = os.path.join(log_dir, "checkpoint")

    if burst_mode:
        from sheeprl_tpu.utils.burst import DREAMER_METRIC_NAMES, HybridPlayerHarness

        wm_cfg_ = cfg.algo.world_model

        def _player_subset(p):
            wm = p["world_model"]
            return {
                "world_model": {
                    "encoder": wm["encoder"],
                    "recurrent_model": wm["recurrent_model"],
                    "representation_model": wm["representation_model"],
                    "transition_model": wm["transition_model"],
                    "initial_recurrent_state": wm["initial_recurrent_state"],
                },
                "actor": p["actor"],
            }

        hp = HybridPlayerHarness(
            fabric, cfg,
            observation_space=observation_space, cnn_keys=cnn_keys, mlp_keys=mlp_keys,
            actions_dim=actions_dim, capacity=buffer_size, seq_len=seq_len, batch_size=batch_size,
            policy_steps_per_iter=policy_steps_per_iter,
            make_burst_fn=lambda ring: make_train_step(
                world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs, ring=ring
            ),
            player_subset=_player_subset,
            carry=(params, opts, moments_state, jnp.int32(0)),
            rb=rb if (state is not None and cfg.buffer.checkpoint) else None,
            with_is_first=True, metric_names=DREAMER_METRIC_NAMES, aggregator=aggregator,
        )
        host_player = PlayerDV3(
            world_model,
            actor,
            actions_dim,
            cfg.env.num_envs,
            int(wm_cfg_.stochastic_size),
            int(wm_cfg_.recurrent_model.recurrent_state_size),
            discrete_size=int(wm_cfg_.discrete_size),
            host_device=hp.host_device,
        )
    elif resident_mode:
        from sheeprl_tpu.replay import SequenceRingDriver

        resident_chunk = max(1, int(np.ceil(cfg.algo.replay_ratio * policy_steps_per_iter)))
        resident_driver = SequenceRingDriver(
            fabric,
            resident_ring_keys,
            capacity=buffer_size,
            n_envs=int(cfg.env.num_envs),
            seq_len=seq_len,
            batch_size=batch_size,
            grad_chunk=resident_chunk,
            make_burst_fn=lambda ring: make_train_step(
                world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs, ring=ring
            ),
            seed=cfg.seed + 31,
            # resume: prefer the exact ring snapshot; fall back to mirroring
            # a host-buffer checkpoint into HBM
            restore=resident_restore
            if resident_restore is not None
            else (rb if (state is not None and cfg.buffer.checkpoint) else None),
            trace_name="dreamer_v3.burst_step",
        )
        resident_carry = (params, opts, moments_state, jnp.int32(0))
    else:
        train_fn = make_train_step(
            world_model, actor, critic, cfg, fabric.mesh, actions_dim, is_continuous, txs, guard=guard
        )
    data_sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))

    # First observation (reference: dreamer_v3.py:538-551)
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), dtype=np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    if burst_mode:
        host_player.init_states(hp.host_params)
    else:
        player.init_states(params)

    from sheeprl_tpu.utils.profiler import TraceProfiler

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir)

    cumulative_per_rank_gradient_steps = 0
    for iter_num in range(start_iter, total_iters + 1):
        profiler.tick(iter_num)
        policy_step += policy_steps_per_iter
        # Host spans (utils.profiler.SPANS): one `iter` per iteration, closed
        # at the loop's foot; its counters are the values it starts from.
        iter_span = recorder.span(
            "iter", parent=recorder.ROOT, iter_num=iter_num, policy_step=policy_step,
            grad_steps=cumulative_per_rank_gradient_steps,
            grant_backlog=hp.grant_backlog if burst_mode else 0,
            staged_rows=hp.runner.staged_count if burst_mode else 0,
        ).start()

        if burst_mode:
            hp.poll()

        with timer("Time/env_interaction_time", SumMetric):
            if iter_num <= learning_starts and state is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    # env-major sample: one-hot each action head along axis -1
                    acts2d = actions.reshape(cfg.env.num_envs, len(actions_dim))
                    actions = np.concatenate(
                        [np.eye(d, dtype=np.float32)[acts2d[:, i]] for i, d in enumerate(actions_dim)],
                        axis=-1,
                    )
            else:
                with recorder.span("player.act"):  # policy forward and the pull of its actions
                    jobs = prepare_obs(fabric, obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                    if burst_mode:
                        # Host-CPU policy on the snapshot params: numpy obs +
                        # CPU-committed params keep the whole step off the wire.
                        action_list = host_player.get_actions(hp.host_params, jobs, hp.host_key())
                    else:
                        rng, subkey = jax.random.split(rng)
                        action_list = player.get_actions(params, jobs, subkey)
                    actions = np.asarray(jnp.concatenate(action_list, axis=-1))
                    if is_continuous:
                        real_actions = actions
                    else:
                        real_actions = np.stack([np.asarray(a).argmax(axis=-1) for a in action_list], axis=-1)

            with recorder.span("stage"):
                step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1)
                if host_mirror:
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)
                if burst_mode:
                    hp.stage_step(step_data)
                elif resident_mode:
                    resident_driver.stage_step(step_data)

            with recorder.span("env.step"):  # the wait for the env workers
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, agent_roe in enumerate(infos["restart_on_exception"]):
                if agent_roe and not dones[i]:
                    if host_mirror:
                        sub_rb = rb.buffer[i]
                        last_inserted_idx = (sub_rb._pos - 1) % sub_rb.buffer_size
                        sub_rb["terminated"][last_inserted_idx] = np.zeros_like(
                            sub_rb["terminated"][last_inserted_idx]
                        )
                        sub_rb["truncated"][last_inserted_idx] = np.ones_like(
                            sub_rb["truncated"][last_inserted_idx]
                        )
                        sub_rb["is_first"][last_inserted_idx] = np.zeros_like(
                            sub_rb["is_first"][last_inserted_idx]
                        )
                    step_data["is_first"][0, i] = np.ones_like(step_data["is_first"][0, i])
                    if burst_mode:
                        # Same truncation patch on the row still in staging
                        # (truncated isn't stored in the device ring).
                        hp.patch_last(i, {"terminated": 0.0, "is_first": 0.0})
                    elif resident_mode:
                        resident_driver.patch_last(i, {"terminated": 0.0, "is_first": 0.0})

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

        # Save the real next observation (reference: dreamer_v3.py:621-627)
        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k in obs_keys:
                        real_next_obs[k][idx] = np.asarray(final_obs[k])

        for k in obs_keys:
            step_data[k] = np.asarray(next_obs[k])[np.newaxis]
        obs = next_obs

        rewards = np.asarray(rewards, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["terminated"] = np.asarray(terminated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["truncated"] = np.asarray(truncated, dtype=np.float32).reshape(1, cfg.env.num_envs, -1)
        step_data["rewards"] = clip_rewards_fn(rewards)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))), dtype=np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            with recorder.span("stage"):
                if host_mirror:
                    rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
                if burst_mode:
                    hp.stage_reset(reset_data, dones_idxes)
                elif resident_mode:
                    resident_driver.stage_reset(reset_data, dones_idxes)

            # Reset already-inserted step data (reference: dreamer_v3.py:652-658)
            step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
            step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
            step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
            step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
            if burst_mode:
                host_player.init_states(hp.host_params, dones_idxes)
            else:
                player.init_states(params, dones_idxes)

        # Train (reference: dreamer_v3.py:660-706)
        if burst_mode:
            if iter_num >= learning_starts:
                hp.grant(ratio(policy_step - prefill_steps * policy_steps_per_iter))
            hp.pump()
            cumulative_per_rank_gradient_steps, train_step = hp.gradient_steps, hp.train_steps
        elif resident_mode:
            if iter_num >= learning_starts:
                resident_driver.grant(ratio(policy_step - prefill_steps * policy_steps_per_iter))
            # ONE fused append+sample+train dispatch per env step (plus
            # append-free drains while a full grant chunk is backlogged)
            with timer("Time/train_time", SumMetric):
                resident_carry, resident_metrics = resident_driver.pump(resident_carry)
            params, opts, moments_state = resident_carry[:3]
            if resident_metrics is not None and aggregator and not aggregator.disabled:
                from sheeprl_tpu.utils.burst import DREAMER_METRIC_NAMES

                for name, value in zip(DREAMER_METRIC_NAMES, resident_metrics):
                    if name in aggregator:
                        aggregator.update(name, value)
            cumulative_per_rank_gradient_steps = resident_driver.gradient_steps
            train_step = resident_driver.train_steps
        elif iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(policy_step - prefill_steps * policy_steps_per_iter)
            if per_rank_gradient_steps > 0:
                # the host-side replay path on the env-step critical path —
                # numpy window sampling + the f32 staging transfer — timed
                # for parity with the async tier's append-only segment
                # (BENCH_METRIC=dreamer_sebulba reads both)
                with timer("Time/replay_path_time", SumMetric):
                    sample = rb.sample(
                        batch_size,
                        sequence_length=seq_len,
                        n_samples=per_rank_gradient_steps,
                    )  # (G, T, B, ...)
                    # ONE packed sharded transfer for the whole sample dict
                    # (the PR-3 stager trick) instead of K per-key device_put
                    # dispatches
                    data = put_packed(sample, data_sharding, dtype=np.float32)
                with timer("Time/train_time", SumMetric):
                    rng, train_key = jax.random.split(rng)
                    params, opts, moments_state, metrics = train_fn(
                        params, opts, moments_state, data, train_key,
                        jnp.int32(cumulative_per_rank_gradient_steps),
                    )
                    if aggregator and not aggregator.disabled:
                        names = (
                            "Loss/world_model_loss", "Loss/observation_loss", "Loss/reward_loss",
                            "Loss/state_loss", "Loss/continue_loss", "State/kl", "State/post_entropy",
                            "State/prior_entropy", "Loss/policy_loss", "Loss/value_loss",
                        )
                        for name, value in zip(names, metrics):
                            if name in aggregator:
                                aggregator.update(name, value)
                cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                train_step += 1
                # metrics[-1] is the mean skipped fraction over the G steps
                if guard and sentinel.observe(float(metrics[-1]) * per_rank_gradient_steps):
                    def _rollback(good):
                        nonlocal params, opts, moments_state, rng
                        params = fabric.put_replicated(
                            jax.tree.map(
                                lambda t, s: jnp.asarray(s),
                                params,
                                {
                                    "world_model": good["world_model"],
                                    "actor": good["actor"],
                                    "critic": good["critic"],
                                    "target_critic": good["target_critic"],
                                },
                            )
                        )
                        cast = lambda t, s: jnp.asarray(s) if hasattr(t, "dtype") else s
                        opts = fabric.put_replicated(jax.tree.map(cast, opts, good["optimizers"]))
                        moments_state = fabric.put_replicated(
                            jax.tree.map(cast, moments_state, good["moments"])
                        )
                        if good.get("rng") is not None:
                            rng = jnp.asarray(good["rng"])

                    sentinel.recover(ckpt_dir, _rollback)

        if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
            if resident_mode:
                logger.log_dict(resident_driver.metrics(), policy_step)
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

        # Checkpoint (reference: dreamer_v3.py:735-760)
        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            if burst_mode:
                # Latest trainer-thread handles (at most one burst stale).
                params, opts, moments_state, _ = hp.carry
            ckpt_state = {
                "world_model": params["world_model"],
                "actor": params["actor"],
                "critic": params["critic"],
                "target_critic": params["target_critic"],
                "optimizers": opts,
                "moments": moments_state,
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
                # host as a DeviceReplayState), per-env heads included
                replay_ckpt = resident_driver.state_dict() if resident_mode else rb
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=replay_ckpt,
            )
        iter_span.stop()

    if burst_mode:
        # Flush the tail: Ratio already counted the remaining grants; grants
        # that can never execute (data still shorter than a window) are
        # abandoned with the run.
        params, opts, moments_state, _ = hp.finish()

    envs.close()
    profiler.close()
    if fabric.is_global_zero and cfg.algo.run_test:
        test(player, params, fabric, cfg, log_dir, greedy=False, writer=logger)

    if not cfg.model_manager.disabled and fabric.is_global_zero:  # pragma: no cover - mlflow optional
        from sheeprl_tpu.utils.mlflow import log_models, register_model

        register_model(
            fabric,
            log_models,
            cfg,
            {
                "world_model": params["world_model"],
                "actor": params["actor"],
                "critic": params["critic"],
                "target_critic": params["target_critic"],
                "moments": moments_state,
            },
        )
    logger.close()


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #


def audit_dreamer_setup(spec, capacity: int = 8, n_envs: int = 2, seq_len: int = 2, grad_chunk: int = 1):
    """Tiny pixel+vector DreamerV3 context on the audit mesh (shared with the
    ``dreamer_sebulba.*`` registrations): XS-scaled agent + optimizers +
    the sequence-ring spec, all replicated — the Dreamer burst/async programs
    run fully replicated with the batch axis split per device in-graph."""
    from sheeprl_tpu.algos.ppo.ppo import _abstract_like
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.burst import dreamer_ring_keys

    batch = 2 * spec.devices
    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            f"env.num_envs={n_envs}",
            "env.screen_size=64",
            "algo=dreamer_v3_XS",
            f"algo.per_rank_batch_size={batch}",
            f"algo.per_rank_sequence_length={seq_len}",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.world_model.reward_model.bins=17",
            "algo.critic.bins=17",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
        ]
    )
    fabric = Fabric(devices=spec.devices, accelerator="cpu")
    obs_space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
            "state": gym.spaces.Box(-20, 20, (4,), np.float32),
        }
    )
    actions_dim = (2,)
    world_model, actor, critic, params, player = build_agent(
        fabric, actions_dim, False, cfg, obs_space, None, None, None, None
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
    moments = init_moments()
    rep = fabric.replicated
    ring_keys = dreamer_ring_keys(obs_space, ["rgb"], ["state"], actions_dim, with_is_first=True)
    carry = (
        _abstract_like(params, rep),
        _abstract_like(opts, rep),
        _abstract_like(moments, rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
    )
    return {
        "cfg": cfg,
        "fabric": fabric,
        "mesh": fabric.mesh,
        "world_model": world_model,
        "actor": actor,
        "critic": critic,
        "params": params,
        "txs": txs,
        "carry": carry,
        "ring_keys": ring_keys,
        "capacity": capacity,
        "n_envs": n_envs,
        "seq_len": seq_len,
        "grad_chunk": grad_chunk,
        "batch": batch,
        "actions_dim": actions_dim,
        "rep": rep,
    }


from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402


@register_audit_programs("dreamer_v3.burst_step")
def _audit_programs(spec: AuditMesh):
    from sheeprl_tpu.data.ring import effective_stage_buckets, make_blob_layouts, ring_cell

    # capacity 128: the ring (3.2 MB) outweighs the XS step's temporaries, so one
    # more copy of it (a lost donation, a relayout) passes the `peak_hbm_bytes`
    # budget's tolerance and fails AUD005
    s = audit_dreamer_setup(spec, capacity=128)
    buckets = effective_stage_buckets((1, 2), 2)  # the SequenceRingDriver flush set
    ring_spec = {
        "capacity": s["capacity"],
        "n_envs": s["n_envs"],
        "grad_chunk": s["grad_chunk"],
        "seq_len": s["seq_len"],
        "batch_size": s["batch"],
        "ring_keys": s["ring_keys"],
        "stage_buckets": buckets,
        "stage_max": 2,
    }
    # ONE lowering path with the driver: the same make_train_step(ring=...)
    # builder SequenceRingDriver dispatches (fused append+sample+train)
    burst_fn = make_train_step(
        s["world_model"], s["actor"], s["critic"], s["cfg"], s["mesh"], s["actions_dim"], False,
        s["txs"], ring=ring_spec,
    )
    layouts = make_blob_layouts(s["ring_keys"], s["n_envs"], s["grad_chunk"], buckets)
    blob = jax.ShapeDtypeStruct((layouts[max(buckets)].nbytes,), jnp.uint8, sharding=s["rep"])
    rb = {  # the ring as stored (utils/burst.py:init_device_ring)
        k: jax.ShapeDtypeStruct((s["capacity"], s["n_envs"]) + ring_cell(shape), dtype, sharding=s["rep"])
        for k, (shape, dtype) in s["ring_keys"].items()
    }
    yield AuditProgram(
        name="dreamer_v3.burst_step",
        fn=burst_fn,
        args=(s["carry"], rb, blob),
        source=__name__,
        donate_argnums=(1,),
        feedback_outputs=(0, 1),
        out_decl={0: P(), 1: P()},
        mesh=s["mesh"],
        wire_dtype=spec.wire_dtype,
    )
