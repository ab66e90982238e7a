"""The on-device (Anakin) PPO block for a language-model policy on the token
MDP (``algo.lm`` set; :mod:`sheeprl_tpu.algos.ppo.ppo_anakin` is the entry
point and the host loop, howto/lm_policy.md the guide).

One iteration, all inside the one jitted ``shard_map`` block, is one batch of
prompts, as in language-model post-training:

- every env resets (a new prompt each);
- **rollout**: prefill of the prompts (a full-sequence forward that fills the
  cache, whichever kinds of state the policy's layers keep), then a
  ``lax.scan`` of ``rollout_steps`` decode steps that
  carries ``(env state, cache, logits, value, key)``: each step samples a
  token from the categorical over the held vocabulary, records token,
  log-probability and value, steps the env and decodes the token through the
  cache for the next step's logits;
- **GAE** (``ops.gae``), then the **update**: ``update_epochs`` x minibatches
  of *whole sequences*; a full-sequence forward with each decoder layer under
  ``jax.checkpoint``; head, value, the clipped policy loss, the value loss
  and the entropy bonus on the response positions only; the optimizer from
  ``optim/``; ``pmean`` over ``dp``.

The block's signature is the classic Anakin block's and one input more, so
the host loop, the block cache, donation and the checkpoint layout are shared.
The input is ``grad_steps``: how many of an iteration's gradient steps the
update may run (the host loop grants all; the update's loop runs that many
times and no step is computed and thrown away). Its metrics add the per-step
losses and gradient norms of the update and the routed layer's counters
(``moe_local_assignments``, ``moe_max_expert_load`` per layer;
``moe_dropped``: over rollout and update, the assignments to held experts
less the rows the grouped products were handed, :func:`decoder_lm.moe_share`;
``moe_compact_calls`` of ``moe_compactable_calls``: of the routed layer's
calls that could move only the head of their sorted rows, prefill's and the
update's forwards, those that did; ``rollout_cache_bytes``: what the rollout's
cache holds on all devices, from its arrays' sizes; and, for a policy whose
router has a selection bias, ``moe_bias_moved`` of ``moe_bias_movable``: of
the assignments made in prefill and the update's forwards, those that the
unbiased scores would not have kept).
``algo.ferry_rollout`` adds what the rollout recorded (tokens,
log-probabilities, values, rewards) to the metrics, for a check against
another implementation.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.models import decoder_lm as lm
from sheeprl_tpu.ops import gae as gae_op
from sheeprl_tpu.parallel.comm import pmean_grads

__all__ = ["LMPolicy", "build_lm_agent", "make_anakin_lm_local_block", "make_anakin_lm_block", "PROGRAM_NAME"]

#: the jitted block's name: its XLA module is ``jit_<this>``, its registered program ``<this>/<iterations>``
PROGRAM_NAME = "ppo_anakin_lm_block"


class LMPolicy:
    """What the block needs of the policy: the decoder's static config and
    the env's lengths."""

    def __init__(self, model: lm.DecoderConfig, prompt_len: int, response_len: int):
        self.model = model
        self.prompt_len, self.response_len = int(prompt_len), int(response_len)


def build_lm_agent(fabric, cfg, jenv, agent_state=None):
    """``(policy, params)``: the decoder from ``algo.lm``, its parameters made
    in one jitted call (or taken from a restored state) and replicated."""
    model = lm.DecoderConfig.from_config(cfg.algo.lm)
    if model.vocab_held != jenv.vocab_size:
        raise ValueError(f"the env draws from {jenv.vocab_size} ids, the policy holds {model.vocab_held}")
    policy = LMPolicy(model, jenv.prompt_len, jenv.response_len)
    if agent_state is not None:
        params = jax.tree.map(lambda s: jnp.asarray(s, jnp.float32), agent_state)
    else:
        params = jax.jit(lambda key: lm.init_params(model, key))(jax.random.PRNGKey(cfg.seed))
    return policy, fabric.put_replicated(params)


def routed_assignments(policy: LMPolicy, tokens: int) -> int:
    """The assignments the routed layers make for ``tokens`` tokens of one sequence."""
    model = policy.model
    return tokens * model.top_k * (model.layers - model.dense_layers)


def _response_outputs(policy: LMPolicy, params, tokens):
    """New log-probabilities, entropies and values on the response positions
    of ``tokens`` (B, P + R), and the routing counters. The head is applied to
    the positions whose next token was sampled, never to the prompt."""
    P_, R = policy.prompt_len, policy.response_len
    x, counters, _ = lm.forward(policy.model, params, tokens)
    with jax.named_scope("lm.head_loss"):
        logits, values = lm.heads(policy.model, params, x[:, P_ - 1 : P_ + R - 1])
        logp_all = jax.nn.log_softmax(logits, axis=-1)
        logp = jnp.take_along_axis(logp_all, tokens[:, P_:, None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    return logp, entropy, values, counters


def make_sequence_train(policy: LMPolicy, tx, cfg, local_envs: int, guard: bool):
    """The per-device update over whole sequences: ``(params, opt_state, data,
    key, clip_coef, ent_coef, grad_steps) -> (params, opt_state, metrics)``;
    ``data`` holds per sequence the tokens and, per response position, the
    rollout's log-probabilities, values, returns and advantages; the first
    ``grad_steps`` of the iteration's ``update_epochs`` x minibatches gradient
    steps are run."""
    mb_size = int(cfg.algo.per_rank_batch_size)
    if local_envs % mb_size:
        raise ValueError(f"per-device sequences ({local_envs}) must be a multiple of per_rank_batch_size ({mb_size})")
    n_mb = local_envs // mb_size
    update_epochs = int(cfg.algo.update_epochs)
    clip_vloss, normalize_adv = bool(cfg.algo.clip_vloss), bool(cfg.algo.normalize_advantages)
    vf_coef, reduction = float(cfg.algo.vf_coef), str(cfg.algo.loss_reduction)

    total = update_epochs * n_mb
    layers, width = policy.model.layers, policy.model.counters

    def gradient_step(params, opt_state, batch, clip_coef, ent_coef):
        advantages = batch["advantages"]
        if normalize_adv:
            advantages = (advantages - advantages.mean()) / (advantages.std() + 1e-8)

        def loss_fn(p):
            logp, entropy, values, counters = _response_outputs(policy, p, batch["tokens"])
            with jax.named_scope("lm.head_loss"):
                pg = policy_loss(logp, batch["logprobs"], advantages, clip_coef, reduction)
                v = value_loss(values, batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
                ent = entropy_loss(entropy, reduction)
            return pg + vf_coef * v + ent_coef * ent, (pg, v, ent, counters)

        (loss, (pg, v, ent, counters)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("ppo.optim"):
            grads = pmean_grads(grads, "dp")
            grad_norm = optax.global_norm(grads)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            bad = jnp.zeros((), jnp.float32)
            if guard:
                from sheeprl_tpu.ops import finite_guard, guarded_select

                ok = jnp.logical_and(jnp.isfinite(grad_norm), finite_guard(loss))
                ok = jax.lax.pmin(ok.astype(jnp.int32), "dp").astype(bool)
                new_params, new_opt_state = guarded_select(ok, (new_params, new_opt_state), (params, opt_state))
                bad = 1.0 - ok.astype(jnp.float32)
        return new_params, new_opt_state, {"pg": pg, "v": v, "ent": ent, "grad_norm": grad_norm, "counters": counters,
                                           "bad": bad}

    def sequence_train(params, opt_state, data, key, clip_coef, ent_coef, grad_steps):
        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        # every epoch's minibatches, in order: (total, mb_size) sequence indices
        order = jnp.concatenate([
            jax.random.permutation(k, local_envs).reshape(n_mb, mb_size) for k in jax.random.split(key, update_epochs)
        ])

        def one_step(i, carry):
            params, opt_state, out = carry
            batch = jax.tree.map(lambda x: x[order[i]], data)
            params, opt_state, step_out = gradient_step(params, opt_state, batch, clip_coef, ent_coef)
            return params, opt_state, jax.tree.map(lambda rows, x: rows.at[i].set(x), out, step_out)

        # a step that is not granted is not run: its row of the per-step outputs stays zero
        out = {k: jnp.zeros((total,), jnp.float32) for k in ("pg", "v", "ent", "grad_norm", "bad")}
        out["counters"] = jnp.zeros((total, layers, width), jnp.int32)
        granted = jnp.clip(grad_steps, 0, total)
        params, opt_state, out = jax.lax.fori_loop(0, granted, one_step, (params, opt_state, out))
        steps = {k: out[k] for k in ("pg", "v", "ent")}
        ran = jnp.maximum(granted, 1).astype(jnp.float32)
        metrics = {k: jax.lax.pmean(x.sum() / ran, "dp") for k, x in steps.items()}
        metrics.update({k + "_steps": jax.lax.pmean(x, "dp") for k, x in steps.items()})
        metrics["grad_norm_steps"] = out["grad_norm"]
        # counters: (steps, layers, 5 or 6) -> what the update's forwards saw, per layer
        metrics["moe_local_assignments"] = jax.lax.psum(out["counters"][..., 0].sum(axis=0), "dp")
        metrics["moe_max_expert_load"] = jax.lax.pmax(out["counters"][..., 1].max(axis=0), "dp")
        metrics["moe_dropped"] = out["counters"][..., 2].sum()
        metrics["moe_compact_calls"] = out["counters"][..., 3].sum()
        metrics["moe_compactable_calls"] = out["counters"][..., 4].sum()
        if policy.model.selection_bias:  # the router's selection bias: the assignments it moved, of those the granted steps made
            metrics["moe_bias_moved"] = out["counters"][..., 5].sum()
            metrics["moe_bias_movable"] = granted * mb_size * routed_assignments(policy, policy.prompt_len + policy.response_len)
        if guard:
            metrics["bad"] = out["bad"].sum()
        return params, opt_state, metrics

    return sequence_train


def make_anakin_lm_local_block(policy: LMPolicy, tx, cfg, benv, local_envs: int, iters_per_block: int,
                               ferry_episodes: bool = True, guard: bool = False):
    """The per-device fused block body (must run inside a ``shard_map`` with a
    ``dp`` axis); signature and outputs as
    :func:`sheeprl_tpu.algos.ppo.ppo_anakin.make_anakin_local_block`, with
    ``grad_steps`` (the gradient steps granted to each iteration) as the last
    input."""
    ferry_rollout = bool(cfg.algo.get("ferry_rollout", False))
    model = policy.model
    P_, R = policy.prompt_len, policy.response_len
    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)
    sequence_train = make_sequence_train(policy, tx, cfg, local_envs, guard)
    benv = type(benv)(benv.env, local_envs)  # this device's envs: the block resets them itself, every iteration

    def rollout(params, key, env_params):
        key, reset_key = jax.random.split(key)
        env_state, obs = benv.reset(reset_key, env_params)
        prompts = obs[:, :P_]
        with jax.named_scope("rollout.prefill"):
            # one sequence at a time: the routed layer's sorted buffers are sized for every
            # assignment of the tokens in flight, and a whole batch of prompts is too many
            x, cache, prefill_counters = jax.lax.map(lambda p: lm.prefill(model, params, p[None], P_ + R), prompts)
            x, cache = jax.tree.map(lambda a: a[:, 0], (x, cache))
            logits, value = lm.heads(model, params, x)
        cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))

        def decode(carry, t):
            env_state, cache, logits, value, key = carry
            with jax.named_scope("rollout.decode"):
                key, akey = jax.random.split(key)
                token = jax.random.categorical(akey, logits, axis=-1).astype(jnp.int32)
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), token[:, None], axis=-1)[:, 0]
                env_state, _, reward, done, info = benv.step(env_state, token, env_params)
                x, cache, counters = lm.decode_step(model, params, cache, token, P_ + t)
                next_logits, next_value = lm.heads(model, params, x)
                # the time limit ends every episode: bootstrap from the state it was cut at
                bootstrapped = reward + gamma * next_value * info["truncated"].astype(jnp.float32)
            y = {"tokens": token, "logprobs": logp, "values": value, "rewards": bootstrapped,
                 "dones": done.astype(jnp.float32), "raw_rewards": reward, "counters": counters}
            return (env_state, cache, next_logits, next_value, key), y

        (env_state, _, _, last_value, key), traj = jax.lax.scan(
            decode, (env_state, cache, logits, value, key), jnp.arange(R)
        )
        counters = prefill_counters.sum(axis=0) + traj.pop("counters").sum(axis=0)  # (layers, 5 or 6): prefill and decode
        return env_state, prompts, traj, last_value, key, counters, cache_bytes

    def local_block(params, opt_state, env_state, obs, ep_ret, ep_len, env_keys, train_key, clip_coef, ent_coef, env_params,
                    grad_steps):
        def one_iter(carry, train_key):
            params, opt_state, _, _, ep_ret, ep_len, env_key = carry
            env_state, prompts, traj, last_value, env_key, rollout_counters, cache_bytes = rollout(params, env_key, env_params)
            with jax.named_scope("ppo.gae"):
                returns, advantages = gae_op(
                    traj["rewards"][..., None], traj["values"][..., None], traj["dones"][..., None],
                    last_value[..., None], gamma=gamma, gae_lambda=gae_lambda,
                )
            per_seq = lambda x: jnp.swapaxes(x, 0, 1)  # (R, E, ...) -> (E, R, ...)  # noqa: E731
            data = {
                "tokens": jnp.concatenate([prompts, per_seq(traj["tokens"])], axis=1),
                "logprobs": per_seq(traj["logprobs"]), "values": per_seq(traj["values"]),
                "returns": per_seq(returns[..., 0]), "advantages": per_seq(advantages[..., 0]),
            }
            params, opt_state, metrics = sequence_train(params, opt_state, data, train_key, clip_coef, ent_coef, grad_steps)
            metrics["moe_rollout_assignments"] = jax.lax.psum(rollout_counters[:, 0], "dp")
            columns = [("moe_dropped", 2), ("moe_compact_calls", 3), ("moe_compactable_calls", 4)]
            if model.selection_bias:  # prefill's assignments could be moved too (decode counts none)
                columns.append(("moe_bias_moved", 5))
                metrics["moe_bias_movable"] = jax.lax.psum(
                    metrics["moe_bias_movable"] + local_envs * routed_assignments(policy, P_), "dp")
            for name, column in columns:
                metrics[name] = jax.lax.psum(metrics[name] + rollout_counters[:, column].sum(), "dp")
            # what the rollout's cache held, every device's: a size, known when the block is traced (float32: over 2**31)
            metrics["rollout_cache_bytes"] = jnp.float32(cache_bytes * jax.lax.psum(1, "dp"))
            ep_return = traj["raw_rewards"].sum(axis=0)
            metrics["reward"] = jax.lax.pmean(ep_return.mean(), "dp")
            if ferry_episodes:
                done = traj["dones"] > 0
                metrics.update(ep_done=done, ep_ret=jnp.where(done, ep_return[None], 0.0),
                               ep_len=jnp.where(done, R, 0).astype(jnp.int32))
            if ferry_rollout:  # what was sampled, as the rollout recorded it (a few hundred KB an iteration)
                metrics["rollout"] = {**{k: data[k] for k in ("tokens", "logprobs", "values")},
                                      "rewards": per_seq(traj["raw_rewards"]), "last_value": last_value}
            obs = env_state.env_state.tokens
            return (params, opt_state, env_state, obs, jnp.zeros_like(ep_ret), jnp.zeros_like(ep_len), env_key), metrics

        carry = (params, opt_state, env_state, obs, ep_ret, ep_len, env_keys[0])
        carry, metrics = jax.lax.scan(one_iter, carry, jax.random.split(train_key, iters_per_block))
        params, opt_state, env_state, obs, ep_ret, ep_len, env_key = carry
        return params, opt_state, env_state, obs, ep_ret, ep_len, env_key[None], metrics

    return local_block


def metric_specs(ferry_episodes: bool, guard: bool, ferry_rollout: bool, selection_bias: bool = False) -> Dict[str, Any]:
    specs = {k: P() for k in ("pg", "v", "ent", "pg_steps", "v_steps", "ent_steps", "grad_norm_steps",
                              "moe_local_assignments", "moe_rollout_assignments", "moe_max_expert_load", "moe_dropped",
                              "moe_compact_calls", "moe_compactable_calls", "rollout_cache_bytes", "reward")}
    if selection_bias:
        specs.update(moe_bias_moved=P(), moe_bias_movable=P())
    if guard:
        specs["bad"] = P()
    if ferry_episodes:
        specs.update(ep_done=P(None, None, "dp"), ep_ret=P(None, None, "dp"), ep_len=P(None, None, "dp"))
    if ferry_rollout:
        specs["rollout"] = {k: P(None, "dp") for k in ("tokens", "logprobs", "values", "rewards", "last_value")}
    return specs


def make_anakin_lm_block(policy: LMPolicy, tx, cfg, mesh, benv, local_envs: int, iters_per_block: int,
                         ferry_episodes: bool = True, guard: bool = False):
    """The jitted fused block: one ``shard_map`` over ``dp``, envs sharded,
    parameters and optimizer state replicated, and everything that is fed back
    donated and pinned to the driver's staging sharding, as
    :func:`sheeprl_tpu.algos.ppo.ppo_anakin.make_anakin_block` does."""
    local_block = make_anakin_lm_local_block(
        policy, tx, cfg, benv, local_envs, iters_per_block, ferry_episodes=ferry_episodes, guard=guard
    )
    env_sharded = P("dp")
    shard_block = shard_map(
        local_block, mesh=mesh,
        in_specs=(P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded, P(), P(), P(), P(), P()),
        out_specs=(P(), P(), env_sharded, env_sharded, env_sharded, env_sharded, env_sharded,
                   metric_specs(ferry_episodes, guard, bool(cfg.algo.get("ferry_rollout", False)),
                                policy.model.selection_bias)),
        check_vma=False,
    )
    env_out, rep_out = NamedSharding(mesh, env_sharded), NamedSharding(mesh, P())
    out_shardings = (rep_out, rep_out, env_out, env_out, env_out, env_out, env_out, None)
    def ppo_anakin_lm_block(*args):
        return shard_block(*args)

    return jax.jit(ppo_anakin_lm_block, donate_argnums=(0, 1, 2, 3, 4, 5, 6), out_shardings=out_shardings)


# --------------------------------------------------------------------------- #
# graft-audit program registration (sheeprl_tpu.analysis.programs)
# --------------------------------------------------------------------------- #

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs  # noqa: E402

#: a four-layer ``[0, 1, 1, 1]`` stack at toy widths, half of its experts held: what the audit and the tests compile
TOY_OVERRIDES = (
    "exp=ppo_anakin_lm", "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2",
    "algo.lm.head_dim=16", "algo.lm.moe_ffn_hidden_size=32", "algo.lm.moe_num_primary_experts=8",
    "algo.lm.moe_num_active_primary_experts=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2",
    "algo.lm.num_hidden_layers=4", "algo.lm.sliding_window_size=8", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64",
    "env.prompt_len=24", "algo.rollout_steps=8",
)
#: the second policy at toy widths: a dense layer and two routed ones with shared experts, latent attention
TOY_OVERRIDES_LATENT = (
    "exp=ppo_anakin_lm_kanana2", "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=4",
    "algo.lm.qk_nope_head_dim=16", "algo.lm.qk_rope_head_dim=8", "algo.lm.qk_head_dim=24", "algo.lm.v_head_dim=16",
    "algo.lm.kv_lora_rank=32", "algo.lm.intermediate_size=96", "algo.lm.moe_intermediate_size=32",
    "algo.lm.n_routed_experts=8", "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2",
    "algo.lm.num_hidden_layers=3", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64", "env.prompt_len=24",
    "algo.rollout_steps=8",
)
#: the third policy at toy widths: a dense layer and four routed ones ([window, window, window, full, window]) with a
#: shared expert, gated grouped-query attention with q/k norms, sandwich norms, a scaled embedding
TOY_OVERRIDES_AFMOE = (
    "exp=ppo_anakin_lm_trinity", "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4",
    "algo.lm.num_key_value_heads=2", "algo.lm.head_dim=16", "algo.lm.intermediate_size=96",
    "algo.lm.moe_intermediate_size=32", "algo.lm.num_experts=8", "algo.lm.num_experts_per_tok=2",
    "algo.lm.experts_held=4", "algo.lm.expert_offset=2", "algo.lm.num_hidden_layers=5", "algo.lm.num_dense_layers=1",
    "algo.lm.sliding_window=8", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64", "env.prompt_len=24",
    "algo.rollout_steps=8",
)

#: the fourth policy at toy widths: a dense layer and four routed ones ([conv, conv, full, conv, conv]) without a shared
#: expert, gated short convolutions beside grouped-query attention with q/k norms, a tied head
TOY_OVERRIDES_LFM2 = (
    "exp=ppo_anakin_lm_lfm2", "algo.lm.hidden_size=64", "algo.lm.num_attention_heads=4",
    "algo.lm.num_key_value_heads=2", "algo.lm.intermediate_size=96", "algo.lm.moe_intermediate_size=32",
    "algo.lm.num_experts=8", "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.expert_offset=2",
    "algo.lm.num_hidden_layers=5", "algo.lm.num_dense_layers=1", "algo.lm.vocab_size=96", "algo.lm.vocab_held=64",
    "env.prompt_len=24", "algo.rollout_steps=8",
)


def _audit_block(spec: AuditMesh, name: str, overrides):
    from sheeprl_tpu.algos.ppo.ppo import _abstract_like
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, make_jax_env
    from sheeprl_tpu.optim.builders import build_optimizer

    mesh = spec.build()
    num_envs = 2 * spec.devices
    cfg = compose([*overrides, f"env.num_envs={num_envs}", "algo.per_rank_batch_size=1"])
    model = lm.DecoderConfig.from_config(cfg.algo.lm)
    jenv = make_jax_env(cfg.env.id, vocab_size=model.vocab_held, prompt_len=int(cfg.env.prompt_len),
                        response_len=int(cfg.algo.rollout_steps))
    policy = LMPolicy(model, jenv.prompt_len, jenv.response_len)
    benv = BatchedJaxEnv(jenv, num_envs)
    params = jax.eval_shape(lambda key: lm.init_params(model, key), jax.random.PRNGKey(0))
    tx = optax.inject_hyperparams(
        lambda learning_rate: build_optimizer({**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm)
    )(learning_rate=float(cfg.algo.optimizer.lr))
    opt_state = jax.eval_shape(tx.init, params)
    rep, env_sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    env_state, obs = jax.eval_shape(benv.reset, jax.random.PRNGKey(1))
    fn = make_anakin_lm_block(policy, tx, cfg, mesh, benv, num_envs // spec.devices, 2, ferry_episodes=True, guard=True)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return AuditProgram(
        name=name,
        fn=fn,
        args=(
            _abstract_like(params, rep), _abstract_like(opt_state, rep), _abstract_like(env_state, env_sh),
            _abstract_like(obs, env_sh), jax.ShapeDtypeStruct((num_envs,), jnp.float32, sharding=env_sh),
            jax.ShapeDtypeStruct((num_envs,), jnp.int32, sharding=env_sh),
            jax.ShapeDtypeStruct((spec.devices, 2), jnp.uint32, sharding=env_sh),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep), scalar, scalar, _abstract_like(jenv.default_params(), rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        ),
        source=__name__,
        donate_argnums=(0, 1, 2, 3, 4, 5, 6),
        feedback_outputs=(0, 1, 2, 3, 4, 5, 6),
        out_decl={0: P(), 1: P(), 2: P("dp"), 3: P("dp"), 4: P("dp"), 5: P("dp"), 6: P("dp")},
        mesh=mesh,
        wire_dtype=spec.wire_dtype,
    )


@register_audit_programs("ppo_anakin_lm.block")
def _audit_programs(spec: AuditMesh):
    yield _audit_block(spec, "ppo_anakin_lm.block", TOY_OVERRIDES)


@register_audit_programs("ppo_anakin_lm.block_latent")
def _audit_programs_latent(spec: AuditMesh):
    yield _audit_block(spec, "ppo_anakin_lm.block_latent", TOY_OVERRIDES_LATENT)


@register_audit_programs("ppo_anakin_lm.block_afmoe")
def _audit_programs_afmoe(spec: AuditMesh):
    yield _audit_block(spec, "ppo_anakin_lm.block_afmoe", TOY_OVERRIDES_AFMOE)


@register_audit_programs("ppo_anakin_lm.block_lfm2")
def _audit_programs_lfm2(spec: AuditMesh):
    yield _audit_block(spec, "ppo_anakin_lm.block_lfm2", TOY_OVERRIDES_LFM2)
