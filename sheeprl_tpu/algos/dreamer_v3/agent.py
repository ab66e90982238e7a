"""Dreamer-V3 agent (reference: ``sheeprl/algos/dreamer_v3/agent.py``).

TPU-first structure:

- every network is a flax module; the RSSM is a frozen dataclass of modules
  plus *pure single-step functions* (``dynamic``/``imagination``) designed to
  be the body of a ``lax.scan`` — the reference's Python time loops
  (``dreamer_v3.py:131-145, 234-240``) become two compiled scans;
- the learnable initial recurrent state is a plain parameter in the world
  model params tree (reference: ``agent.py:382-389``);
- the player is the same params applied with batch-shaped inputs — the
  reference's deep-copied, weight-tied player modules (``agent.py:1225-1236``)
  are unnecessary in functional JAX;
- Hafner's initialization (truncated-normal + scaled-uniform output heads,
  reference ``utils.py:141-188``) is applied by post-init param surgery in
  :func:`build_agent`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.distributions import (
    BernoulliSafeMode,
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
)
from sheeprl_tpu.utils.utils import player_reset_fn as _player_reset_fn
from sheeprl_tpu.utils.utils import player_zeros as _player_zeros
from sheeprl_tpu.models import MLP, LayerNormGRUCell
from sheeprl_tpu.models.blocks import _ConvTranspose
from sheeprl_tpu.models.scan_grads import scan_dense_grads_after
from sheeprl_tpu.ops import symlog

__all__ = [
    "CNNEncoder",
    "MLPEncoder",
    "Encoder",
    "CNNDecoder",
    "MLPDecoder",
    "RecurrentModel",
    "RSSM",
    "Actor",
    "PlayerDV3",
    "WorldModel",
    "build_agent",
    "sample_stochastic",
    "actor_sample",
    "actor_dists",
]


class CNNEncoder(nn.Module):
    """4-stage stride-2 conv encoder, LayerNorm (channel-last) + SiLU per
    stage, flattened output (reference: ``agent.py:42-99``)."""

    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    dtype: Any = None

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)  # (..., H, W, C)
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        for i in range(self.stages):
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                kernel_size=(4, 4),
                strides=(2, 2),
                padding=((1, 1), (1, 1)),
                use_bias=False,
                dtype=self.dtype,
                name=f"conv_{i}",
            )(x)
            x = nn.LayerNorm(epsilon=1e-3, dtype=self.dtype, name=f"ln_{i}")(x)
            x = nn.silu(x)
        return x.reshape(*lead, -1)


class MLPEncoder(nn.Module):
    """Symlog-squashed vector encoder (reference: ``agent.py:100-152``)."""

    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 512
    symlog_inputs: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], axis=-1)
        return MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="model",
        )(x)


class Encoder(nn.Module):
    """Multi-modal encoder concatenating CNN and MLP features."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_channels_multiplier: int
    mlp_layers: int
    dense_units: int
    stages: int = 4
    dtype: Any = None

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        parts = []
        if self.cnn_keys:
            parts.append(
                CNNEncoder(
                    keys=self.cnn_keys,
                    channels_multiplier=self.cnn_channels_multiplier,
                    stages=self.stages,
                    dtype=self.dtype,
                    name="cnn_encoder",
                )(obs)
            )
        if self.mlp_keys:
            parts.append(
                MLPEncoder(
                    keys=self.mlp_keys,
                    mlp_layers=self.mlp_layers,
                    dense_units=self.dense_units,
                    dtype=self.dtype,
                    name="mlp_encoder",
                )(obs)
            )
        return jnp.concatenate(parts, axis=-1)


class CNNDecoder(nn.Module):
    """Inverse of :class:`CNNEncoder`: linear projection to a 4×4 feature map
    then ``stages`` stride-2 transposed convs (reference: ``agent.py:154-227``).
    Returns one tensor per key, split on channels."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    stages: int = 4
    dtype: Any = None

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        lead = latent.shape[:-1]
        x = nn.Dense(self.cnn_encoder_output_dim, dtype=self.dtype, name="fc")(latent)
        x = x.reshape(-1, 4, 4, self.cnn_encoder_output_dim // 16)
        hidden = [(2**i) * self.channels_multiplier for i in reversed(range(self.stages - 1))]
        for i, ch in enumerate(hidden):
            x = _ConvTranspose(
                features=ch,
                kernel_size=(4, 4),
                strides=(2, 2),
                padding=1,
                use_bias=False,
                dtype=self.dtype,
                name=f"deconv_{i}",
            )(x)
            x = nn.LayerNorm(epsilon=1e-3, dtype=self.dtype, name=f"ln_{i}")(x)
            x = nn.silu(x)
        x = _ConvTranspose(
            features=int(sum(self.output_channels)),
            kernel_size=(4, 4),
            strides=(2, 2),
            padding=1,
            dtype=self.dtype,
            name="out",
        )(x)
        x = x.reshape(*lead, *x.shape[1:])
        splits = np.cumsum(np.asarray(self.output_channels[:-1], dtype=np.int64)).tolist()
        parts = jnp.split(x, splits, axis=-1) if len(self.keys) > 1 else [x]
        return {k: p for k, p in zip(self.keys, parts)}


class MLPDecoder(nn.Module):
    """Inverse of :class:`MLPEncoder` with per-key linear heads
    (reference: ``agent.py:229-279``)."""

    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    dtype: Any = None

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="model",
        )(latent)
        return {
            k: nn.Dense(int(d), dtype=self.dtype, name=f"head_{i}")(x)
            for i, (k, d) in enumerate(zip(self.keys, self.output_dims))
        }


class RecurrentModel(nn.Module):
    """MLP + LayerNorm-GRU sequence cell (reference: ``agent.py:281-342``)."""

    recurrent_state_size: int
    dense_units: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = MLP(
            hidden_sizes=(self.dense_units,),
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="mlp",
        )(x)
        h, _ = LayerNormGRUCell(
            hidden_size=self.recurrent_state_size,
            use_bias=False,
            layer_norm=True,
            dtype=self.dtype,
            name="rnn",
        )(recurrent_state, feat)
        return h


class _StochHead(nn.Module):
    """One-hidden-layer MLP emitting stochastic-state logits (used by both
    the transition and representation models)."""

    hidden_size: int
    stoch_state_size: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, addend: Optional[jax.Array] = None) -> jax.Array:
        x = MLP(
            hidden_sizes=(self.hidden_size,),
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="model",
        )(x, addend=addend)
        return nn.Dense(self.stoch_state_size, dtype=self.dtype, name="out")(x)


class _PredictionHead(nn.Module):
    """MLP + linear head (reward / continue / critic share this shape)."""

    output_dim: int
    mlp_layers: int
    dense_units: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="model",
        )(x)
        return nn.Dense(self.output_dim, dtype=self.dtype, name="out")(x)


def _unimix(logits: jax.Array, discrete: int, unimix: float) -> jax.Array:
    """1% uniform mixing of the stochastic-state categoricals
    (reference: ``agent.py:437-450``). In/out: flat ``(..., S*D)``."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        uniform = jnp.ones_like(probs) / discrete
        probs = (1 - unimix) * probs + unimix * uniform
        logits = jnp.log(probs)
    return logits.reshape(*logits.shape[:-2], -1)


def sample_stochastic(logits: jax.Array, discrete: int, key: Optional[jax.Array], sample: bool = True) -> jax.Array:
    """Straight-through sample (or mode) of the grouped categoricals; flat
    ``(..., S*D)`` in and out (reference ``compute_stochastic_state``)."""
    grouped = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = OneHotCategoricalStraightThrough(logits=grouped)
    out = dist.rsample(key) if sample else dist.mode
    return out.reshape(*out.shape[:-2], -1)


@dataclasses.dataclass(frozen=True)
class RSSM:
    """Pure single-step RSSM ops over the world-model params tree
    (reference: ``agent.py:344-594``, incl. the ``DecoupledRSSM`` variant
    selected via ``decoupled``: the representation model then conditions on
    the embedded observation only). Every method is scan-body ready."""

    recurrent_model: RecurrentModel
    representation_model: _StochHead
    transition_model: _StochHead
    discrete: int = 32
    unimix: float = 0.01
    decoupled: bool = False
    learnable_initial_state: bool = True

    def get_initial_states(self, wmp, batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        init = wmp["initial_recurrent_state"]
        if not self.learnable_initial_state:
            init = jax.lax.stop_gradient(init)
        rec = jnp.tanh(init)
        rec = jnp.broadcast_to(rec, (*batch_shape, rec.shape[-1]))
        logits, post = self._transition(wmp, rec, sample_state=False)
        return rec, post

    def _representation(self, wmp, recurrent_state, embedded_obs, key) -> Tuple[jax.Array, jax.Array]:
        if self.decoupled:
            inputs = embedded_obs  # reference DecoupledRSSM._representation (agent.py:582-594)
        else:
            inputs = jnp.concatenate([recurrent_state, embedded_obs], axis=-1)
        logits = self.representation_model.apply(wmp["representation_model"], inputs)
        logits = _unimix(logits, self.discrete, self.unimix)
        return logits, sample_stochastic(logits, self.discrete, key)

    def _prior_logits(self, wmp, recurrent_state) -> jax.Array:
        logits = self.transition_model.apply(wmp["transition_model"], recurrent_state)
        return _unimix(logits, self.discrete, self.unimix)

    def _transition(self, wmp, recurrent_out, key=None, sample_state: bool = True) -> Tuple[jax.Array, jax.Array]:
        logits = self._prior_logits(wmp, recurrent_out)
        return logits, sample_stochastic(logits, self.discrete, key, sample=sample_state)

    def _recurrent_step(self, wmp, posterior, recurrent_state, action, is_first, initial_states):
        """What both dynamic steps share, and all of a step that the next
        step's carry depends on: reset the rows that start an episode to the
        initial states, advance the recurrent state. The prior's logits are a
        function of the new recurrent state alone and feed no carry, so they
        are not formed here: a lone step reads them with ``_prior_logits``,
        ``dynamic_rollout`` once for all ``T`` steps after its loop.
        ``initial_states`` is ``get_initial_states``'s pair; a scan evaluates
        it once before the loop and passes it in (it does not depend on the
        step), a lone call may leave it ``None``."""
        # keep every mixed term in the carried state's dtype: under bf16
        # policies the float32 is_first mask / initial-state param would
        # otherwise promote the scan carry and break its type invariant
        dtype = recurrent_state.dtype
        is_first = is_first.astype(dtype)
        action = (1 - is_first) * action.astype(dtype)
        if initial_states is None:
            initial_states = self.get_initial_states(wmp, recurrent_state.shape[:-1])
        init_rec, init_post = initial_states
        recurrent_state = (1 - is_first) * recurrent_state + is_first * init_rec.astype(dtype)
        posterior = (1 - is_first) * posterior + is_first * init_post.astype(posterior.dtype)
        return self.recurrent_model.apply(
            wmp["recurrent_model"], jnp.concatenate([posterior, action], axis=-1), recurrent_state
        )

    def dynamic(
        self, wmp, posterior, recurrent_state, action, embedded_obs, is_first, key, initial_states=None
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """One dynamic-learning step (reference: ``agent.py:396-436``).
        All tensors are batch-shaped ``(B, ...)``; ``posterior`` flat."""
        recurrent_state = self._recurrent_step(wmp, posterior, recurrent_state, action, is_first, initial_states)
        posterior_logits, posterior = self._representation(wmp, recurrent_state, embedded_obs, key)
        return recurrent_state, posterior, posterior_logits, self._prior_logits(wmp, recurrent_state)

    def dynamic_decoupled(
        self, wmp, posterior, recurrent_state, action, is_first, initial_states=None
    ) -> Tuple[jax.Array, jax.Array]:
        """Decoupled dynamic step: the posterior is precomputed from the
        observations alone; only the recurrent state and the prior advance
        (reference DecoupledRSSM.dynamic, ``agent.py:542-581``)."""
        recurrent_state = self._recurrent_step(wmp, posterior, recurrent_state, action, is_first, initial_states)
        return recurrent_state, self._prior_logits(wmp, recurrent_state)

    def dynamic_rollout(self, wmp, embedded, actions, is_first, key):
        """The ``T``-step dynamic-learning rollout over ``(T, B, ...)`` inputs:
        recurrent states, posteriors, posterior and prior logits; the values
        and gradients of ``T`` chained ``dynamic`` steps. The scan's step
        computes only what the next step's carry depends on; what is a
        function of the scan's stacked inputs or outputs alone runs once, at
        ``T * B`` rows, outside it:

        - before the loop, the initial states, and the ``embedded`` half of the
          representation model's first ``Dense``: ``concat([rec, emb]) @ K`` is
          ``rec @ K[:H] + emb @ K[H:]``, and ``emb`` is an input of the scan;
        - after the loop, the transition model over the stacked recurrent
          states: the prior's logits are an output that no carry reads.

        The weight gradients of the ``Dense`` layers left in the step are
        formed after the backward loop
        (:func:`sheeprl_tpu.models.scan_grads.scan_dense_grads_after`). That
        function hoists a kernel's gradient only where the kernel is a leaf of
        the parameters it is handed and an ``nn.Dense`` applies it, so
        ``K[:H]`` is sliced here, outside, and goes in as the ``dense_0``
        kernel of the representation model applied to ``rec``: sliced inside
        the step, or applied by a bare product, its ``(H, D)`` gradient would
        be read and written by every step of the backward loop."""
        T, B = actions.shape[:2]
        dtype = embedded.dtype
        H = self.recurrent_model.recurrent_state_size
        rec0 = jnp.zeros((B, H), dtype=dtype)
        # what the step reads: only the models it applies, and the initial states as values
        params = {"wmp": {"recurrent_model": wmp["recurrent_model"]}, "initial": self.get_initial_states(wmp, (B,))}

        if self.decoupled:
            # posteriors come from the observations alone, computed in one
            # vectorized pass (reference: dreamer_v3.py:116-131)
            k_repr, key = jax.random.split(key)
            post_logits, posts = self._representation(wmp, None, embedded, k_repr)
            posts_prev = jnp.concatenate([jnp.zeros_like(posts[:1]), posts[:-1]], axis=0)

            def step_dec(p, rec, xs):
                post_prev, act_t, first_t = xs
                rec = self._recurrent_step(p["wmp"], post_prev, rec, act_t, first_t, p["initial"])
                return rec, rec

            _, recs = scan_dense_grads_after(step_dec, params, rec0, (posts_prev, actions, is_first))
            return recs, posts, post_logits, self._prior_logits(wmp, recs)

        representation = wmp["representation_model"]["params"]
        dense_0 = representation["model"]["dense_0"]
        kernel = dense_0["kernel"]  # (H + E, D): rows [:H] meet the recurrent state, rows [H:] the embedded observation
        # Dense's own product (operands in the module's dtype, default precision), accumulated in float32
        emb, k_emb = nn.dtypes.promote_dtype(embedded, kernel[H:], dtype=self.representation_model.dtype)
        pre = jnp.einsum("tbe,ed->tbd", emb, k_emb, preferred_element_type=jnp.float32)
        rec_half = {**representation["model"], "dense_0": {**dense_0, "kernel": kernel[:H]}}
        params["wmp"]["representation_model"] = {"params": {**representation, "model": rec_half}}
        post0 = jnp.zeros((B, self.transition_model.stoch_state_size), dtype=dtype)

        def step(p, carry, xs):
            rec, post = carry
            pre_t, act_t, first_t, k = xs
            rec = self._recurrent_step(p["wmp"], post, rec, act_t, first_t, p["initial"])
            post_logits = self.representation_model.apply(p["wmp"]["representation_model"], rec, addend=pre_t)
            post_logits = _unimix(post_logits, self.discrete, self.unimix)
            post = sample_stochastic(post_logits, self.discrete, k)
            return (rec, post), (rec, post, post_logits)

        xs = (pre, actions, is_first, jax.random.split(key, T))
        recs, posts, post_logits = scan_dense_grads_after(step, params, (rec0, post0), xs)[1]
        return recs, posts, post_logits, self._prior_logits(wmp, recs)

    def imagination(self, wmp, prior, recurrent_state, actions, key) -> Tuple[jax.Array, jax.Array]:
        """One latent imagination step (reference: ``agent.py:482-500``)."""
        recurrent_state = self.recurrent_model.apply(
            wmp["recurrent_model"], jnp.concatenate([prior, actions], axis=-1), recurrent_state
        )
        _, imagined_prior = self._transition(wmp, recurrent_state, key)
        return imagined_prior, recurrent_state


@dataclasses.dataclass(frozen=True)
class WorldModel:
    """Module bundle + RSSM; all learnables live in one ``world_model`` params
    tree with keys matching the module names below."""

    encoder: Encoder
    rssm: RSSM
    observation_model: Any  # dict {"cnn": CNNDecoder|None, "mlp": MLPDecoder|None}
    reward_model: _PredictionHead
    continue_model: _PredictionHead

    def decode(self, wmp, latent: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.observation_model["cnn"] is not None:
            out.update(self.observation_model["cnn"].apply(wmp["cnn_decoder"], latent))
        if self.observation_model["mlp"] is not None:
            out.update(self.observation_model["mlp"].apply(wmp["mlp_decoder"], latent))
        return out


class Actor(nn.Module):
    """Task actor emitting per-head logits (discrete) or mean/std parameters
    (continuous) (reference: ``agent.py:694-847``)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str  # "discrete" | "scaled_normal" | "normal" | "tanh_normal"
    dense_units: int = 1024
    mlp_layers: int = 5
    init_std: float = 0.0
    min_std: float = 0.1
    max_std: float = 1.0
    unimix: float = 0.01
    action_clip: float = 1.0
    dtype: Any = None

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation="silu",
            layer_norm=True,
            dtype=self.dtype,
            name="model",
        )(state)
        if self.is_continuous:
            return [nn.Dense(int(np.sum(self.actions_dim)) * 2, dtype=self.dtype, name="head_0")(x)]
        return [nn.Dense(int(d), dtype=self.dtype, name=f"head_{i}")(x) for i, d in enumerate(self.actions_dim)]


class MinedojoActor(Actor):
    """Mask-aware MineDojo actor: identical architecture, but sampling masks
    invalid action-type / craft / destroy / equip-place logits with ``-inf``
    (reference: ``agent.py:848-930``). The masking itself lives in
    :func:`actor_sample`, keyed on this class."""


def _unimix_logits(logits: jax.Array, amount: float) -> jax.Array:
    """Hafner's uniform-mix regularizer on categorical logits."""
    # `amount` is cfg.algo.unimix, a trace-time Python float — static branch
    if amount <= 0.0:  # graft-lint: disable=GL004
        return logits
    probs = jax.nn.softmax(logits, axis=-1)
    uniform = jnp.ones_like(probs) / probs.shape[-1]
    return jnp.log((1 - amount) * probs + amount * uniform)


def _mask_logits(logits: jax.Array, mask: jax.Array) -> jax.Array:
    """``-inf`` where the (broadcast) mask is invalid."""
    valid = jnp.broadcast_to(mask, logits.shape).astype(bool)
    return jnp.where(valid, logits, -jnp.inf)


def actor_dists(actor: Actor, pre_dist: List[jax.Array]):
    """Build the action distributions from the actor outputs."""
    from sheeprl_tpu.distributions import TanhNormal

    if actor.is_continuous:
        mean, std = jnp.split(pre_dist[0], 2, axis=-1)
        if actor.distribution == "scaled_normal":
            std = (actor.max_std - actor.min_std) * jax.nn.sigmoid(std + actor.init_std) + actor.min_std
            return [Independent(Normal(jnp.tanh(mean), std), 1)]
        if actor.distribution == "normal":
            return [Independent(Normal(mean, std), 1)]
        # tanh_normal: tanh-squashed Gaussian with the log-det-Jacobian in
        # log_prob (reference: agent.py:805-810)
        mean = 5 * jnp.tanh(mean / 5)
        std = jax.nn.softplus(std + actor.init_std) + actor.min_std
        return [Independent(TanhNormal(mean, std), 1)]

    return [
        OneHotCategoricalStraightThrough(logits=_unimix_logits(logits, actor.unimix))
        for logits in pre_dist
    ]


def _minedojo_masked_sample(
    actor: Actor, pre_dist: List[jax.Array], mask: Dict[str, jax.Array], key: jax.Array, greedy: bool
) -> Tuple[List[jax.Array], List[Any]]:
    """Sequential mask-aware sampling over the three MineDojo heads
    (reference: ``agent.py:902-926``, vectorized over the batch instead of the
    reference's per-element Python loops):

    - head 0 (action type): invalid types masked out directly;
    - head 1 (craft arg): masked with ``mask_craft_smelt`` only where head 0
      sampled the craft action (15);
    - head 2 (arg): masked with ``mask_equip_place`` where head 0 sampled
      equip/place (16/17) and ``mask_destroy`` where it sampled destroy (18).

    Unimix is applied *before* masking, as in the reference, so no uniform
    mass leaks back onto invalid actions.
    """
    logits = [_unimix_logits(lo, actor.unimix) for lo in pre_dist]
    keys = jax.random.split(key, len(logits))
    actions: List[jax.Array] = []
    dists: List[Any] = []

    def sample(dist, k):
        return dist.mode if greedy else dist.rsample(k)

    d0 = OneHotCategoricalStraightThrough(logits=_mask_logits(logits[0], mask["mask_action_type"]))
    a0 = sample(d0, keys[0])
    actions.append(a0)
    dists.append(d0)
    # (..., 1) so it broadcasts against the argument-head logits
    functional_action = jnp.argmax(a0, axis=-1, keepdims=True)

    if len(logits) > 1:
        crafting = functional_action == 15
        l1 = jnp.where(crafting, _mask_logits(logits[1], mask["mask_craft_smelt"]), logits[1])
        d1 = OneHotCategoricalStraightThrough(logits=l1)
        actions.append(sample(d1, keys[1]))
        dists.append(d1)
    if len(logits) > 2:
        equip_place = (functional_action == 16) | (functional_action == 17)
        destroy = functional_action == 18
        l2 = jnp.where(equip_place, _mask_logits(logits[2], mask["mask_equip_place"]), logits[2])
        l2 = jnp.where(destroy, _mask_logits(logits[2], mask["mask_destroy"]), l2)
        d2 = OneHotCategoricalStraightThrough(logits=l2)
        actions.append(sample(d2, keys[2]))
        dists.append(d2)
    return actions, dists


def extract_obs_masks(obs: Dict[str, jax.Array]) -> Optional[Dict[str, jax.Array]]:
    """Pull the ``mask_*`` observation keys the MineDojo wrapper emits
    (reference main loop: ``dreamer_v3.py:574-577``)."""
    mask = {k: v for k, v in obs.items() if k.startswith("mask")}
    return mask or None


def actor_sample(
    actor: Actor,
    actor_params,
    state: jax.Array,
    key: jax.Array,
    greedy: bool = False,
    mask: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[List[jax.Array], List[Any]]:
    """Sample (reparameterized / straight-through) actions from the actor
    (reference: ``agent.py:783-846``); mask-aware for :class:`MinedojoActor`."""
    pre_dist = actor.apply(actor_params, state)
    if mask is not None and isinstance(actor, MinedojoActor) and not actor.is_continuous:
        return _minedojo_masked_sample(actor, pre_dist, mask, key, greedy)
    dists = actor_dists(actor, pre_dist)
    actions: List[jax.Array] = []
    if actor.is_continuous:
        d = dists[0]
        act = d.mode if greedy else d.rsample(key)
        if actor.action_clip > 0.0:
            clip = jnp.full_like(act, actor.action_clip)
            act = act * jax.lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(act)))
        actions.append(act)
    else:
        keys = jax.random.split(key, len(dists))
        for d, k in zip(dists, keys):
            actions.append(d.mode if greedy else d.rsample(k))
    return actions, dists


class PlayerDV3:
    """Host-side stateful player carrying ``(actions, recurrent, stochastic)``
    per env (reference: ``agent.py:596-693``)."""

    def __init__(
        self,
        world_model: WorldModel,
        actor: Actor,
        actions_dim: Sequence[int],
        num_envs: int,
        stochastic_size: int,
        recurrent_state_size: int,
        discrete_size: int = 32,
        actor_type: Optional[str] = None,
        host_device=None,
    ):
        self.world_model = world_model
        self.actor = actor
        self.actions_dim = actions_dim
        self.num_envs = num_envs
        self.stochastic_size = stochastic_size
        self.recurrent_state_size = recurrent_state_size
        self.discrete_size = discrete_size
        self.actor_type = actor_type
        self.host_device = host_device
        self.is_continuous = actor.is_continuous
        self.actions = None
        self.recurrent_state = None
        self.stochastic_state = None

        rssm = world_model.rssm
        encoder = world_model.encoder

        def _init(params, n):
            rec, post = rssm.get_initial_states(params["world_model"], (n,))
            return rec, post

        def _step(params, obs, actions, rec, stoch, key, greedy):
            wmp = params["world_model"]
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
                greedy,
                mask=extract_obs_masks(obs),
            )
            return acts, jnp.concatenate(acts, axis=-1), rec, stoch

        self._init_fn = jax.jit(_init, static_argnums=(1,))
        self._step_fn = jax.jit(_step, static_argnums=(6,))
        self._reset_fn = _player_reset_fn(with_values=True)

    def init_states(self, params, reset_envs: Optional[Sequence[int]] = None) -> None:
        # The zero action rows must match _step_fn's output placement/type —
        # an ambient-mesh jnp.zeros is mesh-typed and would retrace the
        # (host) policy jit at every episode end (see utils.player_zeros).
        # _init_fn outputs already follow the committed params device.
        if reset_envs is None or len(reset_envs) == 0:
            self.actions = _player_zeros((self.num_envs, int(np.sum(self.actions_dim))), self.host_device)
            self.recurrent_state, self.stochastic_state = self._init_fn(params, self.num_envs)
        else:
            idx = np.asarray(list(reset_envs))
            rec, post = self._init_fn(params, len(reset_envs))
            self.actions, self.recurrent_state, self.stochastic_state = self._reset_fn(
                self.actions, self.recurrent_state, self.stochastic_state, idx, rec, post
            )

    def get_actions(self, params, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False, mask=None):
        acts, self.actions, self.recurrent_state, self.stochastic_state = self._step_fn(
            params, obs, self.actions, self.recurrent_state, self.stochastic_state, key, greedy
        )
        return acts


# -- initialization (reference: utils.py:141-188) ----------------------------


def _fan_in_out(shape: Sequence[int]) -> Tuple[float, float]:
    if len(shape) == 2:  # Dense kernel (in, out)
        return float(shape[0]), float(shape[1])
    # Conv kernel (kh, kw, in, out)
    space = float(np.prod(shape[:-2]))
    return space * shape[-2], space * shape[-1]


@jax.jit
def hafner_trunc_normal_init(params: Any, key: jax.Array) -> Any:
    """Re-initialize every Dense/Conv kernel with Hafner's truncated normal
    and zero every bias (reference ``init_weights``).

    Jitted: one program per parameter structure — the per-leaf eager path
    compiles a fresh tiny XLA program PER LEAF per process (~1-3 s each on a
    remote TPU backend, never persisted), minutes of pure startup."""
    leaves = jax.tree_util.tree_leaves_with_path(params)
    keys = jax.random.split(key, len(leaves))

    def init_leaf(path, leaf, k):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "kernel" and leaf.ndim >= 2:
            fan_in, fan_out = _fan_in_out(leaf.shape)
            scale = 1.0 / ((fan_in + fan_out) / 2.0)
            std = np.sqrt(scale) / 0.87962566103423978
            return std * jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, dtype=leaf.dtype)
        if name == "bias":
            return jnp.zeros_like(leaf)
        return leaf

    flat = {jax.tree_util.keystr(p): init_leaf(p, l, k) for (p, l), k in zip(leaves, keys)}
    return jax.tree_util.tree_map_with_path(lambda p, l: flat[jax.tree_util.keystr(p)], params)


@functools.partial(jax.jit, static_argnums=(2,))
def uniform_output_init(params: Any, key: jax.Array, given_scale: float) -> Any:
    """Re-initialize Dense kernels in a (sub)tree with Hafner's scaled
    uniform (reference ``uniform_init_weights``). Jitted — see
    :func:`hafner_trunc_normal_init`."""
    leaves = jax.tree_util.tree_leaves_with_path(params)
    keys = jax.random.split(key, len(leaves))

    def init_leaf(path, leaf, k):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "kernel" and leaf.ndim >= 2:
            fan_in, fan_out = _fan_in_out(leaf.shape)
            scale = given_scale / ((fan_in + fan_out) / 2.0)
            limit = np.sqrt(3 * scale)
            return jax.random.uniform(k, leaf.shape, dtype=leaf.dtype, minval=-limit, maxval=limit)
        if name == "bias":
            return jnp.zeros_like(leaf)
        return leaf

    flat = {jax.tree_util.keystr(p): init_leaf(p, l, k) for (p, l), k in zip(leaves, keys)}
    return jax.tree_util.tree_map_with_path(lambda p, l: flat[jax.tree_util.keystr(p)], params)


def build_agent(
    fabric,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    world_model_state: Optional[Dict[str, Any]] = None,
    actor_state: Optional[Dict[str, Any]] = None,
    critic_state: Optional[Dict[str, Any]] = None,
    target_critic_state: Optional[Dict[str, Any]] = None,
) -> Tuple[WorldModel, Actor, _PredictionHead, Dict[str, Any], PlayerDV3]:
    """Create modules + the params tree ``{world_model, actor, critic,
    target_critic}`` (reference: ``agent.py:935-1236``)."""
    wm_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic
    dtype = fabric.precision.compute_dtype

    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    latent_state_size = stoch_state_size + recurrent_state_size

    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_stages = int(np.log2(cfg.env.screen_size) - np.log2(4))
    screen = int(cfg.env.screen_size)
    cnn_channels = [int(np.prod(obs_space[k].shape[2:] or (1,))) for k in cnn_keys]  # NHWC channels
    mlp_dims = [int(np.prod(obs_space[k].shape)) for k in mlp_keys]
    cnn_encoder_output_dim = (
        (2 ** (cnn_stages - 1)) * int(wm_cfg.encoder.cnn_channels_multiplier) * 4 * 4 if cnn_keys else 0
    )

    encoder = Encoder(
        cnn_keys=tuple(cnn_keys),
        mlp_keys=tuple(mlp_keys),
        cnn_channels_multiplier=int(wm_cfg.encoder.cnn_channels_multiplier),
        mlp_layers=int(wm_cfg.encoder.mlp_layers),
        dense_units=int(wm_cfg.encoder.dense_units),
        stages=cnn_stages,
        dtype=dtype,
    )
    encoder_output_dim = (cnn_encoder_output_dim if cnn_keys else 0) + (
        int(wm_cfg.encoder.dense_units) if mlp_keys else 0
    )

    recurrent_model = RecurrentModel(
        recurrent_state_size=recurrent_state_size,
        dense_units=int(wm_cfg.recurrent_model.dense_units),
        dtype=dtype,
    )
    representation_model = _StochHead(
        hidden_size=int(wm_cfg.representation_model.hidden_size), stoch_state_size=stoch_state_size, dtype=dtype
    )
    transition_model = _StochHead(
        hidden_size=int(wm_cfg.transition_model.hidden_size), stoch_state_size=stoch_state_size, dtype=dtype
    )
    decoupled_rssm = bool(wm_cfg.decoupled_rssm)
    rssm = RSSM(
        recurrent_model=recurrent_model,
        representation_model=representation_model,
        transition_model=transition_model,
        discrete=discrete_size,
        unimix=float(cfg.algo.unimix),
        decoupled=decoupled_rssm,
        learnable_initial_state=bool(wm_cfg.learnable_initial_recurrent_state),
    )
    cnn_decoder = (
        CNNDecoder(
            keys=tuple(cfg.algo.cnn_keys.decoder),
            output_channels=tuple(cnn_channels),
            channels_multiplier=int(wm_cfg.observation_model.cnn_channels_multiplier),
            cnn_encoder_output_dim=cnn_encoder_output_dim,
            stages=cnn_stages,
            dtype=dtype,
        )
        if cfg.algo.cnn_keys.decoder
        else None
    )
    mlp_decoder = (
        MLPDecoder(
            keys=tuple(cfg.algo.mlp_keys.decoder),
            output_dims=tuple(mlp_dims),
            mlp_layers=int(wm_cfg.observation_model.mlp_layers),
            dense_units=int(wm_cfg.observation_model.dense_units),
            dtype=dtype,
        )
        if cfg.algo.mlp_keys.decoder
        else None
    )
    reward_model = _PredictionHead(
        output_dim=int(wm_cfg.reward_model.bins),
        mlp_layers=int(wm_cfg.reward_model.mlp_layers),
        dense_units=int(wm_cfg.reward_model.dense_units),
        dtype=dtype,
    )
    continue_model = _PredictionHead(
        output_dim=1,
        mlp_layers=int(wm_cfg.discount_model.mlp_layers),
        dense_units=int(wm_cfg.discount_model.dense_units),
        dtype=dtype,
    )
    world_model = WorldModel(
        encoder=encoder,
        rssm=rssm,
        observation_model={"cnn": cnn_decoder, "mlp": mlp_decoder},
        reward_model=reward_model,
        continue_model=continue_model,
    )

    # ``algo.actor.cls`` picks the sampling behaviour (reference instantiates
    # the hydra target at agent.py:1133-1137); both classes live in this module.
    actor_cls = (
        MinedojoActor
        if str(actor_cfg.get("cls", "") or "").rsplit(".", 1)[-1] == "MinedojoActor"
        else Actor
    )
    actor = actor_cls(
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=is_continuous,
        distribution=(
            cfg.distribution.get("type", "auto").lower()
            if cfg.distribution.get("type", "auto").lower() != "auto"
            else ("scaled_normal" if is_continuous else "discrete")
        ),
        dense_units=int(actor_cfg.dense_units),
        mlp_layers=int(actor_cfg.mlp_layers),
        init_std=float(actor_cfg.init_std),
        min_std=float(actor_cfg.min_std),
        max_std=float(actor_cfg.get("max_std", 1.0)),
        unimix=float(cfg.algo.unimix),
        action_clip=float(actor_cfg.action_clip),
        dtype=dtype,
    )
    critic = _PredictionHead(
        output_dim=int(critic_cfg.bins),
        mlp_layers=int(critic_cfg.mlp_layers),
        dense_units=int(critic_cfg.dense_units),
        dtype=dtype,
    )

    # -- init ----------------------------------------------------------------
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), 12)
    dummy_obs = {}
    for k, ch in zip(cnn_keys, cnn_channels):
        dummy_obs[k] = jnp.zeros((1, screen, screen, ch), dtype=jnp.float32)
    for k, d in zip(mlp_keys, mlp_dims):
        dummy_obs[k] = jnp.zeros((1, d), dtype=jnp.float32)
    dummy_latent = jnp.zeros((1, latent_state_size), dtype=jnp.float32)
    dummy_rec = jnp.zeros((1, recurrent_state_size), dtype=jnp.float32)

    wmp: Dict[str, Any] = {
        "encoder": encoder.init(keys[0], dummy_obs),
        "recurrent_model": recurrent_model.init(
            keys[1], jnp.zeros((1, stoch_state_size + int(np.sum(actions_dim))), dtype=jnp.float32), dummy_rec
        ),
        "representation_model": representation_model.init(
            keys[2],
            jnp.zeros(
                (1, encoder_output_dim + (0 if decoupled_rssm else recurrent_state_size)), dtype=jnp.float32
            ),
        ),
        "transition_model": transition_model.init(keys[3], dummy_rec),
        "reward_model": reward_model.init(keys[4], dummy_latent),
        "continue_model": continue_model.init(keys[5], dummy_latent),
        "initial_recurrent_state": jnp.zeros((recurrent_state_size,), dtype=jnp.float32),
    }
    if cnn_decoder is not None:
        wmp["cnn_decoder"] = cnn_decoder.init(keys[6], dummy_latent)
    if mlp_decoder is not None:
        wmp["mlp_decoder"] = mlp_decoder.init(keys[7], dummy_latent)
    actor_params = actor.init(keys[8], dummy_latent)
    critic_params = critic.init(keys[9], dummy_latent)

    if cfg.algo.hafner_initialization:
        init_keys = jax.random.split(keys[10], 12)
        for i, name in enumerate(
            ["encoder", "recurrent_model", "representation_model", "transition_model", "reward_model", "continue_model"]
        ):
            wmp[name] = hafner_trunc_normal_init(wmp[name], init_keys[i])
        if cnn_decoder is not None:
            wmp["cnn_decoder"] = hafner_trunc_normal_init(wmp["cnn_decoder"], init_keys[6])
        if mlp_decoder is not None:
            wmp["mlp_decoder"] = hafner_trunc_normal_init(wmp["mlp_decoder"], init_keys[7])
        actor_params = hafner_trunc_normal_init(actor_params, init_keys[8])
        critic_params = hafner_trunc_normal_init(critic_params, init_keys[9])

        # scaled-uniform output heads (reference: agent.py:1170-1180)
        u_keys = jax.random.split(keys[11], 10)
        p = wmp["transition_model"]["params"]
        p["out"] = uniform_output_init({"out": p["out"]}, u_keys[0], 1.0)["out"]
        p = wmp["representation_model"]["params"]
        p["out"] = uniform_output_init({"out": p["out"]}, u_keys[1], 1.0)["out"]
        p = wmp["reward_model"]["params"]
        p["out"] = uniform_output_init({"out": p["out"]}, u_keys[2], 0.0)["out"]
        p = wmp["continue_model"]["params"]
        p["out"] = uniform_output_init({"out": p["out"]}, u_keys[3], 1.0)["out"]
        cp = critic_params["params"]
        cp["out"] = uniform_output_init({"out": cp["out"]}, u_keys[4], 0.0)["out"]
        ap = actor_params["params"]
        for i, hk in enumerate([k for k in ap.keys() if k.startswith("head_")]):
            ap[hk] = uniform_output_init({hk: ap[hk]}, u_keys[5 + i % 5], 1.0)[hk]
        if mlp_decoder is not None:
            dp = wmp["mlp_decoder"]["params"]
            for i, hk in enumerate([k for k in dp.keys() if k.startswith("head_")]):
                dp[hk] = uniform_output_init({hk: dp[hk]}, u_keys[5 + i % 5], 1.0)[hk]
        if cnn_decoder is not None:
            dp = wmp["cnn_decoder"]["params"]
            dp["out"] = uniform_output_init({"out": dp["out"]}, u_keys[9], 1.0)["out"]

    params = {
        "world_model": wmp,
        "actor": actor_params,
        "critic": critic_params,
    }
    if world_model_state is not None:
        params["world_model"] = jax.tree.map(
            lambda t, s: jnp.asarray(s, dtype=t.dtype), params["world_model"], world_model_state
        )
    if actor_state is not None:
        params["actor"] = jax.tree.map(lambda t, s: jnp.asarray(s, dtype=t.dtype), params["actor"], actor_state)
    if critic_state is not None:
        params["critic"] = jax.tree.map(lambda t, s: jnp.asarray(s, dtype=t.dtype), params["critic"], critic_state)
    params["target_critic"] = (
        jax.tree.map(lambda t, s: jnp.asarray(s, dtype=t.dtype), params["critic"], target_critic_state)
        if target_critic_state is not None
        else jax.tree.map(jnp.copy, params["critic"])
    )
    params = fabric.put_replicated(params)

    player = PlayerDV3(
        world_model,
        actor,
        actions_dim,
        cfg.env.num_envs,
        stochastic_size,
        recurrent_state_size,
        discrete_size=discrete_size,
    )
    return world_model, actor, critic, params, player
