"""Decoder-only language models with routed experts, as plain functions over a
parameter dict. One module and one :class:`DecoderConfig` hold two published
layers; which one a layer is comes from the config's static kinds
(``attention``, ``router``, ``activation``, ``dense_layers``,
``shared_width``), never from the parameters.

**Grouped-query layers with the router first** (``attention="gqa"``,
``router="softmax"``, ``activation="relu"``; SmallThinker). For input ``x``
(tokens x hidden), RMSNorm without bias:

- ``h = norm_in(x)``; grouped-query attention on ``h`` (``heads`` query heads
  over ``kv_heads`` key-value heads), rotary position encoding where
  ``rope_layout[l]`` is 1 and none where 0, a ``window``-token sliding window
  where ``window_layout[l]`` is 1 and full causal attention where 0;
  ``x' = x + attention``;
- the router reads ``h`` too (it sits before attention): ``experts`` logits,
  the ``top_k`` largest kept, their weights the softmax over those;
- ``y = x' + sum_e w_e E_e(norm_post(x'))`` with ReGLU experts
  ``E_e(u) = W_down (relu(W_gate u) * (W_up u))``.

**Latent-attention layers with the router after attention**
(``attention="mla"``, ``router="sigmoid_bias"``, ``activation="silu"``; the
``deepseek_v3`` layer without low-rank queries):

- ``h = norm_in(x)``; ``q = h Wq`` is ``heads`` x ``[nope_dim | rope_dim]``;
  one down-projection a token ``[c | r] = h Wkva`` (``latent`` wide, normed,
  and ``rope_dim`` rotary, shared by every head); rotary encoding over pairs
  ``(2i, 2i + 1)`` on the queries' rotary part and on ``r``. **Two paths
  through one attention, which agree**: a full sequence (:func:`forward`:
  prefill and the update) *expands* the latent, ``[k_n | v] = c Wkvb`` per
  head, and runs ``window_attention`` on keys ``[k_n | r]`` of ``nope_dim +
  rope_dim`` and values of ``v_dim``; one-token decode reads the cache in the
  *absorbed* form: the key half of ``Wkvb`` folded into the query
  (``qa_h = q_n,h Wuk_h^T``), scores ``qa_h . c_j + q_r,h . r_j`` against the
  cached latents themselves, the value half applied after the weighted sum of
  latents. Scores are scaled by ``(nope_dim + rope_dim) ** -0.5`` in both;
- ``u = norm_post(x')``; the first ``dense_layers`` layers are one gated MLP
  of ``dense_width``; the others route on ``u``: sigmoid scores, the ``top_k``
  of ``score + bias`` kept, weights the *unbiased* scores normalised over the
  kept and scaled by ``routed_scale``; besides the routed part every token
  passes through the shared experts (one SwiGLU of ``shared_width``).
  **The selection bias (``router_bias``) is a leaf the optimizer sees under a
  zero update**: it reaches the result only through ``top_k``'s indices and
  under ``stop_gradient``, so its gradient is exactly zero, Adam's moments of
  it stay zero and the update ``-lr * 0 / (0 + eps)`` leaves it bit-identical
  (asserted in tier-1 and, on the chip, by the cell's ``router_bias_change``).

**The layer is told which experts it holds** (``experts_held`` of them from
``expert_offset``): it routes over all ``experts``, computes its own experts'
part and leaves the rest out, which is one chip's share under expert
parallelism; the sum over the shares, with the shared experts and the dense
layers counted once, is the whole layer. It moves the rows it
holds, not every assignment: assignments are numbered slot-major (``k * N +
n``), those to experts elsewhere sort last, and only the head of the sorted
order (:func:`compact_rows`: twice the mean share, in whole row tiles of the
grouped product) is gathered, multiplied and combined. Nothing is dropped: a
call whose experts hold more than the head takes every row, and the grouped
product takes whatever load the router gives (the layer counts the assignments
to its experts against the rows it hands the product, :func:`moe_share`).
``vocab_held`` rows of the embedding and columns of the head are held the
same way.

Two modes: a full-sequence forward (:func:`forward`; prefill and the update)
and one-token decode through a cache (:func:`prefill`, :func:`decode_step`).
The cache holds **three kinds of state**, whichever the layers need, side by
side: a ``window``-slot ring of ``(k, v)`` per key-value head for each window
layer (position ``p`` lives in slot ``p % window``; keys are cached already
rotated, so the ring's order does not matter), a full-length ``(k, v)`` buffer
for each global grouped-query layer, and for each latent layer the normed
latent ``(B, S, latent)`` with the rotated shared key ``(B, S, rope_dim)``: no
head axis, 576 numbers a position where the expanded keys and values are
``heads * (nope_dim + rope_dim + v_dim)``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.kernels.attn import window_attention
from sheeprl_tpu.ops.kernels.moe import ACTIVATIONS, GMM_ROW_TILE, moe_grouped_ffn

__all__ = ["DecoderConfig", "init_params", "forward", "prefill", "decode_step", "heads", "parameter_count"]


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    experts: int  # the router's width: every expert of the deployment
    top_k: int
    expert_width: int
    experts_held: int
    expert_offset: int
    vocab_held: int
    window: int
    rope_theta: float
    eps: float
    rope_layout: Tuple[int, ...]
    window_layout: Tuple[int, ...]
    remat: bool = True
    # the layer's kinds (module docstring); the defaults are the grouped-query, router-first layer
    attention: str = "gqa"  # or "mla": ``head_dim`` is then ``nope_dim + rope_dim``, ``kv_heads`` ``heads``
    router: str = "softmax"  # or "sigmoid_bias"
    activation: str = "relu"  # the gated MLPs' gate, experts and dense alike: ``ops.kernels.moe.ACTIVATIONS``
    dense_layers: int = 0  # leading layers whose feed-forward is one dense gated MLP of ``dense_width``
    dense_width: int = 0
    shared_width: int = 0  # the shared experts as one gated MLP beside the routed part (0: none)
    routed_scale: float = 1.0
    latent: int = 0  # latent attention's widths
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0

    @property
    def selection_bias(self) -> bool:
        return self.router == "sigmoid_bias"

    @property
    def counters(self) -> int:
        """Numbers a layer counts of its routed part: :func:`moe_share`'s five
        and, with a selection bias, the assignments the bias moved."""
        return 6 if self.selection_bias else 5

    @classmethod
    def from_config(cls, lm: Any) -> "DecoderConfig":
        """From the ``algo.lm`` group, whose keys are one published
        config.json's: a group with ``kv_lora_rank`` is the latent-attention
        layer's, any other the grouped-query layer's."""
        if "kv_lora_rank" in lm:
            return cls._latent_from_config(lm)
        layers = int(lm.num_hidden_layers)
        experts = int(lm.moe_num_primary_experts)
        held = int(lm.experts_held or experts)
        offset = int(lm.expert_offset or 0)
        if offset + held > experts:
            raise ValueError(f"experts {offset}..{offset + held - 1} held of {experts}")
        if not (lm.moe_primary_router_apply_softmax and lm.norm_topk_prob):
            raise ValueError("with the router before attention only the softmax over the kept experts is written down "
                             "(moe_primary_router_apply_softmax and norm_topk_prob); the sigmoid router with a "
                             "selection bias is the latent-attention layer's")
        return cls(
            hidden=int(lm.hidden_size), heads=int(lm.num_attention_heads), kv_heads=int(lm.num_key_value_heads),
            head_dim=int(lm.head_dim), layers=layers, experts=experts, top_k=int(lm.moe_num_active_primary_experts),
            expert_width=int(lm.moe_ffn_hidden_size), experts_held=held, expert_offset=offset,
            vocab_held=int(lm.vocab_held or lm.vocab_size), window=int(lm.sliding_window_size),
            rope_theta=float(lm.rope_theta), eps=float(lm.rms_norm_eps),
            rope_layout=tuple(int(x) for x in lm.rope_layout[:layers]),
            window_layout=tuple(int(x) for x in lm.sliding_window_layout[:layers]), remat=bool(lm.remat),
        )

    @classmethod
    def _latent_from_config(cls, lm: Any) -> "DecoderConfig":
        """The ``deepseek_v3`` keys. What the module does not compute raises."""
        unwritten = {
            "q_lora_rank": lm.q_lora_rank is not None, "rope_scaling": lm.rope_scaling is not None,
            "n_group / topk_group": (int(lm.n_group), int(lm.topk_group)) != (1, 1),
            "scoring_func": lm.scoring_func != "sigmoid", "topk_method": lm.topk_method != "noaux_tc",
            "norm_topk_prob": not lm.norm_topk_prob, "hidden_act": lm.hidden_act != "silu",
            "moe_layer_freq": int(lm.moe_layer_freq) != 1, "attention_bias": bool(lm.attention_bias),
            "rope_interleave": not lm.rope_interleave,
            "num_key_value_heads": int(lm.num_key_value_heads) != int(lm.num_attention_heads),
            "qk_head_dim": int(lm.qk_head_dim) != int(lm.qk_nope_head_dim) + int(lm.qk_rope_head_dim),
        }
        if any(unwritten.values()):
            raise ValueError(
                f"not written down for the latent-attention layer: {sorted(k for k, v in unwritten.items() if v)} "
                "(written down: full-rank queries, no rotary scaling, one selection group, sigmoid scores with the "
                "noaux_tc bias normalised over the kept, SiLU gates, every layer past the dense ones routed, "
                "interleaved rotary pairs, a key-value head a query head)")
        layers, experts = int(lm.num_hidden_layers), int(lm.n_routed_experts)
        held, offset = int(lm.experts_held or experts), int(lm.expert_offset or 0)
        if offset + held > experts:
            raise ValueError(f"experts {offset}..{offset + held - 1} held of {experts}")
        nope, rotary = int(lm.qk_nope_head_dim), int(lm.qk_rope_head_dim)
        return cls(
            hidden=int(lm.hidden_size), heads=int(lm.num_attention_heads), kv_heads=int(lm.num_attention_heads),
            head_dim=nope + rotary, layers=layers, experts=experts, top_k=int(lm.num_experts_per_tok),
            expert_width=int(lm.moe_intermediate_size), experts_held=held, expert_offset=offset,
            vocab_held=int(lm.vocab_held or lm.vocab_size), window=0, rope_theta=float(lm.rope_theta),
            eps=float(lm.rms_norm_eps), rope_layout=(1,) * layers, window_layout=(0,) * layers, remat=bool(lm.remat),
            attention="mla", router="sigmoid_bias", activation="silu",
            dense_layers=min(int(lm.first_k_dense_replace), layers), dense_width=int(lm.intermediate_size),
            shared_width=int(lm.n_shared_experts) * int(lm.moe_intermediate_size),
            routed_scale=float(lm.routed_scaling_factor), latent=int(lm.kv_lora_rank), nope_dim=nope, rope_dim=rotary,
            v_dim=int(lm.v_head_dim),
        )


def init_params(cfg: DecoderConfig, key: jax.Array, std: float = 0.02) -> Dict[str, Any]:
    def normal(key, *shape):
        return std * jax.random.normal(key, shape, jnp.float32)

    latent = cfg.attention == "mla"
    keys = iter(jax.random.split(key, 4 + (12 if latent else 8) * cfg.layers))
    H, Q, KV, F, E = cfg.hidden, cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim, cfg.expert_width, cfg.experts_held
    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731

    def grouped_query_layer():
        return {
            "ln_in": ones(H), "wq": normal(next(keys), H, Q), "wk": normal(next(keys), H, KV),
            "wv": normal(next(keys), H, KV), "wo": normal(next(keys), Q, H), "router": normal(next(keys), H, cfg.experts),
            "ln_post": ones(H), "w_gate": normal(next(keys), E, H, F),
            "w_up": normal(next(keys), E, H, F), "w_down": normal(next(keys), E, F, H),
        }

    def latent_layer(index):
        layer = {
            "ln_in": ones(H), "wq": normal(next(keys), H, Q), "wkva": normal(next(keys), H, cfg.latent + cfg.rope_dim),
            "ln_kv": ones(cfg.latent), "wkvb": normal(next(keys), cfg.latent, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
            "wo": normal(next(keys), cfg.heads * cfg.v_dim, H), "ln_post": ones(H),
        }
        if index < cfg.dense_layers:
            D = cfg.dense_width
            return {**layer, "dense_gate": normal(next(keys), H, D), "dense_up": normal(next(keys), H, D),
                    "dense_down": normal(next(keys), D, H)}
        S = cfg.shared_width
        return {  # a run from scratch starts without a selection bias (a published model ships its own)
            **layer, "router": normal(next(keys), H, cfg.experts), "router_bias": jnp.zeros((cfg.experts,), jnp.float32),
            "shared_gate": normal(next(keys), H, S), "shared_up": normal(next(keys), H, S),
            "shared_down": normal(next(keys), S, H), "w_gate": normal(next(keys), E, H, F),
            "w_up": normal(next(keys), E, H, F), "w_down": normal(next(keys), E, F, H),
        }

    layers = [latent_layer(i) if latent else grouped_query_layer() for i in range(cfg.layers)]
    return {
        "embed": normal(next(keys), cfg.vocab_held, H), "layers": layers, "ln_f": jnp.ones((H,), jnp.float32),
        "head": normal(next(keys), H, cfg.vocab_held), "value_w": normal(next(keys), H, 1),
        "value_b": jnp.zeros((1,), jnp.float32),
    }


def parameter_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotate-half rotary encoding over the whole head; ``x`` is
    ``(..., T, heads, D)``, ``positions`` ``(T,)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rope_pairs(x, positions, theta):
    """Rotary encoding over the pairs ``(2i, 2i + 1)`` of the last axis
    (``rope_interleave``), returned with the pairs' first members in the
    first half and their second members in the second: queries and keys go
    through the same reordering, so every score is the interleaved one's."""
    return rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1), positions, theta)


# -- the routed layer's share -------------------------------------------------
# An assignment is numbered slot-major: ``j = k * N + n`` is token ``n``'s ``k``-th expert, so everything per
# assignment is ``K`` contiguous ``(N, ...)`` slabs and a sum over a token's experts is a sum over slabs.
def compact_rows(cfg: DecoderConfig, tokens: int) -> int:
    """How many rows of the sorted assignments the routed share moves for
    ``tokens`` tokens: twice the mean number that land on the experts held,
    rounded up to the grouped product's row tile, and never more than there
    are assignments (an uncut layer, or a call of less than a tile: all)."""
    made = tokens * cfg.top_k
    twice_mean = -(-2 * made * cfg.experts_held // cfg.experts)
    return min(made, -(-twice_mean // GMM_ROW_TILE) * GMM_ROW_TILE)


@jax.custom_vjp
def _gather_sorted(u, tokens, index, held):
    """Row ``tokens[i]`` of ``u`` for every row ``i`` of the sorted buffer.
    The backward pass is a gather too (through ``index``, ``(K, N)``: each
    assignment's row of the sorted buffer), not the scatter-add that
    differentiating the gather would give; an assignment that is not ``held``
    has no row, and reads one under a zero."""
    return u[tokens]


def _gather_sorted_fwd(u, tokens, index, held):
    return _gather_sorted(u, tokens, index, held), (index, held)


def _gather_sorted_bwd(res, g):
    index, held = res
    return jnp.sum(jnp.where(held[..., None], g[index], 0.0), axis=0), None, None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _combine(ys, weights, picked, index):
    """``sum_k weights[k, n] * ys[index[k, n]]``: each token's experts'
    outputs back in the token's place, weighted and summed over the ``K``
    slabs. Gathers in the backward pass too."""
    return jnp.sum(weights[..., None] * ys[index], axis=0)


def _combine_fwd(ys, weights, picked, index):
    return _combine(ys, weights, picked, index), (ys, weights, picked, index)


def _combine_bwd(res, g):
    ys, weights, picked, index = res
    d_ys = weights.reshape(-1)[picked][:, None] * g[picked % g.shape[0]]
    return d_ys, jnp.sum(ys[index] * g[None], axis=-1), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _share_rows(rows, activation, experts, u, weights, routing):
    """The share over the first ``rows`` rows of the sorted order: gather,
    grouped products, combine. ``weights`` and ``held`` are ``(K, N)``; an
    assignment sorted past ``rows`` reads the last row under a weight of 0."""
    order, inverse, group_sizes, held = routing
    picked = order[:rows]
    index = jnp.minimum(inverse, rows - 1).reshape(weights.shape)
    xs = _gather_sorted(u, picked % u.shape[0], index, held)
    ys = moe_grouped_ffn(xs, experts["w_gate"], experts["w_up"], experts["w_down"], group_sizes, activation)
    return _combine(ys, weights, picked, index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _share_head_or_all(rows, activation, fits, experts, u, weights, routing):
    """:func:`_share_rows` over the first ``rows`` rows where the assignments
    held fit them (``fits``), over every row where they do not. One ``cond``
    forward and one backward, each branch's backward formed from its own
    forward: a ``cond`` differentiated as it stands hands its backward the
    residuals of both branches, the untaken one's as zeros of full size."""
    every = routing[0].shape[0]
    return jax.lax.cond(fits, functools.partial(_share_rows, rows, activation),
                        functools.partial(_share_rows, every, activation), experts, u, weights, routing)


def _share_head_or_all_fwd(rows, activation, fits, experts, u, weights, routing):
    return _share_head_or_all(rows, activation, fits, experts, u, weights, routing), (fits, experts, u, weights, routing)


def _share_head_or_all_bwd(rows, activation, res, g):
    fits, experts, u, weights, routing = res

    def backward(rows):
        return lambda experts, u, weights, g: jax.vjp(
            lambda experts, u, weights: _share_rows(rows, activation, experts, u, weights, routing), experts, u, weights)[1](g)

    grads = jax.lax.cond(fits, backward(rows), backward(routing[0].shape[0]), experts, u, weights, g)
    return (None, *grads, None)


_share_head_or_all.defvjp(_share_head_or_all_fwd, _share_head_or_all_bwd)


def _route(cfg: DecoderConfig, h, router, bias=None):
    """``(weights, experts, moved)``: :func:`route` and, under a selection
    bias, how many of the kept the unbiased scores would not have kept (those
    with ``top_k`` or more unbiased scores strictly above their own; 0
    without a bias)."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    if cfg.router == "softmax":
        top, experts = jax.lax.top_k(logits, cfg.top_k)
        return jax.nn.softmax(top, axis=-1), experts, jnp.int32(0)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), cfg.top_k)
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    weights = cfg.routed_scale * kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    above = jnp.sum(scores[:, None, :] > kept[:, :, None], axis=-1)  # (N, top_k): a compare and a count, no second sort
    return weights, experts, jnp.sum(above >= cfg.top_k, dtype=jnp.int32)


def route(cfg: DecoderConfig, h, router, bias=None):
    """``(weights, experts)`` of the ``top_k`` kept of all ``experts``, both
    ``(N, top_k)``. ``router="softmax"``: the largest logits, weighted by the
    softmax over them. ``"sigmoid_bias"``: the largest of ``sigmoid(logit) +
    bias``, weighted by the unbiased scores normalised over the kept and
    scaled by ``routed_scale``; the bias decides who is kept and no weight,
    and no gradient reaches it."""
    return _route(cfg, h, router, bias)[:2]


def moe_share(cfg: DecoderConfig, layer, u, weights, experts):
    """This chip's part of the routed layer for tokens ``u`` (N, hidden): the
    assignments that land on the experts held, sorted by expert, through the
    grouped feed-forward and back. Assignments to experts elsewhere sort last,
    and only the first :func:`compact_rows` rows of the order are moved; a
    call whose experts hold more than that takes the same arithmetic over
    every row, so nothing is dropped. Returns the partial sum and the counters
    ``(assignments held here, the largest expert's load, assignments dropped,
    whether the call compacted, whether it could)``: the third is the router's
    assignments to the experts held less the rows of the sorted buffer that
    the grouped product is handed as some expert's (a capacity would make it
    positive; there is none); the last is static, 0 where every row is moved
    anyway and there is no branch."""
    N, K, E = u.shape[0], cfg.top_k, cfg.experts_held
    local = (experts - cfg.expert_offset).T  # (K, N): slot-major from here on
    held = (local >= 0) & (local < E)
    slot = jnp.where(held, local, E).reshape(-1)  # assignments elsewhere sort last
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(N * K, dtype=jnp.int32))
    group_sizes = jnp.sum(slot[:, None] == jnp.arange(E)[None, :], axis=0, dtype=jnp.int32)
    rows = compact_rows(cfg, N)
    routed = jnp.sum(group_sizes)
    args = ({k: layer[k] for k in ("w_gate", "w_up", "w_down")}, u, jnp.where(held, weights.T, 0.0),
            (order, inverse, group_sizes, held))
    could = rows < N * K
    if could:
        fits = routed <= rows
        out, handed = _share_head_or_all(rows, cfg.activation, fits, *args), jnp.where(fits, rows, N * K)
    else:  # every row is moved anyway: no branch, and no call that could compact
        fits = jnp.bool_(False)
        out, handed = _share_rows(rows, cfg.activation, *args), rows
    computed = jnp.minimum(routed, handed)
    return out, (computed, jnp.max(group_sizes), jnp.sum(held, dtype=jnp.int32) - computed, fits.astype(jnp.int32),
                 jnp.int32(could))


# -- one layer, full sequence -------------------------------------------------
def _qkv(cfg: DecoderConfig, layer, h, positions, use_rope):
    lead = h.shape[:-1]
    q = jnp.dot(h, layer["wq"]).reshape(*lead, cfg.heads, cfg.head_dim)
    k = jnp.dot(h, layer["wk"]).reshape(*lead, cfg.kv_heads, cfg.head_dim)
    v = jnp.dot(h, layer["wv"]).reshape(*lead, cfg.kv_heads, cfg.head_dim)
    if use_rope:
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
    return q, k, v


def _experts_block(cfg: DecoderConfig, layer, x, weights, experts):
    B, T, H = x.shape
    with jax.named_scope("lm.moe"):
        u = rms_norm(x, layer["ln_post"], cfg.eps).reshape(B * T, H)
        out, counters = moe_share(cfg, layer, u, weights, experts)
    return x + out.reshape(B, T, H), counters


def _grouped_query_layer(cfg: DecoderConfig, index: int, layer, x):
    B, T, H = x.shape
    windowed = bool(cfg.window_layout[index])
    with jax.named_scope("lm.moe"):
        h = rms_norm(x, layer["ln_in"], cfg.eps)
        weights, experts = route(cfg, h.reshape(B * T, H), layer["router"])
    with jax.named_scope("lm.attn_window" if windowed else "lm.attn_global"):
        q, k, v = _qkv(cfg, layer, h, jnp.arange(T), cfg.rope_layout[index])
        o = window_attention(q, k, v, cfg.window if windowed else 0)
        x = x + jnp.dot(o.reshape(B, T, -1), layer["wo"])
    x, counters = _experts_block(cfg, layer, x, weights, experts)
    return x, (k, v), counters


def _latent_projections(cfg: DecoderConfig, layer, x, positions):
    """What both paths of latent attention start from, for ``x`` ``(B, T,
    hidden)``: the queries' two parts ``(B, T, heads, nope_dim)`` and (rotated)
    ``(B, T, heads, rope_dim)``, the normed latent ``(B, T, latent)`` and the
    rotated key all heads share ``(B, T, rope_dim)``."""
    h = rms_norm(x, layer["ln_in"], cfg.eps)
    q = jnp.dot(h, layer["wq"]).reshape(*h.shape[:-1], cfg.heads, cfg.head_dim)
    down = jnp.dot(h, layer["wkva"])
    c = rms_norm(down[..., : cfg.latent], layer["ln_kv"], cfg.eps)
    q_r = rope_pairs(q[..., cfg.nope_dim :], positions, cfg.rope_theta)
    r = rope_pairs(down[..., None, cfg.latent :], positions, cfg.rope_theta)[..., 0, :]
    return q[..., : cfg.nope_dim], q_r, c, r


def _gated_mlp(cfg: DecoderConfig, u, w_gate, w_up, w_down):
    return jnp.dot(ACTIVATIONS[cfg.activation](jnp.dot(u, w_gate)) * jnp.dot(u, w_up), w_down)


def _latent_feed_forward(cfg: DecoderConfig, index: int, layer, x, count_moved: bool = True):
    """The feed-forward half of a latent-attention layer for ``x`` ``(B, T,
    hidden)`` after attention: the dense MLP in a leading layer (its counters
    are zeros), else the routed share and the shared experts on the same
    normed input."""
    B, T, H = x.shape
    if index < cfg.dense_layers:
        with jax.named_scope("lm.ffn_shared"):
            u = rms_norm(x, layer["ln_post"], cfg.eps)
            y = _gated_mlp(cfg, u, layer["dense_gate"], layer["dense_up"], layer["dense_down"])
        return x + y, (jnp.int32(0),) * cfg.counters
    with jax.named_scope("lm.moe"):
        u = rms_norm(x, layer["ln_post"], cfg.eps).reshape(B * T, H)
        weights, experts, moved = _route(cfg, u, layer["router"], layer["router_bias"])
        routed, counters = moe_share(cfg, layer, u, weights, experts)
    with jax.named_scope("lm.ffn_shared"):
        shared = _gated_mlp(cfg, u, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return x + (routed + shared).reshape(B, T, H), (*counters, moved if count_moved else jnp.int32(0))


def _latent_layer(cfg: DecoderConfig, index: int, layer, x):
    """The expanded path: keys and values of every head materialised from the
    latent, through the registry's attention."""
    B, T, _ = x.shape
    with jax.named_scope("lm.attn_mla"):
        q_n, q_r, c, r = _latent_projections(cfg, layer, x, jnp.arange(T))
        kv = jnp.dot(c, layer["wkvb"]).reshape(B, T, cfg.heads, cfg.nope_dim + cfg.v_dim)
        k = jnp.concatenate([kv[..., : cfg.nope_dim], jnp.broadcast_to(r[:, :, None], (B, T, cfg.heads, cfg.rope_dim))], axis=-1)
        o = window_attention(jnp.concatenate([q_n, q_r], axis=-1), k, kv[..., cfg.nope_dim :], 0)
        x = x + jnp.dot(o.reshape(B, T, -1), layer["wo"])
    x, counters = _latent_feed_forward(cfg, index, layer, x)
    return x, (c, r), counters


def layer_forward(cfg: DecoderConfig, index: int, layer, x):
    """``x`` (B, T, hidden) through layer ``index``; also what a cache holds
    of it (the rotated keys and the values, or the normed latent and the
    rotated shared key) and the routing counters."""
    return (_latent_layer if cfg.attention == "mla" else _grouped_query_layer)(cfg, index, layer, x)


def forward(cfg: DecoderConfig, params, tokens, return_kv: bool = False):
    """Full-sequence forward of ``tokens`` (B, T): the hidden states before the
    final norm, the routing counters per layer ``(layers, cfg.counters)`` (a
    dense layer's row is zeros) and, if asked, each layer's cache state. With
    ``cfg.remat`` each layer is rematerialised in the backward pass."""
    with jax.named_scope("lm.embed"):
        x = params["embed"][tokens]
    kvs, counters = [], []
    for i, layer in enumerate(params["layers"]):
        fn = lambda layer, x, _i=i: layer_forward(cfg, _i, layer, x)  # noqa: E731
        if cfg.remat and not return_kv:
            fn = jax.checkpoint(fn)
        x, kv, c = fn(layer, x)
        kvs.append(kv)
        counters.append(jnp.stack(c))
    return x, jnp.stack(counters), (kvs if return_kv else None)


def heads(cfg: DecoderConfig, params, x):
    """Final norm, then logits over the held vocabulary and the value."""
    h = rms_norm(x, params["ln_f"], cfg.eps)
    logits = jnp.dot(h, params["head"], preferred_element_type=jnp.float32)
    value = jnp.dot(h, params["value_w"])[..., 0] + params["value_b"][0]
    return logits, value


# -- the cache ----------------------------------------------------------------
def _cache_len(cfg: DecoderConfig, index: int, max_len: int) -> int:
    return min(cfg.window, max_len) if cfg.window_layout[index] else max_len


def prefill(cfg: DecoderConfig, params, tokens, max_len: int):
    """Full-sequence forward of the prompts ``tokens`` (B, P) that also fills
    the cache for sequences of up to ``max_len`` positions: per layer a pair
    of ``(B, slots, ...)`` arrays, ``(k, v)`` with a ``(kv_heads, D)`` tail
    for a grouped-query layer (``slots`` the window for a window layer, a
    ring, and ``max_len`` for a global one), the latent ``(latent,)`` and the
    shared key ``(rope_dim,)`` for a latent layer (``max_len`` slots). Returns
    the last position's hidden state, the cache and the counters."""
    P = tokens.shape[1]
    x, counters, kvs = forward(cfg, params, tokens, return_kv=True)
    cache = []
    for i, (k, v) in enumerate(kvs):
        slots = _cache_len(cfg, i, max_len)
        kept = min(P, slots)  # a ring keeps the prompt's last `slots` positions
        where = jnp.arange(P - kept, P) % slots

        def fill(a):
            buf = jnp.zeros((a.shape[0], slots) + a.shape[2:], a.dtype)
            return buf.at[:, where].set(a[:, P - kept :])

        cache.append((fill(k), fill(v)))
    return x[:, -1], cache, counters


def _grouped_query_decode(cfg: DecoderConfig, index: int, layer, state, x, position):
    ck, cv = state
    B = x.shape[0]
    groups = cfg.heads // cfg.kv_heads
    windowed = bool(cfg.window_layout[index])
    slots = ck.shape[1]
    with jax.named_scope("lm.moe"):
        h = rms_norm(x, layer["ln_in"], cfg.eps)
        weights, experts = route(cfg, h, layer["router"])
    with jax.named_scope("lm.attn_window" if windowed else "lm.attn_global"):
        q, k, v = _qkv(cfg, layer, h[:, None], position[None], cfg.rope_layout[index])
        slot = position % slots
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, slot, axis=1)
        # slot s holds position: the latest p <= position with p % slots == s
        held = position - (slot - jnp.arange(slots)) % slots
        qg = q.reshape(B, cfg.kv_heads, groups, cfg.head_dim)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, ck) * cfg.head_dim**-0.5
        s = jnp.where((held >= 0)[None, None, None, :], s, -jnp.inf)
        o = jnp.einsum("bhgs,bshd->bhgd", jax.nn.softmax(s, axis=-1), cv)
        x = x + jnp.dot(o.reshape(B, -1), layer["wo"])
    y, counters = _experts_block(cfg, layer, x[:, None], weights, experts)
    return y[:, 0], (ck, cv), counters


def _latent_decode(cfg: DecoderConfig, index: int, layer, state, x, position):
    """The absorbed path: the new token's latent and shared key written at its
    slot, then every head's scores and weighted sum taken against the cached
    latents themselves; the up-projection's key half goes into the query and
    its value half onto the sum, so no key or value of a cached position is
    ever formed."""
    latents, shared = state  # (B, S, latent), (B, S, rope_dim)
    B = x.shape[0]
    with jax.named_scope("lm.attn_mla"):
        q_n, q_r, c, r = _latent_projections(cfg, layer, x[:, None], position[None])
        latents = jax.lax.dynamic_update_slice_in_dim(latents, c, position, axis=1)
        shared = jax.lax.dynamic_update_slice_in_dim(shared, r, position, axis=1)
        up = layer["wkvb"].reshape(cfg.latent, cfg.heads, cfg.nope_dim + cfg.v_dim)
        absorbed = jnp.einsum("bhd,chd->bhc", q_n[:, 0], up[..., : cfg.nope_dim])
        s = (jnp.einsum("bhc,bsc->bhs", absorbed, latents) + jnp.einsum("bhr,bsr->bhs", q_r[:, 0], shared))
        s = jnp.where((jnp.arange(latents.shape[1]) <= position)[None, None, :], s * cfg.head_dim**-0.5, -jnp.inf)
        mixed = jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, axis=-1), latents)
        o = jnp.einsum("bhc,chd->bhd", mixed, up[..., cfg.nope_dim :])
        x = x + jnp.dot(o.reshape(B, -1), layer["wo"])
    y, counters = _latent_feed_forward(cfg, index, layer, x[:, None], count_moved=False)
    return y[:, 0], (latents, shared), counters


def decode_step(cfg: DecoderConfig, params, cache, token, position):
    """One token per sequence: ``token`` (B,) at ``position`` (a traced
    scalar, the same for all) through every layer, reading and writing the
    cache. Plain ``jax.numpy`` over the cache: the work is reading the weights
    and the cached state once. Returns the hidden state (B, hidden), the new
    cache and the counters."""
    with jax.named_scope("lm.embed"):
        x = params["embed"][token]  # (B, H)
    step = _latent_decode if cfg.attention == "mla" else _grouped_query_decode
    new_cache, counters = [], []
    for i, (layer, state) in enumerate(zip(params["layers"], cache)):
        x, state, c = step(cfg, i, layer, state, x, position)
        new_cache.append(state)
        counters.append(jnp.stack(c))
    return x, new_cache, jnp.stack(counters)
