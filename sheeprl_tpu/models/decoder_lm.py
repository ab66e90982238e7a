"""A decoder-only language model with routed experts and mixed attention, as
plain functions over a parameter dict.

One layer, for input ``x`` (tokens x hidden), RMSNorm without bias:

- ``h = norm_in(x)``; grouped-query attention on ``h`` (``heads`` query heads
  over ``kv_heads`` key-value heads), rotary position encoding where
  ``rope_layout[l]`` is 1 and none where 0, a ``window``-token sliding window
  where ``window_layout[l]`` is 1 and full causal attention where 0;
  ``x' = x + attention``;
- the router reads ``h`` too (it sits before attention): ``experts`` logits,
  the ``top_k`` largest kept, their weights the softmax over those;
- ``y = x' + sum_e w_e E_e(norm_post(x'))`` with ReGLU experts
  ``E_e(u) = W_down (relu(W_gate u) * (W_up u))``.

**The layer is told which experts it holds** (``experts_held`` of them from
``expert_offset``): it routes over all ``experts``, computes its own experts'
part and leaves the rest out, which is one chip's share under expert
parallelism; the sum over the shares is the whole layer. It moves the rows it
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
The cache holds **two kinds of state side by side**: a ``window``-slot ring
for each window layer (position ``p`` lives in slot ``p % window``; keys are
cached already rotated, so the ring's order does not matter) and a
full-length buffer for each global layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.kernels.attn import window_attention
from sheeprl_tpu.ops.kernels.moe import GMM_ROW_TILE, moe_grouped_ffn

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

    @classmethod
    def from_config(cls, lm: Any) -> "DecoderConfig":
        """From the ``algo.lm`` group, whose keys are the published config's."""
        layers = int(lm.num_hidden_layers)
        experts = int(lm.moe_num_primary_experts)
        held = int(lm.experts_held or experts)
        offset = int(lm.expert_offset or 0)
        if offset + held > experts:
            raise ValueError(f"experts {offset}..{offset + held - 1} held of {experts}")
        if not (lm.moe_primary_router_apply_softmax and lm.norm_topk_prob):
            raise ValueError("only the softmax-over-the-kept-experts router is written down")
        return cls(
            hidden=int(lm.hidden_size), heads=int(lm.num_attention_heads), kv_heads=int(lm.num_key_value_heads),
            head_dim=int(lm.head_dim), layers=layers, experts=experts, top_k=int(lm.moe_num_active_primary_experts),
            expert_width=int(lm.moe_ffn_hidden_size), experts_held=held, expert_offset=offset,
            vocab_held=int(lm.vocab_held or lm.vocab_size), window=int(lm.sliding_window_size),
            rope_theta=float(lm.rope_theta), eps=float(lm.rms_norm_eps),
            rope_layout=tuple(int(x) for x in lm.rope_layout[:layers]),
            window_layout=tuple(int(x) for x in lm.sliding_window_layout[:layers]), remat=bool(lm.remat),
        )


def init_params(cfg: DecoderConfig, key: jax.Array, std: float = 0.02) -> Dict[str, Any]:
    def normal(key, *shape):
        return std * jax.random.normal(key, shape, jnp.float32)

    keys = iter(jax.random.split(key, 4 + 8 * cfg.layers))
    H, Q, KV, F, E = cfg.hidden, cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim, cfg.expert_width, cfg.experts_held
    layers = []
    for _ in range(cfg.layers):
        layers.append({
            "ln_in": jnp.ones((H,), jnp.float32), "wq": normal(next(keys), H, Q), "wk": normal(next(keys), H, KV),
            "wv": normal(next(keys), H, KV), "wo": normal(next(keys), Q, H), "router": normal(next(keys), H, cfg.experts),
            "ln_post": jnp.ones((H,), jnp.float32), "w_gate": normal(next(keys), E, H, F),
            "w_up": normal(next(keys), E, H, F), "w_down": normal(next(keys), E, F, H),
        })
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


def _share_rows(rows, experts, u, weights, routing):
    """The share over the first ``rows`` rows of the sorted order: gather,
    grouped products, combine. ``weights`` and ``held`` are ``(K, N)``; an
    assignment sorted past ``rows`` reads the last row under a weight of 0."""
    order, inverse, group_sizes, held = routing
    picked = order[:rows]
    index = jnp.minimum(inverse, rows - 1).reshape(weights.shape)
    xs = _gather_sorted(u, picked % u.shape[0], index, held)
    ys = moe_grouped_ffn(xs, experts["w_gate"], experts["w_up"], experts["w_down"], group_sizes)
    return _combine(ys, weights, picked, index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _share_head_or_all(rows, fits, experts, u, weights, routing):
    """:func:`_share_rows` over the first ``rows`` rows where the assignments
    held fit them (``fits``), over every row where they do not. One ``cond``
    forward and one backward, each branch's backward formed from its own
    forward: a ``cond`` differentiated as it stands hands its backward the
    residuals of both branches, the untaken one's as zeros of full size."""
    every = routing[0].shape[0]
    return jax.lax.cond(fits, functools.partial(_share_rows, rows), functools.partial(_share_rows, every),
                        experts, u, weights, routing)


def _share_head_or_all_fwd(rows, fits, experts, u, weights, routing):
    return _share_head_or_all(rows, fits, experts, u, weights, routing), (fits, experts, u, weights, routing)


def _share_head_or_all_bwd(rows, res, g):
    fits, experts, u, weights, routing = res

    def backward(rows):
        return lambda experts, u, weights, g: jax.vjp(
            lambda experts, u, weights: _share_rows(rows, experts, u, weights, routing), experts, u, weights)[1](g)

    grads = jax.lax.cond(fits, backward(rows), backward(routing[0].shape[0]), experts, u, weights, g)
    return (None, *grads, None)


_share_head_or_all.defvjp(_share_head_or_all_fwd, _share_head_or_all_bwd)


def route(cfg: DecoderConfig, h, router):
    """``(weights, experts)`` of the ``top_k`` kept of all ``experts``, both ``(N, top_k)``."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    top, experts = jax.lax.top_k(logits, cfg.top_k)
    return jax.nn.softmax(top, axis=-1), experts


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
        out, handed = _share_head_or_all(rows, fits, *args), jnp.where(fits, rows, N * K)
    else:  # every row is moved anyway: no branch, and no call that could compact
        fits = jnp.bool_(False)
        out, handed = _share_rows(rows, *args), rows
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


def layer_forward(cfg: DecoderConfig, index: int, layer, x):
    """``x`` (B, T, hidden) through layer ``index``; also the layer's rotated
    keys and its values (what a cache holds) and the routing counters."""
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


def forward(cfg: DecoderConfig, params, tokens, return_kv: bool = False):
    """Full-sequence forward of ``tokens`` (B, T): the hidden states before the
    final norm, the routing counters per layer ``(layers, 5)`` and, if asked,
    each layer's ``(k, v)``. With ``cfg.remat`` each layer is rematerialised
    in the backward pass."""
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


# -- the two-kind cache -------------------------------------------------------
def _cache_len(cfg: DecoderConfig, index: int, max_len: int) -> int:
    return min(cfg.window, max_len) if cfg.window_layout[index] else max_len


def prefill(cfg: DecoderConfig, params, tokens, max_len: int):
    """Full-sequence forward of the prompts ``tokens`` (B, P) that also fills
    the cache for sequences of up to ``max_len`` positions: per layer ``(k,
    v)`` of shape ``(B, slots, kv_heads, D)``, ``slots`` the window for a
    window layer (a ring) and ``max_len`` for a global one. Returns the last
    position's hidden state, the cache and the counters."""
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


def decode_step(cfg: DecoderConfig, params, cache, token, position):
    """One token per sequence: ``token`` (B,) at ``position`` (a traced
    scalar, the same for all) through every layer, reading and writing the
    cache. Plain ``jax.numpy`` over the cache: the work is reading the weights
    and the cached keys once. Returns the hidden state (B, hidden), the new
    cache and the counters."""
    with jax.named_scope("lm.embed"):
        x = params["embed"][token]  # (B, H)
    B = x.shape[0]
    groups = cfg.heads // cfg.kv_heads
    new_cache, counters = [], []
    for i, (layer, (ck, cv)) in enumerate(zip(params["layers"], cache)):
        windowed = bool(cfg.window_layout[i])
        slots = ck.shape[1]
        with jax.named_scope("lm.moe"):
            h = rms_norm(x, layer["ln_in"], cfg.eps)
            weights, experts = route(cfg, h, layer["router"])
        with jax.named_scope("lm.attn_window" if windowed else "lm.attn_global"):
            q, k, v = _qkv(cfg, layer, h[:, None], position[None], cfg.rope_layout[i])
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
        y, c = _experts_block(cfg, layer, x[:, None], weights, experts)
        x = y[:, 0]
        new_cache.append((ck, cv))
        counters.append(jnp.stack(c))
    return x, new_cache, jnp.stack(counters)
