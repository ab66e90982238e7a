"""Attention ops: single-device reference + the blockwise/online-softmax
pieces the sequence-parallel schedules (``parallel/sequence.py``) are built
from.

The reference framework has no attention anywhere (SURVEY §5: GRU/LSTM
temporal models only) — these ops exist so the framework handles the same
scale a modern long-context world model needs (e.g. a transformer RSSM à la
TransDreamer): sequences sharded over an ``sp`` mesh axis instead of
device-local windows.

Layout: ``(batch, seq, heads, head_dim)`` throughout — the TPU-friendly
layout where the contraction dims land on the MXU and ``seq`` is shardable.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["reference_attention", "block_attention", "online_softmax_merge"]


def reference_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False, scale: Optional[float] = None
) -> jax.Array:
    """Plain softmax attention, the numerical ground truth for the parallel
    schedules. Shapes ``(B, T, H, D)``."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T_q, T_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((T_q, T_k), dtype=bool), k=T_k - T_q)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def block_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array,
    k_offset: jax.Array,
    causal: bool,
    scale: float,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One (q-block, kv-block) step of blockwise attention.

    Returns the un-normalized accumulator pieces for online-softmax merging:
    ``(out_block, row_max, row_sum)`` with ``out_block = exp(s - m) @ v``.
    ``q_offset``/``k_offset`` are the blocks' global sequence positions, so a
    causal mask stays correct when blocks travel around a ring. A ``window``
    (with ``causal``) also hides keys more than ``window - 1`` positions
    behind the query: the window counts the query's own position.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # (B, H, Tq, Tk)
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)  # (B, H, Tq)
    # fully-masked rows produce m = -inf; exp(-inf - -inf) would be nan
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out, m_safe, jnp.sum(p, axis=-1)


def online_softmax_merge(
    acc: Tuple[jax.Array, jax.Array, jax.Array],
    blk: Tuple[jax.Array, jax.Array, jax.Array],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge a new block's un-normalized ``(out, max, sum)`` into the running
    accumulator — the flash-attention streaming-softmax update."""
    out_a, m_a, l_a = acc
    out_b, m_b, l_b = blk
    m = jnp.maximum(m_a, m_b)
    alpha = jnp.exp(m_a - m)
    beta = jnp.exp(m_b - m)
    out = out_a * _bh_to_bqh(alpha) + out_b * _bh_to_bqh(beta)
    return out, m, l_a * alpha + l_b * beta


def _bh_to_bqh(x: jax.Array) -> jax.Array:
    """(B, H, Tq) → (B, Tq, H, 1) broadcast helper."""
    return jnp.transpose(x, (0, 2, 1))[..., None]
