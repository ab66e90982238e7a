"""Sequence/context parallelism over an ``sp`` mesh axis.

Two schedules, both operating on activations whose SEQUENCE dimension is
sharded across devices (layout ``(B, T, H, D)``, ``T`` sharded on ``sp``):

- :func:`ring_attention` — blockwise attention with the KV shard rotating
  around the ring via ``lax.ppermute`` and an online-softmax accumulator
  (Liu et al., Ring Attention; the flash-attention streaming update lives in
  ``ops/attention.py``). Communication is overlap-friendly nearest-neighbor
  ICI traffic; memory per device stays O(T/n).
- :func:`ulysses_attention` — all-to-all sequence↔head reshard (DeepSpeed
  Ulysses): each device attends over the FULL sequence for ``H/n`` heads,
  then reshards back. Two ``all_to_all`` collectives per call; requires
  ``heads % n == 0``.

Both are pure functions of already-sharded arrays designed to be called
INSIDE a ``shard_map`` whose in/out specs shard ``T`` (ring) or used through
the convenience wrappers :func:`make_ring_attention` /
:func:`make_ulysses_attention` that build the ``shard_map`` for a mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from sheeprl_tpu.ops.attention import block_attention, online_softmax_merge, _bh_to_bqh

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "make_ring_attention",
    "make_ulysses_attention",
]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over ``axis_name``; call inside ``shard_map`` with the
    sequence dim of q/k/v sharded on that axis."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    q_offset = idx * t_local

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        out, m, l, kv = carry
        k_blk, v_blk = kv
        # the kv block currently held came from device (idx - step) mod n
        k_offset = ((idx - step) % n) * t_local
        blk = block_attention(q, k_blk, v_blk, q_offset, k_offset, causal, scale)
        out, m, l = online_softmax_merge((out, m, l), blk)
        kv = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
        return out, m, l, kv

    B, T, H, D = q.shape
    out0 = jnp.zeros((B, T, H, D), dtype=jnp.float32)
    m0 = jnp.full((B, H, T), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, T), dtype=jnp.float32)
    out, m, l, _ = jax.lax.fori_loop(0, n, body, (out0, m0, l0, (k, v)))
    return (out / jnp.maximum(_bh_to_bqh(l), 1e-38)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ulysses all-to-all attention over ``axis_name``; call inside
    ``shard_map`` with the sequence dim sharded on that axis."""
    from sheeprl_tpu.ops.attention import reference_attention

    n = axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads ({q.shape[2]}) must be divisible by the sp axis size ({n})")

    def seq_to_heads(x):  # (B, T/n, H, D) -> (B, T, H/n, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # (B, T, H/n, D) -> (B, T/n, H, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = reference_attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v), causal=causal, scale=scale)
    return heads_to_seq(out)


def _make(fn, mesh: Mesh, axis_name: str, causal: bool, scale: Optional[float]):
    mapped = shard_map(
        partial(fn, axis_name=axis_name, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    return jax.jit(mapped)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp", causal: bool = False, scale: Optional[float] = None):
    """Jitted ring attention over ``mesh``: takes global ``(B, T, H, D)``
    arrays with ``T`` sharded on ``axis_name``."""
    return _make(ring_attention, mesh, axis_name, causal, scale)


def make_ulysses_attention(mesh: Mesh, axis_name: str = "sp", causal: bool = False, scale: Optional[float] = None):
    """Jitted Ulysses attention over ``mesh`` (see :func:`make_ring_attention`)."""
    return _make(ulysses_attention, mesh, axis_name, causal, scale)
