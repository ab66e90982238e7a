"""Grouped expert feed-forward: the routed layer's three matrix products over
rows already sorted by expert.

``moe_grouped_ffn(xs, w_gate, w_up, w_down, group_sizes, activation)``: ``xs`` is
``(M, hidden)`` with the rows of expert 0 first, then expert 1, ... (``E``
experts held, ``group_sizes[e]`` rows each); rows past ``sum(group_sizes)``
belong to no expert held here and come back as zeros. ``M`` is whatever the
caller sorted and cut: every assignment of its tokens, or the head of them
that its experts' rows fit (``sum(group_sizes) <= M`` is the caller's to
see to; a multiple of ``GMM_ROW_TILE`` tiles evenly). Each expert is the gated
``W_down (act(W_gate u) * (W_up u))`` with ``activation`` (static) ``"relu"``
(ReGLU, the default) or ``"silu"`` (SwiGLU). No capacity: a group may hold
every row or none.

- reference tier: ``lax.ragged_dot`` (plain lax, differentiable as it is);
- kernel tier: the grouped matrix product that ships in jax
  (``pallas.ops.tpu.megablox.gmm``, forward and its ``tgmm`` backward), on
  bfloat16 operands with float32 accumulation, which is what XLA's default
  precision does to float32 operands on the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.kernels import registry

__all__ = ["moe_grouped_ffn", "moe_grouped_ffn_reference", "moe_grouped_ffn_pallas", "GMM_ROW_TILE", "ACTIVATIONS"]

#: the gate's activation, by the name a caller gives
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}

# (rows, contraction, columns) tiles of the grouped product at most this large
GMM_TILING = (512, 1024, 1024)
#: a caller that hands the product fewer rows than it has assignments cuts them to a multiple of this
GMM_ROW_TILE = GMM_TILING[0]
# contraction x columns of one tile at most this many: the backward's transposed product holds its float32
# accumulator and two bfloat16 output buffers of that area beside its float32 gradient rows, and 1024 x 1024 is
# over the kernel's 16 MiB of scoped VMEM (experts of 1024 on a hidden of 2048)
GMM_TILE_AREA = 1024 * 768


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``; the
    whole of a smaller or indivisible ``dim``."""
    best = next((t for t in range(cap - cap % 128, 0, -128) if dim % t == 0), None)
    return best if best else min(dim, cap)


def _zero_rows_past(y: jax.Array, group_sizes: jax.Array) -> jax.Array:
    rows = jax.lax.broadcasted_iota(jnp.int32, (y.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), y, jnp.zeros((), y.dtype))


def _ffn(product, xs, w_gate, w_up, w_down, group_sizes, activation="relu"):
    """The three grouped products of the gated experts. A grouped product
    leaves the rows past the last group unwritten on the chip, forward and
    backward alike: they are zeroed on the way in and on the way out of each
    product, so that the same holds for every gradient."""
    group_sizes = group_sizes.astype(jnp.int32)

    def guarded(lhs, rhs):
        return _zero_rows_past(product(_zero_rows_past(lhs, group_sizes), rhs, group_sizes), group_sizes)

    hidden = ACTIVATIONS[activation](guarded(xs, w_gate)) * guarded(xs, w_up)
    return guarded(hidden, w_down).astype(xs.dtype)


def moe_grouped_ffn_reference(xs, w_gate, w_up, w_down, group_sizes, activation="relu"):
    return _ffn(jax.lax.ragged_dot, xs, w_gate, w_up, w_down, group_sizes, activation)


def gmm_tiling(m: int, k: int, n: int):
    """The grouped product's ``(rows, contraction, columns)`` tiles for ``(m,
    k) x (k, n)``: each as large as ``GMM_TILING`` allows and divides its
    dimension, the columns' narrowed until a tile's area is at most
    ``GMM_TILE_AREA``."""
    tk = _tile(k, GMM_TILING[1])
    return min(GMM_TILING[0], m), tk, _tile(n, min(GMM_TILING[2], max(128, GMM_TILE_AREA // tk)))


def _gmm(lhs, rhs, group_sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tiling = gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2])
    return megablox.gmm(
        lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), group_sizes, jnp.float32, tiling, interpret=interpret
    )


def _grouped_ffn_gmm(xs, w_gate, w_up, w_down, group_sizes, activation="relu", interpret=False):
    return _ffn(functools.partial(_gmm, interpret=interpret), xs, w_gate, w_up, w_down, group_sizes, activation)


def moe_grouped_ffn_pallas(xs, w_gate, w_up, w_down, group_sizes, activation="relu"):
    return registry.platform_dispatch(
        functools.partial(_grouped_ffn_gmm, activation=activation),
        functools.partial(moe_grouped_ffn_reference, activation=activation), xs, w_gate, w_up, w_down, group_sizes
    )


registry.register(
    "moe_grouped_ffn",
    reference=moe_grouped_ffn_reference,
    pallas=moe_grouped_ffn_pallas,
    doc="gated experts (ReGLU or SwiGLU) over rows sorted by expert: three grouped matrix products, no dropped row",
)


def moe_grouped_ffn(xs, w_gate, w_up, w_down, group_sizes, activation="relu"):
    return registry.dispatch("moe_grouped_ffn")(xs, w_gate, w_up, w_down, group_sizes, activation)
