"""Fused two-hot/symlog kernels for the Dreamer return/reward heads
(``distributions.TwoHotEncodingDistribution``; reference torch path:
``sheeprl/utils/distribution.py:224-277``).

Two kernels cover the distribution's hot methods:

- :func:`two_hot_symlog_loss` — ``log_prob`` under the default
  ``symlog``/``symexp`` transforms: symlog-encode the target, two-hot it
  over the bucket support, and contract with the (already log-normalized)
  logits, all in ONE VPU pass per row block. The inline jnp version
  materializes two ``(..., K)`` one-hot matmuls plus half a dozen ``(..., K)``
  comparison intermediates per loss; the kernel keeps everything for a row
  in registers/VMEM and writes a single scalar per row.
- :func:`two_hot_symexp_decode` — ``mean``: softmax over the buckets,
  expectation against the bin support, symexp back to reward space.

The lax references are literal extractions of the distribution's inline
math, so ``ops.backend=lax`` reproduces the historical graphs bit-for-bit.
In-kernel the bin support is rebuilt from a broadcasted iota (1D iota does
not lower on TPU); this matches ``jnp.linspace`` up to 1 ulp, which only
matters for values landing *exactly* on a bin edge — and there the two-hot
weights are continuous, so the result still agrees to float tolerance.

Gradients: ``jax.custom_vjp`` with the Pallas kernel on the forward and the
reference chain re-derived on the backward. Interpret mode only in a process
with no TPU, as everywhere in the kernel tier.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.core import symexp, symlog
from sheeprl_tpu.ops.kernels import registry

__all__ = [
    "two_hot_symlog_loss",
    "two_hot_symlog_loss_reference",
    "two_hot_symexp_decode",
    "two_hot_symexp_decode_reference",
]


def two_hot_symlog_loss_reference(
    logits: jax.Array, value: jax.Array, low: float = -20.0, high: float = 20.0
) -> jax.Array:
    """``TwoHotEncodingDistribution.log_prob`` for the default transforms,
    extracted verbatim: ``logits`` are the distribution's log-normalized
    logits ``(..., K)``, ``value`` the raw-space target ``(..., 1)``."""
    x = symlog(value)
    num_buckets = logits.shape[-1]
    bins = jnp.linspace(low, high, num_buckets, dtype=logits.dtype)
    below = jnp.sum((bins <= x).astype(jnp.int32), axis=-1, keepdims=True) - 1
    above = num_buckets - jnp.sum((bins > x).astype(jnp.int32), axis=-1, keepdims=True)
    below = jnp.clip(below, 0, num_buckets - 1)
    above = jnp.clip(above, 0, num_buckets - 1)
    equal = below == above
    dist_to_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x))
    dist_to_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x))
    total = dist_to_below + dist_to_above
    weight_below = dist_to_above / total
    weight_above = dist_to_below / total
    target = (
        jax.nn.one_hot(below[..., 0], num_buckets, dtype=logits.dtype) * weight_below
        + jax.nn.one_hot(above[..., 0], num_buckets, dtype=logits.dtype) * weight_above
    )
    return jnp.sum(target * logits, axis=-1)


def two_hot_symexp_decode_reference(
    logits: jax.Array, low: float = -20.0, high: float = 20.0
) -> jax.Array:
    """``TwoHotEncodingDistribution.mean`` for the default transforms:
    softmax expectation over the bin support, symexp'd back, ``(..., 1)``."""
    probs = jax.nn.softmax(logits, axis=-1)
    bins = jnp.linspace(low, high, logits.shape[-1], dtype=logits.dtype)
    return symexp(jnp.sum(probs * bins, axis=-1, keepdims=True))


def _bins_iota(num_buckets: int, low: float, high: float):
    """Bin support as a ``(1, K)`` f32 row from a 2D iota (TPU-safe)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, num_buckets), 1)
    step = (high - low) / (num_buckets - 1) if num_buckets > 1 else 0.0
    return iota, low + iota.astype(jnp.float32) * step


def _pick(iota, idx, table):
    """``table[idx]`` per row without a gather: mask-select over the bucket
    axis (``iota (1, K)``, ``idx (bn, 1)``, ``table (bn_or_1, K)``)."""
    return jnp.sum(jnp.where(iota == idx, table, 0.0), axis=-1, keepdims=True)


def _loss_kernel(logits_ref, value_ref, out_ref, *, low, high):
    num_buckets = logits_ref.shape[-1]
    logits = logits_ref[...].astype(jnp.float32)
    value = value_ref[...].astype(jnp.float32)
    x = jnp.sign(value) * jnp.log1p(jnp.abs(value))  # symlog
    iota, bins = _bins_iota(num_buckets, low, high)
    below = jnp.sum((bins <= x).astype(jnp.int32), axis=-1, keepdims=True) - 1
    above = num_buckets - jnp.sum((bins > x).astype(jnp.int32), axis=-1, keepdims=True)
    below = jnp.clip(below, 0, num_buckets - 1)
    above = jnp.clip(above, 0, num_buckets - 1)
    equal = below == above
    dist_to_below = jnp.where(equal, 1.0, jnp.abs(_pick(iota, below, bins) - x))
    dist_to_above = jnp.where(equal, 1.0, jnp.abs(_pick(iota, above, bins) - x))
    total = dist_to_below + dist_to_above
    weight_below = dist_to_above / total
    weight_above = dist_to_below / total
    out = weight_below * _pick(iota, below, logits) + weight_above * _pick(iota, above, logits)
    out_ref[...] = out.astype(out_ref.dtype)


def _decode_kernel(logits_ref, out_ref, *, low, high):
    num_buckets = logits_ref.shape[-1]
    logits = logits_ref[...].astype(jnp.float32)
    _, bins = _bins_iota(num_buckets, low, high)
    shifted = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = shifted / jnp.sum(shifted, axis=-1, keepdims=True)
    v = jnp.sum(probs * bins, axis=-1, keepdims=True)
    out = jnp.sign(v) * (jnp.exp(jnp.abs(v)) - 1)  # symexp
    out_ref[...] = out.astype(out_ref.dtype)


def _rows(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def _loss_pallas_forward(logits, value, *, low, high, interpret):
    from jax.experimental import pallas as pl

    out_aval = jax.eval_shape(
        functools.partial(two_hot_symlog_loss_reference, low=low, high=high), logits, value
    )
    lead, num_buckets = logits.shape[:-1], logits.shape[-1]
    n = _rows(lead)
    logits2 = logits.reshape(n, num_buckets)
    value2 = jnp.broadcast_to(value, lead + (1,)).reshape(n, 1)
    block_n = min(n, 256)
    out = pl.pallas_call(
        functools.partial(_loss_kernel, low=float(low), high=float(high)),
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, num_buckets), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), out_aval.dtype),
        interpret=interpret,
        name="two_hot_symlog_loss",
    )(logits2, value2)
    return out.reshape(out_aval.shape)


def _decode_pallas_forward(logits, *, low, high, interpret):
    from jax.experimental import pallas as pl

    out_aval = jax.eval_shape(
        functools.partial(two_hot_symexp_decode_reference, low=low, high=high), logits
    )
    lead, num_buckets = logits.shape[:-1], logits.shape[-1]
    n = _rows(lead)
    logits2 = logits.reshape(n, num_buckets)
    block_n = min(n, 256)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, low=float(low), high=float(high)),
        grid=(pl.cdiv(n, block_n),),
        in_specs=[pl.BlockSpec((block_n, num_buckets), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), out_aval.dtype),
        interpret=interpret,
        name="two_hot_symexp_decode",
    )(logits2)
    return out.reshape(out_aval.shape)


@functools.lru_cache(maxsize=None)
def _build_loss(low: float, high: float):
    reference = functools.partial(two_hot_symlog_loss_reference, low=low, high=high)

    @jax.custom_vjp
    def loss(logits, value):
        return registry.platform_dispatch(
            functools.partial(_loss_pallas_forward, low=low, high=high), reference, logits, value
        )

    def fwd(logits, value):
        return loss(logits, value), (logits, value)

    def bwd(residual, g):
        _, vjp = jax.vjp(reference, *residual)
        return vjp(g)

    loss.defvjp(fwd, bwd)
    return loss


@functools.lru_cache(maxsize=None)
def _build_decode(low: float, high: float):
    reference = functools.partial(two_hot_symexp_decode_reference, low=low, high=high)

    @jax.custom_vjp
    def decode(logits):
        return registry.platform_dispatch(
            functools.partial(_decode_pallas_forward, low=low, high=high), reference, logits
        )

    def fwd(logits):
        return decode(logits), (logits,)

    def bwd(residual, g):
        _, vjp = jax.vjp(reference, *residual)
        return vjp(g)

    decode.defvjp(fwd, bwd)
    return decode


def _loss_pallas(logits, value, low=-20.0, high=20.0):
    return _build_loss(float(low), float(high))(logits, value)


def _decode_pallas(logits, low=-20.0, high=20.0):
    return _build_decode(float(low), float(high))(logits)


registry.register(
    "two_hot_symlog_loss",
    reference=two_hot_symlog_loss_reference,
    pallas=_loss_pallas,
    doc="Fused symlog encode + two-hot + cross-entropy for the Dreamer return heads.",
)
registry.register(
    "two_hot_symexp_decode",
    reference=two_hot_symexp_decode_reference,
    pallas=_decode_pallas,
    doc="Fused softmax expectation + symexp decode (TwoHotEncodingDistribution.mean).",
)


def two_hot_symlog_loss(
    logits: jax.Array,
    value: jax.Array,
    low: float = -20.0,
    high: float = 20.0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Registry-dispatched two-hot/symlog log-probability ``(..., K) x
    (..., 1) -> (...,)`` (``logits`` must be log-normalized)."""
    return registry.dispatch("two_hot_symlog_loss", backend)(logits, value, low, high)


def two_hot_symexp_decode(
    logits: jax.Array,
    low: float = -20.0,
    high: float = 20.0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Registry-dispatched two-hot mean decode ``(..., K) -> (..., 1)``."""
    return registry.dispatch("two_hot_symexp_decode", backend)(logits, low, high)
