"""Fused two-hot/symlog kernels for the Dreamer return/reward heads
(``distributions.TwoHotEncodingDistribution``; reference torch path:
``sheeprl/utils/distribution.py:224-277``).

Two kernels cover the distribution's hot methods:

- :func:`two_hot_symlog_loss` — ``log_prob`` under the default
  ``symlog``/``symexp`` transforms. The bins are uniform, so the two-hot
  target of a symlog'd value ``x`` is a hat function of it, ``w_k = max(0,
  1 - |x - b_k| / gap)``, and the loss is ONE contraction over the bucket
  axis, ``sum_k w_k(x) * logits_k``: no bucket search, no gather, no
  one-hot. It is plain ``jax.numpy`` on every platform and has no Mosaic
  body: XLA fuses the contraction into one multiply-reduce, and where two
  losses read the same logits (the critic's, against the lambda returns and
  against the target critic) it reads them once. On the chip that beat the
  same contraction as a Mosaic kernel, which was deleted (``PERF.md``
  finding 31). The inline jnp version materializes two ``(..., K)``
  one-hot matmuls plus half a dozen ``(..., K)`` comparison intermediates
  per loss.
- :func:`two_hot_symexp_decode` — ``mean``: softmax over the buckets,
  expectation against the bin support, symexp back to reward space, in one
  VPU pass per row block of a Pallas kernel.

The lax references are literal extractions of the distribution's inline
math, so ``ops.backend=lax`` reproduces the historical graphs bit-for-bit.
The decode kernel rebuilds the bin support from a broadcasted iota (1D iota
does not lower on TPU), which matches ``jnp.linspace`` up to 1 ulp; an ulp
of a bin moves the expectation by 1e-7 of it. The loss cannot afford that
(an ulp of a bin near +-20 is 1e-5 of a two-hot weight) and takes the
reference's own bins (:func:`_support`).

Gradients: ``jax.custom_vjp``. The loss's backward is a closed form of the
same hat (``d logits = g * w(x)``; ``d value`` from the two neighbouring
logits), the decode's is the reference chain re-derived. Interpret mode only
in a process with no TPU, as everywhere in the kernel tier.
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
    return low + iota.astype(jnp.float32) * step


@functools.lru_cache(maxsize=None)
def _support(low: float, high: float, num_buckets: int):
    """The loss's bins ``(K,)`` and the slope of each bin's hat on either side
    of it: ``rise[k] = 1 / (b[k+1] - b[k])`` above it, ``fall[k] = -1 / (b[k] -
    b[k-1])`` below it, 0 where the support ends. Host constants, f32.

    The bins are the ones the reference gets when it is called eagerly:
    ``jnp.linspace`` run as its own program, outside whatever is being
    traced, once per process and support. No formula of this module's would
    do: ``jnp.linspace`` rounds a bin by an ulp one way or the other
    depending on the backend and on what it is fused with. The slopes are
    each pair of neighbours' actual distance for the same reason. A program
    that uses them holds them as constants: no ``_linspace`` is left in it."""
    with jax.core.eval_context():  # not ensure_compile_time_eval: that runs _linspace op by op, and rounds otherwise
        bins = np.asarray(jnp.linspace(low, high, num_buckets, dtype=jnp.float32))
    slope = np.float32(1.0) / np.diff(bins)
    zero = np.zeros((1,), np.float32)
    return bins, np.concatenate([slope, zero]), np.concatenate([zero, -slope])


def _hat(x, bins, rise, fall):
    """Two-hot weights ``(..., K)`` of ``x`` ``(..., 1)``, already clamped to
    the support: ``w_k = max(0, 1 - |x - b_k| / gap)``, the gap being the one
    on ``x``'s side of ``b_k``. Zero on every bin but ``x``'s two neighbours,
    one on a bin ``x`` sits on, summing to one."""
    d = x - bins
    return jnp.maximum(0.0, 1.0 - jnp.maximum(d * rise, d * fall))


def _decode_kernel(logits_ref, out_ref, *, low, high):
    num_buckets = logits_ref.shape[-1]
    logits = logits_ref[...].astype(jnp.float32)
    bins = _bins_iota(num_buckets, low, high)
    shifted = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = shifted / jnp.sum(shifted, axis=-1, keepdims=True)
    v = jnp.sum(probs * bins, axis=-1, keepdims=True)
    out = jnp.sign(v) * (jnp.exp(jnp.abs(v)) - 1)  # symexp
    out_ref[...] = out.astype(out_ref.dtype)


def _rows(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def _loss_forward(logits, value, *, low, high, interpret=False):
    # ``interpret``: registry.platform_dispatch's keyword; there is no Pallas
    # call to interpret, the same jnp runs everywhere
    out_aval = jax.eval_shape(
        functools.partial(two_hot_symlog_loss_reference, low=low, high=high), logits, value
    )
    x = jnp.clip(symlog(value.astype(jnp.float32)), low, high)
    weights = _hat(x, *_support(low, high, logits.shape[-1]))
    return jnp.sum(weights * logits.astype(jnp.float32), axis=-1).astype(out_aval.dtype)


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
            functools.partial(_loss_forward, low=low, high=high), reference, logits, value
        )

    def fwd(logits, value):
        return loss(logits, value), (logits, value)

    def bwd(residual, g):
        # Closed form, no gather and no one-hot: the target is the hat itself,
        # and between two bins the loss is linear in symlog(value).
        logits, value = residual
        bins, rise, fall = _support(low, high, logits.shape[-1])
        x, symlog_vjp = jax.vjp(symlog, value.astype(jnp.float32))
        g = g[..., None].astype(jnp.float32)
        d_logits = g * _hat(jnp.clip(x, low, high), bins, rise, fall)
        # d value is the reference's own: between two bins the loss rises by
        # (logits_above - logits_below) / gap; beyond the support's ends it is
        # flat; ON a bin, where jnp.abs has the slope +1 at 0 for both of the
        # reference's distances, the same with the sign turned.
        climb = (logits[..., 1:] - logits[..., :-1]).astype(jnp.float32) * rise[:-1]
        climb = jnp.where((bins[:-1] <= x) & (x < bins[1:]), jnp.where(bins[:-1] == x, -climb, climb), 0.0)
        (d_value,) = symlog_vjp(g * jnp.sum(climb, axis=-1, keepdims=True))
        return d_logits.astype(logits.dtype), d_value.astype(value.dtype)

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


def _loss_hat(logits, value, low=-20.0, high=20.0):
    value = jnp.broadcast_to(value, logits.shape[:-1] + (1,))
    return _build_loss(float(low), float(high))(logits, value)


def _decode_pallas(logits, low=-20.0, high=20.0):
    return _build_decode(float(low), float(high))(logits)


registry.register(
    "two_hot_symlog_loss",
    reference=two_hot_symlog_loss_reference,
    pallas=_loss_hat,  # registry.COMPILED_BY_XLA
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
