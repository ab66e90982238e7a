"""Fused GAE scan kernel — the PPO-family advantage path
(``ops.core.gae``; reference: ``sheeprl/utils/utils.py:64-101``).

The lax reference runs a reversed ``lax.scan`` whose per-step body is four
tiny elementwise ops over ``(B,)`` rows; XLA executes it as ``T`` sequential
fusions with the carry bouncing through HBM each step. This kernel loads the
whole ``(T, N)`` rollout block into VMEM once and walks the recurrence
``last = delta[t] + gamma * lambda * nd[t] * last`` in-register with a
``fori_loop``, emitting both ``returns`` and ``advantages`` in the same
pass. Accumulation is f32 regardless of input dtype, exactly like the
reference (return estimation is where low precision visibly hurts).

The lax reference IS :func:`sheeprl_tpu.ops.core.gae`, so ``ops.backend=lax``
keeps today's graphs bit-for-bit; the kernel mirrors its op order, so the
interpret-mode forward agrees to the last ulp on CPU CI. Compiled for "TPU
v5 lite" (chipless AOT) at (128, 1) through (128, 4096) rollouts.

Gradients: ``jax.custom_vjp`` — Pallas forward, reference scan re-derived on
the backward (the scan's VJP is itself a cheap scan).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.core import gae as gae_reference
from sheeprl_tpu.ops.kernels import registry

__all__ = ["gae", "gae_reference"]


def _gae_kernel(r_ref, v_ref, nvs_ref, nd_ref, ret_ref, adv_ref, *, gamma, lam, horizon):
    from jax.experimental import pallas as pl

    block_n = r_ref.shape[-1]

    def body(i, last):
        t = horizon - 1 - i
        reward = r_ref[pl.ds(t, 1), :]
        value = v_ref[pl.ds(t, 1), :]
        next_val = nvs_ref[pl.ds(t, 1), :]
        nonterminal = nd_ref[pl.ds(t, 1), :]
        delta = reward + gamma * next_val * nonterminal - value
        last = delta + gamma * lam * nonterminal * last
        adv_ref[pl.ds(t, 1), :] = last
        ret_ref[pl.ds(t, 1), :] = last + value
        return last

    jax.lax.fori_loop(0, horizon, body, jnp.zeros((1, block_n), jnp.float32))


def _gae_pallas_forward(rewards, values, dones, next_value, *, gamma, gae_lambda, interpret):
    from jax.experimental import pallas as pl

    ret_aval, adv_aval = jax.eval_shape(
        functools.partial(gae_reference, gamma=gamma, gae_lambda=gae_lambda),
        rewards,
        values,
        dones,
        next_value,
    )
    horizon = rewards.shape[0]
    n = int(np.prod(rewards.shape[1:])) if rewards.ndim > 1 else 1
    # Same upcast + shift the reference performs, outside the kernel (cheap
    # XLA ops); the kernel owns the sequential recurrence.
    r = rewards.astype(jnp.float32).reshape(horizon, n)
    v = values.astype(jnp.float32).reshape(horizon, n)
    nd = (1.0 - dones.astype(jnp.float32)).reshape(horizon, n)
    nv = next_value.astype(jnp.float32).reshape(1, n)
    nvs = jnp.concatenate([v[1:], nv], axis=0)
    block_n = min(n, 512)
    spec = pl.BlockSpec((horizon, block_n), lambda i: (0, i))
    returns, advantages = pl.pallas_call(
        functools.partial(_gae_kernel, gamma=float(gamma), lam=float(gae_lambda), horizon=horizon),
        grid=(pl.cdiv(n, block_n),),
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((horizon, n), jnp.float32),
            jax.ShapeDtypeStruct((horizon, n), jnp.float32),
        ],
        interpret=interpret,
        name="gae",
    )(r, v, nvs, nd)
    return returns.reshape(ret_aval.shape), advantages.reshape(adv_aval.shape)


@functools.lru_cache(maxsize=None)
def _build_gae(gamma: float, gae_lambda: float):
    reference = functools.partial(gae_reference, gamma=gamma, gae_lambda=gae_lambda)

    @jax.custom_vjp
    def fused_gae(rewards, values, dones, next_value):
        return registry.platform_dispatch(
            functools.partial(_gae_pallas_forward, gamma=gamma, gae_lambda=gae_lambda),
            reference,
            rewards,
            values,
            dones,
            next_value,
        )

    def fwd(rewards, values, dones, next_value):
        return fused_gae(rewards, values, dones, next_value), (rewards, values, dones, next_value)

    def bwd(residual, g):
        rewards, values, dones, next_value = residual
        # dones may be integer/bool-typed at some call sites; differentiate
        # only through the float inputs and hand back its symbolic zero.
        _, vjp = jax.vjp(
            lambda r, v, nv: reference(r, v, dones, nv), rewards, values, next_value
        )
        d_r, d_v, d_nv = vjp(g)
        return d_r, d_v, _zero_cotangent(dones), d_nv

    fused_gae.defvjp(fwd, bwd)
    return fused_gae


def _zero_cotangent(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


def _gae_pallas(rewards, values, dones, next_value, gamma, gae_lambda):
    return _build_gae(float(gamma), float(gae_lambda))(rewards, values, dones, next_value)


registry.register(
    "gae",
    reference=gae_reference,
    pallas=_gae_pallas,
    doc="Fused GAE recurrence over a (T, ...) rollout -> (returns, advantages).",
)


def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    next_value: jax.Array,
    gamma: float,
    gae_lambda: float,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Registry-dispatched GAE (drop-in for :func:`sheeprl_tpu.ops.core.gae`)."""
    return registry.dispatch("gae", backend)(rewards, values, dones, next_value, gamma, gae_lambda)
