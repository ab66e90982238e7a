"""Fused PER sum-tree batched descent (``replay.sumtree``; PER,
arXiv:1511.05952).

The lax path runs the ``log2(P)`` statically-unrolled descent levels as
separate gather/compare/select fusions, re-reading the ``(2P,)`` tree from
HBM at every level, then a second pass (``importance_weights``) reads the
leaves again. This kernel loads the tree into VMEM ONCE and walks all
levels plus the importance-weight epilogue in a single pass, so the
sampling frontier (``mass``/``idx`` per draw) never leaves registers:
``(2P,) x (B,) -> (leaf_idx (B,) int32, weights (B,) f32)``.

The TPU compiler refuses this kernel at every shape (the per-level
``take_along_axis`` over the ``(1, 2P)`` tree is a gather Mosaic does not
have — message in :data:`registry.AUTO_LAX_ON_TPU`), so ``auto`` runs the lax
reference on TPU and no VMEM bound has been observed; the kernel runs only
in interpret mode (``ops.kernels.sumtree_sample=pallas`` without a TPU). By
arithmetic alone the resident f32 tree is ``8 MiB`` at ``P = 2^20`` leaves,
half of the 16 MiB of scoped VMEM a v5e kernel gets.

The lax reference is the literal ``sample`` + ``importance_weights``
composition the SAC PER path ran before this kernel existed, so
``ops.backend=lax`` reproduces that graph bit-for-bit.

Gradients: ``jax.custom_vjp`` — descent indices are integer outputs and
carry no gradient; the weights differentiate through the reference chain
(tree priorities, ``u`` and ``beta``) on the backward.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.kernels import registry

__all__ = ["sumtree_sample", "sumtree_sample_reference"]


def sumtree_sample_reference(tree: jax.Array, u: jax.Array, n_valid, beta) -> Tuple[jax.Array, jax.Array]:
    """The two-pass lax chain: proportional descent, then unnormalized PER
    importance weights for the drawn leaves."""
    # Lazy import: replay's package init reaches data.ring, which dispatches
    # back into this kernel tier — a module-level import would cycle.
    from sheeprl_tpu.replay import sumtree as st

    leaf = st.sample(tree, u)
    weights = st.importance_weights(tree, leaf, n_valid, beta)
    return leaf, weights


def _sumtree_kernel(tree_ref, u_ref, nv_ref, beta_ref, idx_ref, w_ref, *, levels, leaves):
    tree = tree_ref[...]  # (1, 2P) — the whole tree, resident in VMEM
    u = u_ref[...]  # (1, B)
    total = tree[0, 1]
    mass = jnp.minimum(u, 1.0 - 1e-7) * total
    idx = jnp.ones(u.shape, jnp.int32)
    for _ in range(levels):  # statically unrolled descent
        left = jnp.take_along_axis(tree, 2 * idx, axis=1)
        go_right = mass >= left
        mass = jnp.where(go_right, mass - left, mass)
        idx = 2 * idx + go_right.astype(jnp.int32)
    priority = jnp.take_along_axis(tree, idx, axis=1)  # == tree[P + leaf]
    prob = priority / jnp.maximum(total, 1e-12)
    weights = jnp.power(jnp.maximum(nv_ref[0, 0] * prob, 1e-12), -beta_ref[0, 0])
    idx_ref[...] = idx - leaves
    w_ref[...] = weights.astype(w_ref.dtype)


def _sumtree_pallas_forward(tree, u, n_valid, beta, *, interpret):
    from jax.experimental import pallas as pl

    leaves = tree.shape[0] // 2
    levels = int(np.log2(leaves))
    batch = u.shape[0]
    leaf, weights = pl.pallas_call(
        functools.partial(_sumtree_kernel, levels=levels, leaves=leaves),
        out_shape=[
            jax.ShapeDtypeStruct((1, batch), jnp.int32),
            jax.ShapeDtypeStruct((1, batch), jnp.float32),
        ],
        interpret=interpret,
        name="sumtree_sample",
    )(
        tree.astype(jnp.float32).reshape(1, 2 * leaves),
        u.astype(jnp.float32).reshape(1, batch),
        jnp.asarray(n_valid, jnp.float32).reshape(1, 1),
        jnp.asarray(beta, jnp.float32).reshape(1, 1),
    )
    return leaf.reshape(batch), weights.reshape(batch)


@jax.custom_vjp
def _sumtree_pallas(tree, u, n_valid, beta):
    return registry.platform_dispatch(
        _sumtree_pallas_forward, sumtree_sample_reference, tree, u, n_valid, beta
    )


def _fwd(tree, u, n_valid, beta):
    return _sumtree_pallas(tree, u, n_valid, beta), (tree, u, n_valid, beta)


def _bwd(residual, g):
    tree, u, n_valid, beta = residual
    _g_leaf, g_w = g  # integer leaf indices carry no gradient

    def weights_of(tree_, u_, nv_, beta_):
        return sumtree_sample_reference(tree_, u_, nv_, beta_)[1]

    _, vjp = jax.vjp(weights_of, tree, u, _as_f32(n_valid), _as_f32(beta))
    d_tree, d_u, d_nv, d_beta = vjp(g_w)
    return d_tree, d_u, _restore(d_nv, n_valid), _restore(d_beta, beta)


def _as_f32(x):
    return jnp.asarray(x, jnp.float32)


def _restore(ct, primal):
    if jnp.issubdtype(jnp.result_type(primal), jnp.inexact):
        return ct.astype(jnp.result_type(primal))
    return _zero_cotangent(primal)


def _zero_cotangent(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


_sumtree_pallas.defvjp(_fwd, _bwd)

registry.register(
    "sumtree_sample",
    reference=sumtree_sample_reference,
    pallas=_sumtree_pallas,
    doc="Fused PER descent + importance weights, tree resident in VMEM.",
)


def sumtree_sample(
    tree: jax.Array, u: jax.Array, n_valid, beta, backend: Optional[str] = None
) -> Tuple[jax.Array, jax.Array]:
    """Registry-dispatched proportional PER draw:
    ``(2P,) tree x (B,) uniforms -> (leaf_idx, unnormalized IS weights)``."""
    return registry.dispatch("sumtree_sample", backend)(tree, u, n_valid, beta)
