"""``lax.scan`` over a step of flax modules whose ``Dense`` weight gradients
are formed once, after the backward loop.

JAX's transpose of a scan keeps the cotangent of every closed-over array in
the backward loop's carry, so a step that applies a weight matrix ``W`` does
``dW += x_t^T dz_t`` per time step: a rank-``B`` update that reads and writes
the whole matrix. With few rows and wide matrices (DreamerV3-XL: 16 rows
against 5120 x 12288) that accumulation is the loop's memory traffic.

:func:`scan_dense_grads_after` runs the same step with the matrices held
constant and a zero *tap* added to each ``Dense``'s output as a per-step scan
input. The backward loop then emits each tap's cotangent ``dz_t`` as a stacked
output instead of carrying ``dW``; the forward loop emits each ``Dense``'s
input ``x_t``; and ``dW = sum_t x_t^T dz_t`` is one contraction per matrix
after the loop. Vectors (biases, norm scales) stay in the carry: kilobytes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["scan_dense_grads_after"]


def _tapped_step(step, treedef, leaves, carry, x, taps):
    """``step`` on the parameters ``leaves``, watching every ``nn.Dense`` whose
    kernel is one of their 2-D leaves (found by identity: ``step`` must apply
    the leaves it is handed). Returns ``step``'s result and, per leaf, the
    inputs and outputs of the ``Dense`` calls that applied it, in call order.
    With ``taps`` (per leaf, one array per call) each output has its tap added."""
    index = {id(leaf): i for i, leaf in enumerate(leaves) if jnp.ndim(leaf) == 2}
    inputs: List[List[jax.Array]] = [[] for _ in leaves]
    outputs: List[List[jax.Array]] = [[] for _ in leaves]

    def interceptor(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if type(context.module) is nn.Dense and context.method_name == "__call__":
            i = index.get(id(context.module.get_variable("params", "kernel")))
            if i is not None:
                if taps is not None:
                    out = out + taps[i][len(inputs[i])]
                inputs[i].append(args[0])
                outputs[i].append(out)
        return out

    with nn.intercept_methods(interceptor):
        result = step(jax.tree.unflatten(treedef, leaves), carry, x)
    return result, inputs, outputs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def scan_dense_grads_after(step: Callable[[Any, Any, Any], Tuple[Any, Any]], params: Any, carry0: Any, xs: Any):
    """``lax.scan(lambda c, x: step(params, c, x), carry0, xs)``, differentiable
    in ``params``, ``carry0`` and ``xs``, with the gradient of every 2-D kernel
    that a ``flax.linen.Dense`` inside ``step`` applies formed after the
    backward loop (module docstring). ``step`` must take every array it uses
    through its arguments. Kernels that no ``Dense`` applies, and all other
    parameters, are differentiated by the scan as usual."""
    return jax.lax.scan(lambda c, x: step(params, c, x), carry0, xs)


def _fwd(step, params, carry0, xs):
    leaves, treedef = jax.tree.flatten(params)
    length = jax.tree.leaves(xs)[0].shape[0]
    # one abstract trace of the step says which leaves a Dense applies, and each call's output shape:
    # per leaf, one zero tap per call (none: the leaf is not hoisted)
    x0 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs)
    outputs = jax.eval_shape(lambda l, c, x: _tapped_step(step, treedef, l, c, x, None)[2], leaves, carry0, x0)
    taps = jax.tree.map(lambda out: jnp.zeros((length, *out.shape), out.dtype), outputs)

    def run(scanned_leaves, carry0, xs, taps):
        # a hoisted kernel is a constant of this function, not an argument
        inside = [leaf if s is None else s for leaf, s in zip(leaves, scanned_leaves)]

        def body(carry, x_and_tap):
            (carry, out), inputs, _ = _tapped_step(step, treedef, inside, carry, *x_and_tap)
            return carry, (out, inputs)

        carry, (outs, inputs) = jax.lax.scan(body, carry0, (xs, taps))
        return (carry, outs), inputs

    scanned_leaves = [None if t else leaf for leaf, t in zip(leaves, taps)]
    out, vjp_fn, inputs = jax.vjp(run, scanned_leaves, carry0, xs, taps, has_aux=True)
    return out, (vjp_fn, inputs, params)


def _bwd(step, residuals, cotangents):
    vjp_fn, inputs, params = residuals
    leaves, treedef = jax.tree.flatten(params)
    d_leaves, d_carry0, d_xs, d_taps = vjp_fn(cotangents)
    for i, (kernel, xs_i, dzs_i) in enumerate(zip(leaves, inputs, d_taps)):
        if xs_i:
            # one contraction over time and batch per call; default precision, float32 accumulation
            d_leaves[i] = sum(
                jnp.einsum(
                    "ni,no->io",
                    x.reshape(-1, x.shape[-1]).astype(dz.dtype),
                    dz.reshape(-1, dz.shape[-1]),
                    preferred_element_type=jnp.float32,
                )
                for x, dz in zip(xs_i, dzs_i)
            ).astype(kernel.dtype)
    return jax.tree.unflatten(treedef, d_leaves), d_carry0, d_xs


scan_dense_grads_after.defvjp(_fwd, _bwd)
