"""Pallas TPU kernel for the Hafner-GRU gate chain — the pointwise tail of
every RSSM step (``models.LayerNormGRUCell``; reference torch cell:
``sheeprl/models/models.py:331-412``).

After the fused ``Dense -> (LayerNorm)`` projection, the cell runs
``split -> sigmoid(reset) -> tanh(reset * cand) -> sigmoid(update - 1) ->
blend`` — five elementwise passes over a ``(B, 3H)`` tensor that the dynamic
and imagination scans execute at every timestep. This kernel pins the whole
chain into ONE VPU pass per block: the ``(B, 3H)`` projection and the
``(B, H)`` carry are read from VMEM once and a single ``(B, H)`` result is
written back, instead of round-tripping each intermediate through HBM when
XLA's fuser splits the chain.

Gradients: ``jax.custom_vjp`` with the Pallas kernel on the forward and the
(cheap, fully-fusable) jnp reference chain re-derived on the backward.

In a process with no TPU an explicit ``ops.backend=pallas`` runs the kernel
in Pallas ``interpret`` mode, so the CPU test mesh exercises the same code
path numerically; in a process that holds a TPU, host-CPU lowerings take the
reference (:func:`registry.platform_dispatch`). This module is the template
entry of the kernel tier: every other kernel in
:mod:`sheeprl_tpu.ops.kernels` follows the same reference/pallas/registry
triple.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.kernels import registry

__all__ = ["gru_gates", "gru_gates_pallas", "gru_gates_reference"]


def gru_gates_reference(fused: jax.Array, h: jax.Array) -> jax.Array:
    """The plain-jnp gate chain (ground truth and backward-pass body)."""
    reset, cand, update = jnp.split(fused, 3, axis=-1)
    reset = jax.nn.sigmoid(reset)
    cand = jnp.tanh(reset * cand)
    update = jax.nn.sigmoid(update - 1)
    return update * cand + (1 - update) * h


def _kernel(fused_ref, h_ref, out_ref):
    # Gate math in f32 regardless of the IO dtype: Mosaic rejects the mixed
    # f32-scalar/bf16-vector broadcasts the transcendental lowerings emit
    # under bf16, and the VPU pays nothing extra for f32 elementwise.
    fused = fused_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    hidden = h.shape[-1]
    reset = jax.nn.sigmoid(fused[..., :hidden])
    cand = jnp.tanh(reset * fused[..., hidden : 2 * hidden])
    update = jax.nn.sigmoid(fused[..., 2 * hidden :] - 1)
    out_ref[...] = (update * cand + (1 - update) * h).astype(out_ref.dtype)


# Scoped VMEM the TPU compiler grants one kernel by default (v5e: 16 MiB,
# from its own "Scoped allocation with size 40.00M and limit 16.00M" at
# B=1024, H=4096, f32 with a 256-row block). The block is sized to 3/4 of it.
_VMEM_BLOCK_BUDGET = 12 * 2**20
_MAX_BLOCK_ROWS = 256


def _block_rows(batch: int, hidden: int, itemsize: int) -> int:
    """Rows per grid step such that the block fits scoped VMEM at any width.

    Per row the pipeline holds the ``3H`` projection, the ``H`` carry and the
    ``H`` result twice (double buffering) in the IO dtype, and the kernel body
    about eight ``H``-wide f32 temporaries (the upcast inputs and the gate
    chain). Observed by compiling for "TPU v5 lite" (jax 0.9.0, libtpu
    0.0.34) at B=1024, H=4096: f32 blocks of 80 rows fit and 96 do not, bf16
    blocks of 128 fit and 160 do not; this rule picks 40 and 48 there, 168
    and 224 at H=1024, and the 256-row cap at H <= 512.
    """
    per_row = 2 * 5 * hidden * itemsize + 8 * hidden * 4
    rows = min(_VMEM_BLOCK_BUDGET // per_row, _MAX_BLOCK_ROWS)
    if rows >= batch:
        return batch
    sublanes = 8 * max(1, 4 // itemsize)  # (8, 128) f32 tiles, (16, 128) bf16
    rows = rows // sublanes * sublanes
    if rows == 0:
        raise ValueError(
            f"gru_gates: a {sublanes}-row block of width H={hidden} ({itemsize}-byte elements) "
            f"does not fit the {_VMEM_BLOCK_BUDGET >> 20} MiB scoped-VMEM block budget"
        )
    return rows


def _pallas_forward(fused: jax.Array, h: jax.Array, interpret: bool) -> jax.Array:
    from jax.experimental import pallas as pl

    B, H = h.shape
    # Block over the batch; each row keeps its full 3H projection in VMEM.
    block_b = _block_rows(B, H, jnp.dtype(h.dtype).itemsize)
    grid = (pl.cdiv(B, block_b),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 3 * H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H), h.dtype),
        interpret=interpret,
        name="gru_gates",
    )(fused, h)


def _forward(fused: jax.Array, h: jax.Array) -> jax.Array:
    return registry.platform_dispatch(_pallas_forward, gru_gates_reference, fused, h)


@jax.custom_vjp
def gru_gates_pallas(fused: jax.Array, h: jax.Array) -> jax.Array:
    """Fused GRU gate chain, always on the Pallas path:
    ``(B, 3H) x (B, H) -> (B, H)``."""
    return _forward(fused, h)


def _fwd(fused, h):
    return _forward(fused, h), (fused, h)


def _bwd(residual, g):
    fused, h = residual
    _, vjp = jax.vjp(gru_gates_reference, fused, h)
    return vjp(g)


gru_gates_pallas.defvjp(_fwd, _bwd)

registry.register(
    "gru_gates",
    reference=gru_gates_reference,
    pallas=gru_gates_pallas,
    doc="Fused GRU gate chain (B, 3H) x (B, H) -> (B, H); RSSM step tail.",
)


def gru_gates(fused: jax.Array, h: jax.Array, backend: Optional[str] = None) -> jax.Array:
    """Registry-dispatched GRU gate chain (``backend=None`` follows the
    ``ops.backend`` config; ``"pallas"``/``"lax"`` force a tier)."""
    return registry.dispatch("gru_gates", backend)(fused, h)
