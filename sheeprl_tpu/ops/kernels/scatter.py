"""Ragged multi-head ring scatter (``data.ring``'s per-env-head append).

The device ring commits one staged blob per dispatch: slot ``(s, e)`` of a
``(S, e) + cell`` staged block lands at ``storage[row[s, e], col_offset + e]``,
where ``row`` carries the per-env ragged pack from
:func:`sheeprl_tpu.data.ring.ring_append_rows` and dropped/padded slots are
marked ``row == capacity``. The lax path is a fancy-indexed
``.at[...].set(mode="drop")`` — XLA lowers it as a full-buffer scatter that
re-threads the (donated) ring through a scatter op per storage key. The
Pallas kernel instead streams only the ``S*e`` touched rows: scalar-prefetched
row/col indices drive the output ``BlockSpec`` directly (the classic
prefetch-scatter pattern), the ring aliases in-place via
``input_output_aliases``, and untouched rows are never read or written.

Both tiers take and return the ring AS STORED: ``(C, E) + cell`` with
``cell = data.ring.ring_cell(shape)``, i.e. ``(feat // 128, 128)`` or
``(1, feat)`` — one ring cell per block (Mosaic wants a block's last two dims
divisible by (8, 128) or equal to the array's). Nothing here reshapes
storage, staged rows or the result. It did until PR 28: the ring was kept
env-shaped (``u8[C, E, 64, 64, 3]``, whose device layout puts ``C``
minor-most), the kernel built the cell view with ``reshape`` on every call,
neither reshape was a bitcast under the TPU's tiled layouts, and XLA
bracketed the kernel (34 us per three bursts on the 1.15 GiB pixel ring of
the DreamerV3 cells) in seven whole-ring rewrites, 8.6 GB a burst
(PERF.md, finding 28: `kernel_ragged_ring_scatter_ms` 2.11 -> 0.0009 and
`train_step_ms` 22.70 -> 19.96 at S, on the chip). A caller that reshapes a
whole ring key around this call brings them back;
``tests/test_ops/test_kernels.py`` (the chipless TPU compile of a pixel
ring's append, both ways), ``tests/test_utils/test_burst.py`` (no such
equation in the traced burst program) and the ``dreamer_v3.burst_step`` audit
budget guard against it. A key with a narrow cell (``f32[C, E, 1, 18]``) still
arrives with ``C`` minor-most and is copied into the call's row-major layout
and back, 0.4–7 MB a key: pinning the row-major layout on the program's ring
argument instead pads those keys to 128 lanes at rest (+196 MB) and leaves
the gather's copies, so it was left alone.

Dropped slots cannot skip their grid step, so they are parked on the row
*before* the env's write head (``(pos[e] - 1) % capacity``) and write back
the old block value: ``ring_append_rows`` packs each env densely from
``pos[e]``, so that row is provably untouched by any valid write of the same
dispatch (a full-capacity wrap with a dropped slot is impossible —
``count <= S - dropped``), making the write-back a no-op regardless of grid
order or pipelining.

Preconditions (both call sites satisfy them): ``staged.dtype ==
storage.dtype``, ``capacity == storage.shape[0]``, ``staged.shape[2:] ==
storage.shape[2:]`` (two dims), and every ``col_offset + e`` in bounds.

Gradients: ``jax.custom_vjp`` — Pallas forward, scatter/gather VJP of the
lax reference on the backward (float dtypes only; the ring's uint8 image
keys are never differentiated).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.ops.kernels import registry

__all__ = ["ragged_ring_scatter", "ragged_ring_scatter_reference"]


def ragged_ring_scatter_reference(
    storage: jax.Array, staged: jax.Array, row: jax.Array, pos: jax.Array, col_offset=0
) -> jax.Array:
    """The literal call-site scatter: ``storage.at[row, cols].set(staged,
    mode="drop")`` with per-slot columns ``col_offset + arange(e)``. ``pos``
    (the pre-append write heads) is unused here — only the Pallas variant
    needs it to park dropped slots on a provably-untouched row."""
    del pos
    e = row.shape[1]
    cols = col_offset + jnp.broadcast_to(jnp.arange(e)[None, :], row.shape)
    return storage.at[row, cols].set(staged, mode="drop")


def _scatter_kernel(rows_ref, cols_ref, mask_ref, staged_ref, old_ref, out_ref):
    del rows_ref, cols_ref  # consumed by the index maps
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    take = mask_ref[i] > 0
    out_ref[...] = jnp.where(take, staged_ref[...], old_ref[...])


def _scatter_pallas_forward(storage, staged, row, pos, col_offset, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    capacity, env_cols = storage.shape[:2]
    cell = storage.shape[2:]  # data.ring.ring_cell: one ring row, two dims
    slots, e = row.shape

    mask = (row < capacity).astype(jnp.int32)
    # Park dropped slots on the row before this env's write head: never
    # touched by a valid write of the same dispatch (see module docstring),
    # so writing the old value back there is a no-op.
    safe_row = jnp.where(mask > 0, row, (pos[None, :] - 1) % capacity).astype(jnp.int32)
    cols = (col_offset + jnp.broadcast_to(jnp.arange(e), row.shape)).astype(jnp.int32)

    block = pl.BlockSpec((1, 1) + cell, lambda i, rows, cols, mask: (rows[i], cols[i], 0, 0))
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots * e,),
            in_specs=[
                pl.BlockSpec((1, 1) + cell, lambda i, rows, cols, mask: (i // e, i % e, 0, 0)),
                block,
            ],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(storage.shape, storage.dtype),
        input_output_aliases={4: 0},  # storage updates in place
        interpret=interpret,
        name="ragged_ring_scatter",
    )(safe_row.reshape(slots * e), cols.reshape(slots * e), mask.reshape(slots * e), staged, storage)


@jax.custom_vjp
def _scatter_pallas(storage, staged, row, pos, col_offset):
    return registry.platform_dispatch(
        _scatter_pallas_forward, ragged_ring_scatter_reference, storage, staged, row, pos, col_offset
    )


def _fwd(storage, staged, row, pos, col_offset):
    return _scatter_pallas(storage, staged, row, pos, col_offset), (storage, staged, row, pos, col_offset)


def _bwd(residual, g):
    storage, staged, row, pos, col_offset = residual
    _, vjp = jax.vjp(
        lambda s, t: ragged_ring_scatter_reference(s, t, row, pos, col_offset), storage, staged
    )
    d_storage, d_staged = vjp(g)
    return d_storage, d_staged, _zero_cotangent(row), _zero_cotangent(pos), _zero_cotangent(col_offset)


def _zero_cotangent(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


_scatter_pallas.defvjp(_fwd, _bwd)


def _scatter_pallas_entry(storage, staged, row, pos, col_offset=0):
    # Uniform traced operands into the custom_vjp boundary.
    return _scatter_pallas(
        storage, staged, jnp.asarray(row, jnp.int32), jnp.asarray(pos, jnp.int32),
        jnp.asarray(col_offset, jnp.int32),
    )


registry.register(
    "ragged_ring_scatter",
    reference=ragged_ring_scatter_reference,
    pallas=_scatter_pallas_entry,
    doc="Per-env-head ragged ring append via scalar-prefetched block scatter.",
)


def ragged_ring_scatter(
    storage: jax.Array,
    staged: jax.Array,
    row: jax.Array,
    pos: jax.Array,
    col_offset=0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Registry-dispatched ragged ring append on the stored view: ``(C, E) +
    cell x (S, e) + cell x (S, e) rows -> (C, E) + cell`` (``row == capacity``
    slots are dropped)."""
    return registry.dispatch("ragged_ring_scatter", backend)(storage, staged, row, pos, col_offset)
