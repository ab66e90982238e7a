"""Device-resident sequence ring for burst training (TPU-native; no
reference counterpart).

The reference samples replay windows on the host and ships every batch to the
accelerator (``sheeprl/data/buffers.py:395-528`` feeding the Dreamer train
loops). That is one host→device sync per gradient step plus the batch upload (batch 16 x seq 64 of 64x64 pixels is ~12.6 MB). The
burst design inverts it: raw transitions stream to a device uint8 ring with
per-env write heads, windows are sampled ON device with the
``SequentialReplayBuffer`` validity rule, and a whole chunk of granted
gradient steps runs per dispatch.

Shared by the Dreamer-V1/V2/V3 burst paths; the index math is unit-tested in
``tests/test_algos/test_dreamer_ring.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from sheeprl_tpu.ops.kernels import ragged_ring_scatter

__all__ = [
    "ring_cell",
    "ring_view",
    "env_view",
    "ring_append_rows",
    "ring_sample_windows",
    "ring_sample_windows_episode",
    "build_burst_train_step",
    "build_seq_append_step",
    "build_seq_train_step",
    "make_seq_append_layout",
    "make_seq_ctl_layout",
    "BlobLayout",
    "effective_stage_buckets",
    "make_blob_layouts",
    "make_layout",
    "pack_burst_blob",
    "unpack_burst_blob",
]


def ring_cell(shape) -> Tuple[int, int]:
    """The two trailing dims one ring row of an env-shaped key is STORED
    under: ``(feat // 128, 128)`` where the row's element count is a multiple
    of 128 (pixels: 64x64x3 -> 96x128), ``(1, feat)`` otherwise (vectors,
    scalars). Every ring key lives on the device as ``(capacity, n_envs) +
    ring_cell(shape)``: the rows the ragged scatter's blocks and the
    sampler's gather address, so neither rewrites the ring. (Mosaic wants a
    block's last two dims divisible by (8, 128) or equal to the array's; a
    ``(1, 1, feat)`` block of ``(C, E, feat)`` is neither once ``E > 1``. Kept
    env-shaped, a ``u8[C, E, 64, 64, 3]`` ring gets a device layout with ``C``
    minor-most and XLA rewrites all of it around every 17-row write.) This is
    the one place that knows the convention; the host side (staged rows, blob
    segments, host buffers, checkpoints read back through
    :func:`env_view`) keeps the env's shapes."""
    feat = int(np.prod(shape, dtype=np.int64))
    return (feat // 128, 128) if feat % 128 == 0 else (1, feat)


def ring_view(x, shape):
    """``(...lead) + shape -> (...lead) + ring_cell(shape)``: env-shaped rows
    into the stored view (numpy or jax; a view of contiguous bytes)."""
    return x.reshape(x.shape[: x.ndim - len(shape)] + ring_cell(shape))


def env_view(x, shape):
    """``(...lead) + ring_cell(shape) -> (...lead) + shape``: stored rows back
    into the env's shape (the sampled batch, a checkpoint's host copy)."""
    return x.reshape(x.shape[:-2] + tuple(shape))


def ring_append_rows(pos, valid_n, staged_mask, capacity: int):
    """Per-env ragged ring-append indices (burst mode).

    Slot ``i`` writes env ``e`` iff ``staged_mask[i, e]``; each env's rows
    pack densely from its own write head (mirrors
    ``EnvIndependentReplayBuffer``'s ragged adds). Returns the ``(S, E)``
    row indices (``capacity`` marks dropped/padded slots), the new per-env
    write heads and the new per-env valid counts.
    """
    counts = jnp.cumsum(staged_mask.astype(jnp.int32), axis=0)  # (S, E)
    row = (pos[None, :] + counts - 1) % capacity
    row = jnp.where(staged_mask > 0, row, capacity)
    new_pos = (pos + counts[-1]) % capacity
    new_valid = jnp.minimum(valid_n + counts[-1], capacity)
    return row, new_pos, new_valid


def ring_sample_windows(key, env_idx, pos, valid_n, capacity: int, seq_len: int):
    """Uniform sequence-window starts with the ``SequentialReplayBuffer``
    validity rule: a window never crosses its env's write head (the
    oldest→newest data boundary once the ring is full). Returns ``(T, B)``
    time indices for the given per-element env choices."""
    vn = valid_n[env_idx]
    full = vn >= capacity
    n_starts = jnp.where(full, capacity - seq_len + 1, jnp.maximum(vn - seq_len + 1, 1))
    base = jnp.where(full, pos[env_idx], 0)
    u = jax.random.uniform(key, env_idx.shape)
    start = (base + (u * n_starts).astype(jnp.int32)) % capacity
    return (start[None, :] + jnp.arange(seq_len)[:, None]) % capacity


def episode_window_table(pos, valid_n, is_first, capacity: int, seq_len: int):
    """Per-env table of episode-rule-valid window starts (the
    ``EpisodeBuffer`` analogue): a start is valid iff its window satisfies
    the sequential rule AND contains no episode boundary in its interior
    (``is_first`` may be 1 only at the window's first row), so training
    never mixes two episodes in one sequence.

    Envs with NO boundary-free window fall back to their sequential-rule
    starts (the host buffer raises instead — a no-op is not expressible
    in-graph). Returns ``(table, n_valid)``: ``table`` is ``(C, E)`` with
    each env's valid starts packed to the front in ascending order,
    ``n_valid`` the per-env count (min 1).

    Everything here depends only on the ring state after the burst's single
    append, so callers compute it ONCE per burst and draw per-step starts
    with :func:`sample_window_starts` at O(batch) cost. ``is_first`` is the
    ring key as stored (:func:`ring_cell`).
    """
    F = (env_view(is_first, (1,))[..., 0] > 0).astype(jnp.int32)  # (C, E)
    # interior[p, e] = any is_first in rows p+1 .. p+seq_len-1 (circular):
    # windowed sum via a doubled cumsum.
    G = jnp.concatenate([F, F[: seq_len]], axis=0)
    cs = jnp.concatenate([jnp.zeros((1, F.shape[1]), jnp.int32), jnp.cumsum(G, axis=0)], axis=0)
    p = jnp.arange(capacity)
    interior = (cs[p + seq_len] - cs[p + 1]) > 0  # (C, E)

    # sequential validity per position: distance from the env's oldest valid
    # row is < n_starts (same arithmetic as ring_sample_windows, vectorized
    # over positions).
    full = valid_n >= capacity
    n_starts = jnp.where(full, capacity - seq_len + 1, jnp.maximum(valid_n - seq_len + 1, 1))  # (E,)
    base = jnp.where(full, pos, 0)  # (E,)
    dist = (p[:, None] - base[None, :]) % capacity  # (C, E)
    seq_ok = dist < n_starts[None, :]

    ep_ok = seq_ok & ~interior  # (C, E)
    env_has_ep = jnp.any(ep_ok, axis=0)  # (E,)
    ok = jnp.where(env_has_ep[None, :], ep_ok, seq_ok)  # (C, E)
    # valid positions packed to the front, ascending (stable sort on ~ok)
    table = jnp.argsort(~ok, axis=0, stable=True).astype(jnp.int32)
    n_valid = jnp.maximum(ok.sum(axis=0), 1)
    return table, n_valid


def sample_window_starts(key, env_idx, table, n_valid, capacity: int, seq_len: int):
    """Uniform draw from a packed valid-start table: ``(T, B)`` time indices
    for the given per-element env choices. O(batch) per call."""
    u = jax.random.uniform(key, env_idx.shape)
    nv = n_valid[env_idx]
    idx = jnp.minimum((u * nv).astype(jnp.int32), nv - 1)
    start = table[idx, env_idx]
    return (start[None, :] + jnp.arange(seq_len)[:, None]) % capacity


def ring_sample_windows_episode(key, env_idx, pos, valid_n, is_first, capacity: int, seq_len: int):
    """One-shot episode-rule sampling (table + draw). TPU-native deviations
    from the host ``EpisodeBuffer`` (documented in
    ``howto/tpu_parallelism.md``): starts are uniform over valid *windows*
    (longer episodes are sampled proportionally more, like the sequential
    buffer) rather than uniform over episodes; the open episode's prefix is
    sampleable; ``prioritize_ends`` stays a host-path feature. The burst
    step uses the split form (:func:`episode_window_table` once per burst +
    :func:`sample_window_starts` per gradient step)."""
    table, n_valid = episode_window_table(pos, valid_n, is_first, capacity, seq_len)
    return sample_window_starts(key, env_idx, table, n_valid, capacity, seq_len)


def effective_stage_buckets(stage_buckets, stage_max: int) -> Tuple[int, ...]:
    """The normalized flush-bucket set (always ends with ``stage_max``).

    Shared by ``BurstRunner`` and the packed-blob layout construction so the
    host packer and the device unpacker can never disagree on bucket sizes."""
    buckets = sorted(set(int(b) for b in (stage_buckets or ()) if 0 < int(b) <= int(stage_max)))
    if not buckets or buckets[-1] < int(stage_max):
        buckets.append(int(stage_max))
    return tuple(buckets)


class BlobLayout(NamedTuple):
    """Byte layout of one packed burst upload (one staging bucket size)."""

    nbytes: int
    segments: Tuple[Tuple[str, int, tuple, Any], ...]  # (name, offset, shape, np.dtype)


def make_layout(spec) -> BlobLayout:
    """Build a :class:`BlobLayout` from ``(name, shape, dtype)`` triples.

    Segment offsets are 4-byte aligned so 32-bit segments can be bitcast
    from the uint8 view; total length is padded to a 4-byte multiple."""
    segs = []
    off = 0
    for name, shape, dtype in spec:
        off = (off + 3) & ~3
        segs.append((name, off, tuple(int(s) for s in shape), np.dtype(dtype)))
        off += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return BlobLayout((off + 3) & ~3, tuple(segs))


def make_blob_layouts(
    ring_keys: Dict[str, Tuple[tuple, Any]],
    n_envs: int,
    grad_chunk: int,
    buckets: Tuple[int, ...],
    key_width: int = 2,
) -> Dict[int, BlobLayout]:
    """Per-bucket byte layouts for the single-upload burst job.

    A host→device transfer costs per-transfer latency, not just bytes: the
    unpacked burst job ships ~8 separate host arrays and pays that latency
    for each one, serially, on every flush. Packing the staged rows, write
    masks, ring heads, PRNG key, and grant mask into ONE uint8 blob makes a
    flush a single host→device transfer; the (statically shaped) segments
    are sliced and bitcast back out on device inside the burst program.

    Returns ``{bucket_size: BlobLayout}``. Segment offsets are 4-byte
    aligned so 32-bit segments can be bitcast from the byte view. Blob
    lengths are unique across buckets (the length doubles as the jit trace
    key on the device side).
    """
    layouts: Dict[int, BlobLayout] = {}
    seen_lengths = set()
    for size in buckets:
        spec = [(k, (size, n_envs) + tuple(shape), dtype) for k, (shape, dtype) in ring_keys.items()]
        spec += [
            ("__mask__", (size, n_envs), np.int32),
            ("__pos__", (n_envs,), np.int32),
            ("__valid_n__", (n_envs,), np.int32),
            ("__key__", (key_width,), np.uint32),
            ("__validmask__", (grad_chunk,), np.float32),
        ]
        layout = make_layout(spec)
        total = layout.nbytes
        while total in seen_lengths:
            total += 4
        seen_lengths.add(total)
        layouts[int(size)] = BlobLayout(total, layout.segments)
    return layouts


def pack_burst_blob(layout: BlobLayout, values: Dict[str, np.ndarray]) -> np.ndarray:
    """Host side: copy every segment's bytes into one fresh uint8 blob.

    Always a fresh allocation: the blob is queued to the trainer thread, so
    reusing a buffer across flushes would mutate a job still in flight."""
    blob = np.zeros(layout.nbytes, np.uint8)
    for name, off, shape, dtype in layout.segments:
        arr = np.ascontiguousarray(values[name], dtype=dtype)
        blob[off : off + arr.nbytes] = arr.view(np.uint8).ravel()
    return blob


def unpack_burst_blob(blob: jax.Array, layout: BlobLayout, ring_keys=None) -> Dict[str, jax.Array]:
    """Device side (traced): slice + bitcast each segment back out. A segment
    named in ``ring_keys`` holds staged ring rows and comes out in the ring's
    stored view, ``(S, E) + ring_cell(shape)`` (a segment is flat bytes, so
    either view costs the same)."""
    cells = {k: ring_cell(shape) for k, (shape, _dtype) in dict(ring_keys or ()).items()}
    out = {}
    for name, off, shape, dtype in layout.segments:
        itemsize = np.dtype(dtype).itemsize
        n = int(np.prod(shape))
        shape = shape[:2] + cells.get(name, shape[2:])
        seg = jax.lax.slice_in_dim(blob, off, off + n * itemsize, axis=0)
        if itemsize == 1:
            arr = seg.reshape(shape)
            if np.dtype(dtype) != np.uint8:
                arr = jax.lax.bitcast_convert_type(arr, jnp.dtype(dtype))
        else:
            arr = jax.lax.bitcast_convert_type(seg.reshape((n, itemsize)), jnp.dtype(dtype)).reshape(shape)
        out[name] = arr
    return out


def _granted_step(
    gradient_step: Callable[[Any, Any], Any],
    storage: Dict[str, Any],
    ring_keys: Dict[str, Tuple[tuple, Any]],
    sample_starts: Callable[[Any, Any], Any],
    batch_per_dev: int,
    ring_envs: int,
):
    """Shared scan body of the granted-chunk train loops — the coupled burst
    (:func:`build_burst_train_step`) and the decoupled append-free step
    (:func:`build_seq_train_step`) run the SAME gated gradient step, differing
    only in where the window starts come from (``sample_starts(key, env_idx)
    -> (T, B)`` time indices). Padding steps beyond the granted chunk skip
    EVERYTHING — the window sampling and ring gather live inside the taken
    branch (``lax.cond`` executes one branch; operands computed outside it
    would still run unconditionally) — and the zero metrics are derived from
    the true branch's structure, so the two cond branches can never drift
    apart. ``storage`` is the ring as stored; only the gathered ``(T, B)``
    windows are reshaped to the env's shapes (``ring_keys``), so
    ``gradient_step`` sees the batch a host-sampled path would give it."""

    def sampled_step(c, xs):
        k, valid_flag = xs

        def _run(c):
            with jax.named_scope("ring.sample"):  # utils.profiler.REGIONS
                k_env, k_start, k_grad = jax.random.split(k, 3)
                env_idx = jax.random.randint(k_env, (batch_per_dev,), 0, ring_envs)
                t_idx = sample_starts(k_start, env_idx)  # (T, B)
                batch = {
                    kk: env_view(storage[kk][t_idx, env_idx[None, :]], ring_keys[kk][0]) for kk in storage
                }
            nc, m = gradient_step(c, (batch, k_grad))
            # Metrics may be a tuple (Dreamers) or a dict (P2E) — keep the
            # structure, normalize the dtype for the masked mean.
            return nc, jax.tree.map(lambda x: x.astype(jnp.float32), m)

        metrics_shape = jax.eval_shape(_run, c)[1]
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), metrics_shape)
        return jax.lax.cond(valid_flag > 0, _run, lambda cc: (cc, zeros), c)

    return sampled_step


def build_burst_train_step(
    gradient_step: Callable[[Any, Any], Any],
    mesh,
    ring: Dict[str, Any],
    compiler_options: Dict[str, Any] | None = None,
):
    """Wrap an algo's per-gradient-step update into a ring-owning burst step.

    ``gradient_step(carry, (batch, key)) -> (carry, metrics)`` is the same
    scan body the algo's host-sampled path uses; ``carry`` is an arbitrary
    pytree (params/opts/… — Dreamer-V1 carries 2 leaves groups, V2/V3 add a
    cumulative-step counter and V3 the Moments state). The returned jitted
    function has signature::

        burst_fn(carry, rb, blob) -> (carry, rb, metrics)

    with ``rb`` the device ring dict as stored (``(capacity, n_envs) +
    ring_cell(shape)`` per key; donated) and ``blob`` ONE uint8 upload per
    flush (:func:`make_blob_layouts`: the ``(S, E, ...)`` host rows, the
    ``(S, E)`` env write masks, the per-env heads, the train key and a
    ``(grad_chunk,)`` 0/1 mask of granted steps — padding steps skip all
    work via ``lax.cond``). Each bucket's blob length selects its layout, so
    every flush bucket is its own trace.
    """
    capacity = int(ring["capacity"])
    ring_envs = int(ring["n_envs"])
    grad_chunk = int(ring["grad_chunk"])
    ring_seq = int(ring["seq_len"])
    ring_batch = int(ring["batch_size"])
    episode_rule = bool(ring.get("episode_rule", False))  # Dreamer-V2 buffer.type=episode
    ring_keys = ring["ring_keys"]
    n_dev = mesh.devices.size

    def local_burst(carry, rb, staged, staged_mask, pos, valid_n, key, valid):
        # -- per-env ring append. Slot i writes env e iff staged_mask[i, e];
        # each env's rows pack densely from its own write head (ragged adds).
        with jax.named_scope("ring.append"):  # utils.profiler.REGIONS
            row, new_pos, new_valid = ring_append_rows(pos, valid_n, staged_mask, capacity)
            # registry-dispatched ragged scatter (ops.kernels; the lax backend is
            # the literal .at[row, cols].set(..., mode="drop") this site ran)
            rb = {k: ragged_ring_scatter(rb[k], staged[k], row, pos) for k in rb}
        # No env may be shorter than a sample window yet (the host buffer
        # raises in that case); until then every step is a no-op append.
        valid = valid * jnp.all(new_valid >= ring_seq).astype(valid.dtype)

        if episode_rule:
            # Ring contents are fixed after the single append above, so the
            # episode-validity table is computed ONCE per burst; each
            # gradient step then draws starts at O(batch).
            with jax.named_scope("ring.sample"):
                ep_table, ep_n_valid = episode_window_table(
                    new_pos, new_valid, rb["is_first"], capacity, ring_seq
                )
            sample_starts = lambda k, env_idx: sample_window_starts(
                k, env_idx, ep_table, ep_n_valid, capacity, ring_seq
            )
        else:
            sample_starts = lambda k, env_idx: ring_sample_windows(
                k, env_idx, new_pos, new_valid, capacity, ring_seq
            )
        sampled_step = _granted_step(
            gradient_step, rb, ring_keys, sample_starts, ring_batch // n_dev, ring_envs
        )

        key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
        keys = jax.random.split(key, grad_chunk)
        carry, metrics = jax.lax.scan(sampled_step, carry, (keys, valid))
        # Average over the GRANTED steps only (padding contributes zeros).
        denom = jnp.maximum(valid.sum(), 1.0)
        metrics = jax.tree.map(lambda x: jax.lax.pmean((x * valid).sum() / denom, "dp"), metrics)
        return carry, rb, metrics

    shard_burst = shard_map(
        local_burst,
        mesh=mesh,
        in_specs=(P(),) * 8,
        out_specs=(P(),) * 3,
        check_vma=False,
    )

    raw_buckets = tuple(int(b) for b in ring["stage_buckets"])
    layouts = make_blob_layouts(
        ring_keys,
        ring_envs,
        grad_chunk,
        # Same normalization BurstRunner applies to its flush buckets, so
        # every bucket the runner can select has a layout here.
        effective_stage_buckets(raw_buckets, int(ring.get("stage_max", max(raw_buckets)))),
    )
    by_length = {layout.nbytes: layout for layout in layouts.values()}

    def packed_burst(carry, rb, blob):
        layout = by_length[blob.shape[0]]
        with jax.named_scope("ring.append"):
            u = unpack_burst_blob(blob, layout, ring_keys)
        return shard_burst(
            carry,
            rb,
            {k: u[k] for k in ring_keys},
            u["__mask__"],
            u["__pos__"],
            u["__valid_n__"],
            u["__key__"],
            u["__validmask__"],
        )

    # Only the ring is donated: the carry handles (params/opts/...) are read
    # by the main thread (checkpoints) while a burst may be in flight —
    # donation would hand it deleted buffers. The fed-back outputs'
    # placements are pinned (carry and ring are both fed back every burst):
    # left to inference, jit may canonicalize them to an equivalent placement
    # with a different C++ jit-cache key and silently recompile on the next
    # dispatch (the PR 8 class; checked by graft-audit AUD002 on
    # `dreamer_v3.burst_step`).
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    return jax.jit(
        packed_burst,
        donate_argnums=(1,),
        out_shardings=(rep, rep, rep),
        compiler_options=compiler_options,
    )


# --------------------------------------------------------------------------- #
# Decoupled (Sebulba) sequence-ring programs: ragged per-env-head appends
# from concurrent actor threads + the append-free governed train step.
# --------------------------------------------------------------------------- #


def make_seq_append_layout(
    ring_keys: Dict[str, Tuple[tuple, Any]], local_envs: int, stage_rows: int
) -> BlobLayout:
    """Byte layout of ONE actor's append blob: ``stage_rows`` staged rows over
    the actor's OWN ``local_envs`` env columns (regular rows mask every env,
    ragged reset rows mask only the done envs), plus the per-row write masks
    and the actor's env-column offset into the full ring. A single bucket
    size (the per-block maximum) keeps the append program at exactly one
    abstract signature for every actor."""
    spec = [
        (k, (stage_rows, local_envs) + tuple(shape), np.dtype(jnp.dtype(dtype)))
        for k, (shape, dtype) in ring_keys.items()
    ]
    spec += [
        ("__mask__", (stage_rows, local_envs), np.int32),
        ("__offset__", (), np.int32),
    ]
    return make_layout(spec)


def make_seq_ctl_layout(grad_chunk: int) -> BlobLayout:
    """Control blob of the append-free train dispatch: just the granted-step
    mask — the train-key stream lives ON DEVICE in the ring state."""
    return make_layout([("__validmask__", (grad_chunk,), np.float32)])


def build_seq_append_step(
    mesh,
    ring_keys: Dict[str, Tuple[tuple, Any]],
    capacity: int,
    n_envs: int,
    local_envs: int,
    stage_rows: int,
    compiler_options: Dict[str, Any] | None = None,
):
    """The donated ragged multi-head scatter: ``fn(state, blob) -> state``.

    ``state`` is the async sequence-ring pytree (``storage`` dict as stored,
    :func:`ring_cell`, + per-env ``pos``/``valid`` heads + the device
    train-key) and ``blob`` one actor's
    :func:`make_seq_append_layout` upload, already staged on the mesh. Each
    env column in the actor's slice advances its OWN write head by its masked
    row count (``ring_append_rows`` — reset rows advance only the done envs),
    so concurrent actors' blobs commit raggedly without ever sharing a head.
    The single-writer learner owns the dispatch; actors only pack.
    """
    layout = make_seq_append_layout(ring_keys, local_envs, stage_rows)

    def local_append(storage, pos, valid, staged, mask, offset):
        pos_l = jax.lax.dynamic_slice(pos, (offset,), (local_envs,))
        valid_l = jax.lax.dynamic_slice(valid, (offset,), (local_envs,))
        row, new_pos_l, new_valid_l = ring_append_rows(pos_l, valid_l, mask, capacity)
        # rows of dropped/padded slots carry index `capacity` -> dropped by
        # the registry-dispatched ragged scatter (lax backend: the literal
        # .at[row, cols].set(..., mode="drop") this site ran)
        storage = {k: ragged_ring_scatter(storage[k], staged[k], row, pos_l, offset) for k in storage}
        pos = jax.lax.dynamic_update_slice(pos, new_pos_l, (offset,))
        valid = jax.lax.dynamic_update_slice(valid, new_valid_l, (offset,))
        return storage, pos, valid

    shard_append = shard_map(
        local_append,
        mesh=mesh,
        in_specs=(P(),) * 6,
        out_specs=(P(),) * 3,
        check_vma=False,
    )

    def packed_append(state, blob):
        u = unpack_burst_blob(blob, layout, ring_keys)
        storage, pos, valid = shard_append(
            state["storage"], state["pos"], state["valid"],
            {k: u[k] for k in ring_keys}, u["__mask__"], u["__offset__"],
        )
        return {"storage": storage, "pos": pos, "valid": valid, "key": state["key"]}

    # Donated AND fed back every commit: pin the placements (PR 8 class).
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    fn = jax.jit(packed_append, donate_argnums=(0,), out_shardings=rep, compiler_options=compiler_options)
    return fn, layout


def build_seq_train_step(
    gradient_step: Callable[[Any, Any], Any],
    mesh,
    ring: Dict[str, Any],
    compiler_options: Dict[str, Any] | None = None,
):
    """Append-free governed train step over the async sequence ring:
    ``fn(carry, state, ctl_blob) -> (carry, state, metrics)``.

    The ring state's per-env heads are DEVICE arrays (the append program
    advances them in-graph), so each granted gradient step draws its
    ``(T, B)`` windows with the live per-env head validity — an env mid-reset
    behind the others simply exposes fewer valid starts. The train-key stream
    rides the ring state (advanced in-graph, checkpointed with it); the ctl
    blob carries only the granted-step mask.

    Returns ``fn(carry, state, ctl_blob) -> (carry, new_key, metrics)``: the
    advanced train-key is the ONLY piece of ring state this program changes,
    so it is the only piece returned — the caller splices it back
    (``AsyncSequenceRing.set_key``). Returning the whole state would force a
    full ring copy per dispatch: a donation-less passthrough under pinned
    ``out_shardings`` materializes a fresh output buffer (measured ~2 s per
    dispatch on an 800 MB pixel ring), and the storage must NOT be donated —
    the append program is the ring's only in-place writer. The carry stays
    undonated too: the ParamServer publishes references the actors keep
    pulling across updates.
    """
    capacity = int(ring["capacity"])
    ring_envs = int(ring["n_envs"])
    grad_chunk = int(ring["grad_chunk"])
    ring_seq = int(ring["seq_len"])
    ring_batch = int(ring["batch_size"])
    ring_keys = ring["ring_keys"]
    n_dev = mesh.devices.size
    ctl_layout = make_seq_ctl_layout(grad_chunk)

    def local_train(carry, storage, pos, valid_n, key, validmask):
        # in-graph belt matching the host-side grant gate: no env may be
        # shorter than a sample window (the host buffer raises in that state)
        validmask = validmask * jnp.all(valid_n >= ring_seq).astype(validmask.dtype)
        new_key, k_dispatch = jax.random.split(key)
        k_local = jax.random.fold_in(k_dispatch, jax.lax.axis_index("dp"))
        keys = jax.random.split(k_local, grad_chunk)

        sample_starts = lambda k, env_idx: ring_sample_windows(
            k, env_idx, pos, valid_n, capacity, ring_seq
        )
        sampled_step = _granted_step(
            gradient_step, storage, ring_keys, sample_starts, ring_batch // n_dev, ring_envs
        )
        carry, metrics = jax.lax.scan(sampled_step, carry, (keys, validmask))
        denom = jnp.maximum(validmask.sum(), 1.0)
        metrics = jax.tree.map(lambda x: jax.lax.pmean((x * validmask).sum() / denom, "dp"), metrics)
        return carry, new_key, metrics

    shard_train = shard_map(
        local_train,
        mesh=mesh,
        in_specs=(P(),) * 6,
        out_specs=(P(),) * 3,
        check_vma=False,
    )

    def packed_train(carry, state, ctl_blob):
        u = unpack_burst_blob(ctl_blob, ctl_layout)
        carry, new_key, metrics = shard_train(
            carry, state["storage"], state["pos"], state["valid"], state["key"], u["__validmask__"]
        )
        return carry, new_key, metrics

    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    fn = jax.jit(
        packed_train,
        out_shardings=(rep, rep, rep),
        compiler_options=compiler_options,
    )
    return fn, ctl_layout
