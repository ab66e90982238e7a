"""Device-resident replay buffer: a dict-of-jnp ring living in accelerator
HBM, with in-graph sampling fused into the jitted train step.

Why: the off-policy mains used to sample replay batches on the host in numpy
and ship them key-by-key with ``device_put`` on every gradient step — the
host-in-the-loop dispatch pattern the Podracer report (arXiv:2104.06272)
identifies as the accelerator throughput killer. Here the storage IS device
memory: the env loop stages raw transitions on the host and flushes them as
ONE packed uint8 blob per step (the ``data/ring.py`` layout machinery), and
the train step appends + samples + updates in a single dispatch.

Layout and ownership:

- storage ``{key: (capacity, n_envs, *feat)}``, replicated over the ``dp``
  mesh or — when ``n_envs`` divides the device count — **sharded along the
  env axis** (per-device HBM = total / n_devices; each device samples its
  own batch shard from its own env shard, which is globally uniform because
  env shards are equal-sized);
- the write head (``pos``/``valid``), the train-key stream, and the PER
  sum-tree live ON DEVICE inside :attr:`state` and are advanced in-graph —
  the host keeps mirrors only for flush gating and ``Replay/*`` metrics;
- :attr:`state` is a plain pytree: the algo's jitted step takes it donated
  and returns the successor, so XLA reuses the ring buffers in place.

Checkpointing: :meth:`state_dict` pulls everything to host numpy inside a
:class:`DeviceReplayState` (picklable — it rides the existing ``state["rb"]``
sidecar through :class:`~sheeprl_tpu.fault.CheckpointManager`), and
:meth:`load_state_dict` re-uploads on resume.

Spillover: :func:`resolve_device_resident` sizes the ring against an HBM
budget; capacities that do not fit degrade gracefully to the host
:class:`~sheeprl_tpu.data.buffers.ReplayBuffer` path behind the same config
knob (``buffer.device_resident=auto``).
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.data.ring import BlobLayout, make_layout, pack_burst_blob, unpack_burst_blob
from sheeprl_tpu.replay import sumtree

__all__ = [
    "DeviceReplayBuffer",
    "DeviceReplayState",
    "resolve_device_resident",
    "restore_host_buffer",
    "restore_host_env_buffer",
    "estimate_ring_bytes",
]


def estimate_ring_bytes(
    specs: Dict[str, Tuple[tuple, Any]],
    capacity: int,
    n_envs: int,
    n_dev: int = 1,
    shard_envs: bool = False,
    prioritized: bool = False,
    sequence: Optional[Dict[str, int]] = None,
) -> int:
    """Per-device HBM footprint of a ring with the given storage spec.

    ``sequence`` switches on the per-env-head sequence-ring accounting (the
    Dreamer shape): beyond the flat storage rows, the footprint carries the
    per-env write heads + the device train-key, the per-position window
    validity working set the in-graph sampler materializes (a ``(capacity,
    n_envs)`` mask/start table, int32), and — the part that actually bites
    for pixel rings — the gathered ``(seq_len, batch)`` sample window each
    gradient step materializes in f32 after the uint8 decode. Pass
    ``{"seq_len": T, "batch_size": B}``; omitting it keeps the flat-row
    estimate (the SAC shape).
    """
    div = n_dev if shard_envs else 1
    total = 0
    row_bytes_f32 = 0
    for _k, (shape, dtype) in specs.items():
        feat = int(np.prod(shape or (1,)))
        total += capacity * (n_envs // div) * feat * np.dtype(dtype).itemsize
        row_bytes_f32 += feat * 4
    if prioritized:
        total += 2 * sumtree.leaf_count(capacity * n_envs) * 4
    if sequence is not None:
        seq_len = int(sequence["seq_len"])
        batch = int(sequence["batch_size"])
        # per-env heads (pos + valid, int32) + the device train-key
        total += n_envs * 2 * 4 + 8
        # window-validity working set: (capacity, n_envs) int32 masks/starts
        total += capacity * n_envs * 4
        # the gathered sample window, f32 after the in-graph uint8 decode
        total += seq_len * (batch // max(1, n_dev)) * row_bytes_f32
    return int(total)


def resolve_device_resident(
    setting: Any,
    specs: Dict[str, Tuple[tuple, Any]],
    capacity: int,
    n_envs: int,
    n_dev: int,
    hbm_budget_gb: float,
    prioritized: bool = False,
    allow_shard: bool = True,
    sequence: Optional[Dict[str, int]] = None,
) -> Tuple[bool, bool, str]:
    """Spillover decision: ``(use_device, shard_envs, reason)``.

    ``setting`` is the ``buffer.device_resident`` knob: ``False`` | ``True``
    | ``"auto"``. ``auto`` enables the device ring iff it fits the per-device
    HBM budget; an explicit ``True`` that does not fit **degrades to the host
    (memmap-capable) path with a warning** instead of OOMing at allocation —
    capacities beyond HBM are exactly what the host tier is for.

    ``sequence`` (``{"seq_len": T, "batch_size": B}``) switches the estimate
    to the per-env-head sequence-ring shape — heads, validity working set
    and the gathered f32 sample window, not just flat rows — so a Dreamer
    ring that only fits as flat rows cannot sneak past the gate and OOM at
    its first append (see :func:`estimate_ring_bytes`).
    """
    if isinstance(setting, str):
        setting = setting.strip().lower()
        if setting not in ("auto", "true", "false"):
            raise ValueError(f"buffer.device_resident must be true/false/auto, got '{setting}'")
        setting = {"auto": "auto", "true": True, "false": False}[setting]
    if setting is False:
        return False, False, "disabled by config"
    shard_envs = allow_shard and n_dev > 1 and n_envs % n_dev == 0 and not prioritized
    budget = float(hbm_budget_gb) * (1 << 30)
    est = estimate_ring_bytes(specs, capacity, n_envs, n_dev, shard_envs, prioritized, sequence=sequence)
    if est <= budget:
        return True, shard_envs, f"ring fits HBM budget ({est / 2**20:.1f} MiB <= {hbm_budget_gb} GiB)"
    reason = (
        f"device ring would need {est / 2**30:.2f} GiB/device "
        f"(budget buffer.hbm_budget_gb={hbm_budget_gb}); spilling to the host buffer"
    )
    if setting is True:
        warnings.warn(f"buffer.device_resident=true but {reason}")
    return False, False, reason


class DeviceReplayState:
    """Host-side snapshot of a device ring (the picklable checkpoint unit
    that rides ``state['rb']`` through the checkpoint sidecar)."""

    def __init__(self, kind: str, arrays: Dict[str, np.ndarray], meta: Dict[str, Any]) -> None:
        self.kind = kind  # "uniform" | "sequence"
        self.arrays = arrays
        self.meta = meta

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        keys = ", ".join(sorted(self.arrays))
        return f"DeviceReplayState(kind={self.kind!r}, arrays=[{keys}], meta={self.meta})"


class DeviceReplayBuffer:
    """Scalar-write-head device ring with in-graph uniform/PER sampling
    (the SAC-shaped buffer; the Dreamer families use the per-env-head
    sequence driver in :mod:`sheeprl_tpu.replay.driver`).

    The class owns allocation, host-side staging + packed-blob flushing,
    checkpoint state, and ``Replay/*`` metrics. The *sampling itself* is not
    a method: the algo's train-step builder composes the in-graph kernels
    (:mod:`sheeprl_tpu.replay.indices`, :mod:`sheeprl_tpu.replay.sumtree`)
    against :attr:`state`, so one dispatch covers append + sample + the whole
    granted chunk of gradient steps.
    """

    def __init__(
        self,
        fabric,
        specs: Dict[str, Tuple[tuple, Any]],
        capacity: int,
        n_envs: int,
        *,
        prioritized: bool = False,
        per_alpha: float = 0.6,
        per_eps: float = 1e-6,
        shard_envs: bool = False,
        stage_rows: int = 1,
        extra_spec: Sequence[Tuple[str, tuple, Any]] = (),
        seed: int = 0,
    ) -> None:
        if capacity <= 0 or n_envs <= 0:
            raise ValueError(f"need positive capacity/n_envs (got {capacity}, {n_envs})")
        n_dev = fabric.mesh.devices.size
        if shard_envs and n_envs % n_dev != 0:
            raise ValueError(f"shard_envs requires n_envs ({n_envs}) divisible by devices ({n_dev})")
        if shard_envs and prioritized:
            # the PER tree is replicated and kept in sync by all-gathering
            # leaf updates; a per-device tree over env shards would sample
            # each shard proportionally to its LOCAL mass, not the global one
            warnings.warn("prioritized replay requires replicated storage; disabling env sharding")
            shard_envs = False
        self.fabric = fabric
        self.specs = {k: (tuple(shape), jnp.dtype(dtype)) for k, (shape, dtype) in specs.items()}
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self.n_dev = int(n_dev)
        self.shard_envs = bool(shard_envs)
        self.local_envs = self.n_envs // (self.n_dev if self.shard_envs else 1)
        self.prioritized = bool(prioritized)
        self.per_alpha = float(per_alpha)
        self.per_eps = float(per_eps)
        self.stage_rows = int(stage_rows)
        self.tree_leaves = sumtree.leaf_count(self.capacity * self.n_envs) if prioritized else 0

        if self.stage_rows > self.capacity:
            raise ValueError(
                f"stage_rows ({self.stage_rows}) cannot exceed the ring capacity ({self.capacity})"
            )
        # One packed host→device transfer per flush (data/ring.py layouts).
        # Three layouts carve the same segment list for the two dispatch
        # topologies: the coupled fused step consumes `layout` (transitions +
        # control in one blob), the decoupled (Sebulba) pair consumes
        # `append_layout` (transitions only — packed by actor threads) and
        # `ctl_layout` (control segments only — packed by the learner at
        # train-dispatch time, when the grant governor knows them).
        base_spec = [(k, (self.stage_rows, self.n_envs) + shape, np.dtype(str(dtype)))
                     for k, (shape, dtype) in self.specs.items()]
        base_spec.append(("__count__", (), np.int32))
        extra = [(name, tuple(shape), np.dtype(dtype)) for name, shape, dtype in extra_spec]
        self.append_layout: BlobLayout = make_layout(base_spec)
        self.ctl_layout: Optional[BlobLayout] = make_layout(extra) if extra else None
        self.layout: BlobLayout = make_layout(base_spec + extra)

        self._storage_sharding = (
            fabric.sharding(None, "dp") if self.shard_envs else fabric.replicated
        )
        self.state = self._alloc(seed)

        # host mirrors: flush gating + metrics only (device owns the truth)
        self._pos = 0
        self._full = False
        self._staged: List[Dict[str, np.ndarray]] = []
        self._metrics = {
            "flushes": 0,
            "inserts": 0,
            "bytes_staged": 0,
            "insert_latency_s": 0.0,
            "dispatch_latency_s": 0.0,
        }

    # -- allocation ----------------------------------------------------------
    def _alloc(self, seed: int) -> Dict[str, Any]:
        fabric = self.fabric
        specs = self.specs
        rep = fabric.replicated

        # Materialize on device (a host zeros + device_put would allocate the
        # whole ring in host memory and copy it host→device — same rationale
        # as utils/burst.init_device_ring).
        def _zeros():
            state = {
                "storage": {
                    k: jnp.zeros((self.capacity, self.n_envs) + shape, dtype)
                    for k, (shape, dtype) in specs.items()
                },
                "pos": jnp.zeros((), jnp.int32),
                "valid": jnp.zeros((), jnp.int32),
                "key": jax.random.PRNGKey(seed),
            }
            if self.prioritized:
                state["tree"] = sumtree.init(self.capacity * self.n_envs)
                state["max_p"] = jnp.ones((), jnp.float32)
            return state

        shardings = jax.tree.map(lambda _: rep, jax.eval_shape(_zeros))
        for k in specs:
            shardings["storage"][k] = self._storage_sharding
        return jax.jit(_zeros, out_shardings=shardings)()

    # -- properties ----------------------------------------------------------
    @property
    def full(self) -> bool:
        return self._full

    @property
    def pos(self) -> int:
        return self._pos

    @property
    def valid_rows(self) -> int:
        return self.capacity if self._full else self._pos

    @property
    def empty(self) -> bool:
        return self.valid_rows == 0 and not self._staged

    def __len__(self) -> int:
        return self.capacity

    # -- staging + flush -----------------------------------------------------
    def add(self, step_data: Dict[str, np.ndarray]) -> None:
        """Stage one ``(1, n_envs, ...)`` transition row for the next flush."""
        if len(self._staged) >= self.stage_rows:
            raise RuntimeError(
                f"staging area holds {self.stage_rows} row(s); flush (make_job) before adding more"
            )
        row = {}
        for k, (shape, dtype) in self.specs.items():
            row[k] = np.asarray(step_data[k], dtype=np.dtype(str(dtype))).reshape(
                (self.n_envs,) + shape
            )
        self._staged.append(row)
        self._metrics["inserts"] += self.n_envs

    def _advance_head(self, count: int) -> None:
        """Shared wrap rule for the host head mirrors (same as the host
        buffer, data/buffers.py:154-156)."""
        if self._pos + count >= self.capacity:
            self._full = True
        self._pos = (self._pos + count) % self.capacity

    def _stack_rows(self, rows: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
        """Zero-filled ``(stage_rows, n_envs, ...)`` segment dict (+ the row
        count) from a list of transition rows — the shared packing body of
        :meth:`make_job` and :meth:`pack_rows`."""
        values: Dict[str, np.ndarray] = {}
        for k, (shape, dtype) in self.specs.items():
            arr = np.zeros((self.stage_rows, self.n_envs) + shape, np.dtype(str(dtype)))
            for i, row in enumerate(rows):
                arr[i] = np.asarray(row[k], dtype=np.dtype(str(dtype))).reshape(
                    (self.n_envs,) + shape
                )
            values[k] = arr
        values["__count__"] = np.asarray(len(rows), np.int32)
        return values

    def make_job(self, extras: Optional[Dict[str, np.ndarray]] = None) -> jax.Array:
        """Pack the staged rows (possibly zero — backlog-drain dispatches
        append nothing) plus the caller's extra segments into ONE uint8 blob,
        stage it on the mesh (replicated) with an EXPLICIT transfer, and
        advance the host head mirrors. Explicit staging (vs. handing numpy to
        the fused dispatch) keeps the steady state clean under
        ``jax.transfer_guard("disallow")`` and lets the copy overlap the rest
        of the host loop instead of riding the dispatch."""
        t0 = time.perf_counter()
        count = len(self._staged)
        values = self._stack_rows(self._staged)
        for k, v in (extras or {}).items():
            values[k] = v
        self._staged.clear()
        blob = self.fabric.put_replicated(pack_burst_blob(self.layout, values))
        self._advance_head(count)
        self._metrics["flushes"] += 1
        self._metrics["bytes_staged"] += int(blob.nbytes)
        self._metrics["insert_latency_s"] += time.perf_counter() - t0
        return blob

    # -- decoupled (Sebulba) append/train dispatch pair ----------------------
    def pack_rows(self, rows: Sequence[Dict[str, np.ndarray]]) -> np.ndarray:
        """Pack up to ``stage_rows`` transition rows (each ``(n_envs, ...)``)
        into one append blob for :meth:`make_append_step`.

        Unlike :meth:`add`/:meth:`make_job` this is a pure function of its
        argument — nothing on ``self`` is touched — so CONCURRENT actor
        threads can each pack their own blob (the single-writer learner
        advances the host mirrors via :meth:`note_append` when it consumes
        one). Returns a host uint8 array; the caller stages it on the mesh
        (``fabric.put_replicated``) from its own thread, off the learner's
        critical path."""
        if len(rows) > self.stage_rows:
            raise ValueError(
                f"{len(rows)} rows exceed the append blob capacity (stage_rows={self.stage_rows})"
            )
        return pack_burst_blob(self.append_layout, self._stack_rows(rows))

    def note_append(self, count: int) -> None:
        """Advance the host head mirrors for one consumed append blob (the
        learner-side bookkeeping twin of :meth:`make_job`'s tail)."""
        count = int(count)
        if count <= 0:
            return
        self._advance_head(count)
        self._metrics["flushes"] += 1
        self._metrics["inserts"] += count * self.n_envs
        self._metrics["bytes_staged"] += int(self.append_layout.nbytes)

    def make_ctl_job(self, extras: Dict[str, np.ndarray]) -> jax.Array:
        """Pack ONLY the control segments (``extra_spec``) and stage them on
        the mesh — the append-free train step's per-dispatch input."""
        if self.ctl_layout is None:
            raise RuntimeError(
                "DeviceReplayBuffer was built without extra_spec control segments"
            )
        return self.fabric.put_replicated(pack_burst_blob(self.ctl_layout, dict(extras)))

    def make_append_step(self, donate: bool = True):
        """Build the jitted multi-row append program for the decoupled
        (Sebulba) topology: ``fn(rb_state, blob) -> rb_state``.

        ``blob`` is an :meth:`pack_rows` blob already staged on the mesh. Up
        to ``stage_rows`` rows are scattered at the write head in ONE
        donated in-place dispatch (rows past ``__count__`` target index
        ``capacity`` and are dropped); with PER enabled, fresh transitions
        enter the sum-tree at the running max priority. Sampling stays with
        the train step — the learner thread owns both dispatches, so the
        ring never has two writers in flight."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        capacity = self.capacity
        rows = self.stage_rows
        n_envs = self.n_envs
        prioritized = self.prioritized
        layout = self.append_layout
        specs = self.specs

        def local_append(storage, pos, vld, tree, max_p, staged, count):
            real_idx = (pos + jnp.arange(rows)) % capacity
            idx = jnp.where(jnp.arange(rows) < count, real_idx, capacity)
            storage = {k: storage[k].at[idx].set(staged[k], mode="drop") for k in storage}
            new_pos = (pos + count) % capacity
            new_vld = jnp.minimum(vld + count, capacity)
            if prioritized:
                # fresh rows enter at the running max priority; padding rows
                # rewrite their current value (a value-level no-op)
                leaves = (
                    real_idx[:, None] * n_envs + jnp.arange(n_envs, dtype=real_idx.dtype)[None, :]
                ).reshape(-1)
                row_valid = jnp.repeat(jnp.arange(rows) < count, n_envs)
                prio = jnp.where(row_valid, max_p, sumtree.get(tree, leaves))
                tree = sumtree.update(tree, leaves, prio)
            return storage, new_pos, new_vld, tree, max_p

        storage_spec = P(None, "dp") if self.shard_envs else P()
        shard_append = shard_map(
            local_append,
            mesh=self.fabric.mesh,
            in_specs=(storage_spec, P(), P(), P(), P(), storage_spec, P()),
            out_specs=(storage_spec, P(), P(), P(), P()),
            check_vma=False,
        )

        def packed_append(rb_state, blob):
            u = unpack_burst_blob(blob, layout)
            staged = {k: u[k] for k in specs}
            tree = rb_state.get("tree", jnp.zeros((2,), jnp.float32))
            max_p = rb_state.get("max_p", jnp.ones((), jnp.float32))
            storage, pos, vld, tree, max_p = shard_append(
                rb_state["storage"], rb_state["pos"], rb_state["valid"], tree, max_p,
                staged, u["__count__"],
            )
            new_state = {"storage": storage, "pos": pos, "valid": vld, "key": rb_state["key"]}
            if prioritized:
                new_state["tree"] = tree
                new_state["max_p"] = max_p
            return new_state

        # Pin the fed-back ring state's placements: the (possibly env-
        # sharded) storage is donated and fed back EVERY append — left to
        # inference, jit may canonicalize it to an equivalent placement with
        # a different C++ jit-cache key and silently recompile on the next
        # dispatch (graft-lint GL008 / graft-audit AUD002, the PR 8 class).
        from jax.sharding import NamedSharding

        rep_out = NamedSharding(self.fabric.mesh, P())
        state_out = {
            "storage": NamedSharding(self.fabric.mesh, storage_spec),
            "pos": rep_out,
            "valid": rep_out,
            "key": rep_out,
        }
        if prioritized:
            state_out.update(tree=rep_out, max_p=rep_out)
        return jax.jit(
            packed_append, donate_argnums=(0,) if donate else (), out_shardings=state_out
        )

    def note_dispatch_latency(self, seconds: float) -> None:
        """Wall time of the fused append+sample+train dispatch (the whole
        program — sampling is in-graph and has no separable host cost)."""
        self._metrics["dispatch_latency_s"] += float(seconds)

    def metrics(self) -> Dict[str, float]:
        """``Replay/*`` metric dict for ``logger.log_dict``."""
        return {
            "Replay/occupancy": self.valid_rows / self.capacity,
            "Replay/size": self.valid_rows * self.n_envs,
            "Replay/flushes": self._metrics["flushes"],
            "Replay/inserts": self._metrics["inserts"],
            "Replay/bytes_staged": self._metrics["bytes_staged"],
            "Replay/insert_latency_s": round(self._metrics["insert_latency_s"], 4),
            "Replay/dispatch_latency_s": round(self._metrics["dispatch_latency_s"], 4),
        }

    # -- checkpoint ----------------------------------------------------------
    def state_dict(self) -> DeviceReplayState:
        """Pull the ring to host (one pipelined transfer) for checkpointing.
        Call with an empty staging area (the mains flush every iteration)."""
        if self._staged:
            raise RuntimeError("checkpointing with staged-but-unflushed rows would drop them")
        host = jax.device_get(self.state)
        arrays = {f"storage/{k}": np.asarray(v) for k, v in host["storage"].items()}
        for k in ("pos", "valid", "key", "tree", "max_p"):
            if k in host:
                arrays[k] = np.asarray(host[k])
        meta = {
            "capacity": self.capacity,
            "n_envs": self.n_envs,
            "prioritized": self.prioritized,
            "host_pos": self._pos,
            "host_full": self._full,
            "metrics": dict(self._metrics),
        }
        return DeviceReplayState("uniform", arrays, meta)

    def load_state_dict(self, snap: DeviceReplayState) -> "DeviceReplayBuffer":
        if snap.kind != "uniform":
            raise ValueError(f"cannot restore a '{snap.kind}' replay snapshot into DeviceReplayBuffer")
        if snap.meta["capacity"] != self.capacity or snap.meta["n_envs"] != self.n_envs:
            raise ValueError(
                f"replay snapshot shape mismatch: checkpoint ({snap.meta['capacity']}, "
                f"{snap.meta['n_envs']}) vs configured ({self.capacity}, {self.n_envs})"
            )
        state: Dict[str, Any] = {"storage": {}}
        for k in self.specs:
            state["storage"][k] = jax.device_put(snap.arrays[f"storage/{k}"], self._storage_sharding)
        rep = self.fabric.replicated
        for k in ("pos", "valid", "key", "tree", "max_p"):
            if k in snap.arrays:
                state[k] = jax.device_put(jnp.asarray(snap.arrays[k]), rep)
        self.state = state
        self._pos = int(snap.meta["host_pos"])
        self._full = bool(snap.meta["host_full"])
        self._metrics.update(snap.meta.get("metrics", {}))
        return self

    def load_host_buffer(self, rb) -> "DeviceReplayBuffer":
        """Mirror a restored host ``ReplayBuffer`` into the ring (resuming a
        host-tier checkpoint into resident mode). PER priorities are not in
        the host checkpoint, so filled slots restart at uniform priority."""
        if rb.empty:
            return self
        if rb.buffer_size != self.capacity or rb.n_envs != self.n_envs:
            raise ValueError(
                f"host buffer shape ({rb.buffer_size}, {rb.n_envs}) does not match the "
                f"device ring ({self.capacity}, {self.n_envs})"
            )
        state: Dict[str, Any] = {"storage": {}, "key": self.state["key"]}
        for k, (shape, dtype) in self.specs.items():
            host = np.asarray(rb.buffer[k], dtype=np.dtype(str(dtype))).reshape(
                (self.capacity, self.n_envs) + shape
            )
            state["storage"][k] = jax.device_put(host, self._storage_sharding)
        pos, full = rb._pos, rb.full
        valid = self.capacity if full else pos
        rep = self.fabric.replicated
        state["pos"] = jax.device_put(jnp.asarray(pos, jnp.int32), rep)
        state["valid"] = jax.device_put(jnp.asarray(valid, jnp.int32), rep)
        if self.prioritized:
            P = self.tree_leaves
            tree = np.zeros(2 * P, np.float32)
            # row-major (row, env) flattening: rows [0, valid) are exactly
            # the first valid * n_envs leaves
            tree[P : P + valid * self.n_envs] = 1.0
            w = P // 2
            while w >= 1:
                tree[w : 2 * w] = tree[2 * w : 4 * w].reshape(w, 2).sum(axis=-1)
                w //= 2
            state["tree"] = jax.device_put(jnp.asarray(tree), rep)
            state["max_p"] = jax.device_put(jnp.ones((), jnp.float32), rep)
        self.state = state
        self._pos = int(pos)
        self._full = bool(full)
        return self


def _assign_host_key(rb, key: str, arr: np.ndarray) -> None:
    """Install one storage array into a host ``ReplayBuffer``, honoring its
    memmap backing: a memmap-configured buffer gets a disk-backed
    ``MemmapArray`` (same layout its own lazy ``add`` allocation would
    build), not an in-RAM copy that would defeat the spillover tier's whole
    point. Ring dtypes are kept (the ring stores e.g. ``terminated`` as
    float32 where the host loop writes uint8 — later adds cast in,
    value-preserving)."""
    if rb._memmap:
        from pathlib import Path

        from sheeprl_tpu.data.memmap import MemmapArray

        mm = MemmapArray(
            dtype=arr.dtype,
            shape=arr.shape,
            filename=Path(rb._memmap_dir) / f"{key}.memmap",
            mode=rb._memmap_mode,
        )
        mm[:] = arr
        rb._buf[key] = mm
    else:
        rb._buf[key] = np.array(arr)


def restore_host_buffer(snap: DeviceReplayState, rb, fill_missing: Optional[Dict[str, Tuple[tuple, Any]]] = None) -> None:
    """Fill a host ``ReplayBuffer`` from a resident checkpoint snapshot (the
    resume-into-host-tier crossover: knob flipped off, spillover kicked in,
    or the hybrid burst path taking over). ``fill_missing`` zero-allocates
    keys the host loop writes but the ring never stored (e.g. SAC's
    ``truncated``), so later ``add`` calls find a congruent storage dict."""
    if snap.kind != "uniform":
        raise ValueError(f"cannot restore a '{snap.kind}' replay snapshot into a flat host buffer")
    cap, n_envs = int(snap.meta["capacity"]), int(snap.meta["n_envs"])
    if cap != rb.buffer_size or n_envs != rb.n_envs:
        raise ValueError(
            f"replay snapshot shape ({cap}, {n_envs}) does not match the host buffer "
            f"({rb.buffer_size}, {rb.n_envs})"
        )
    for name, arr in snap.arrays.items():
        if name.startswith("storage/"):
            _assign_host_key(rb, name[len("storage/") :], np.asarray(arr))
    for k, (shape, dtype) in (fill_missing or {}).items():
        if k not in rb._buf:
            _assign_host_key(rb, k, np.zeros((cap, n_envs) + tuple(shape), dtype))
    rb._pos = int(snap.meta["host_pos"])
    rb._full = bool(snap.meta["host_full"])


def restore_host_env_buffer(snap: DeviceReplayState, rb, fill_missing: Optional[Dict[str, Tuple[tuple, Any]]] = None) -> None:
    """Fill a host ``EnvIndependentReplayBuffer`` from a resident *sequence*
    ring snapshot (the Dreamer-side resume-into-host-tier crossover). Each
    env's column becomes its sub-buffer's storage, and the per-env write
    heads carry over, so sequential-window sampling resumes with identical
    validity semantics."""
    if snap.kind != "sequence":
        raise ValueError(f"cannot restore a '{snap.kind}' replay snapshot into per-env host buffers")
    cap, n_envs = int(snap.meta["capacity"]), int(snap.meta["n_envs"])
    if cap != rb.buffer_size or n_envs != rb.n_envs:
        raise ValueError(
            f"replay snapshot shape ({cap}, {n_envs}) does not match the host buffer "
            f"({rb.buffer_size}, {rb.n_envs})"
        )
    pos = np.asarray(snap.arrays["pos"])
    valid = np.asarray(snap.arrays["valid"])
    for e, sub in enumerate(rb.buffer):
        for name, arr in snap.arrays.items():
            if name.startswith("storage/"):
                _assign_host_key(sub, name[len("storage/") :], np.asarray(arr[:, e : e + 1]))
        for k, (shape, dtype) in (fill_missing or {}).items():
            if k not in sub._buf:
                _assign_host_key(sub, k, np.zeros((cap, 1) + tuple(shape), dtype))
        sub._pos = int(pos[e])
        sub._full = bool(valid[e] >= cap)
