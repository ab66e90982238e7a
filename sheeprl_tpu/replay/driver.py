"""The decoupled (Sebulba) device sequence ring of the Dreamer family:
:class:`AsyncSequenceRing` (storage, per-env write heads and the train-key
stream on the device; actors pack ragged blobs, the one learner commits them)
and :class:`SeqBlobWriter` (the actor's zero-copy blob writer). The coupled
mains keep their device ring behind ``utils/burst.py`` (the burst topology of
``algos/world_model_loop.py``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from sheeprl_tpu.data.ring import (
    build_seq_append_step,
    env_view,
    pack_burst_blob,
    ring_view,
)
from sheeprl_tpu.replay.device_buffer import DeviceReplayState
from sheeprl_tpu.utils.burst import init_device_ring

__all__ = ["AsyncSequenceRing", "SeqBlobWriter"]


def _storage_to_host(storage, ring_keys) -> Dict[str, np.ndarray]:
    """The ring's keys as a checkpoint holds them: on the host, in the env's
    shapes (``(capacity, n_envs) + shape``, the format from before the ring
    was stored in its cell view, so snapshots of either age load and
    ``restore_host_env_buffer`` reads them as they are)."""
    return {f"storage/{k}": env_view(v, ring_keys[k][0]) for k, v in jax.device_get(storage).items()}


def _storage_from_host(fabric, snap: DeviceReplayState, ring_keys) -> Dict[str, Any]:
    """A snapshot's keys back onto the device, reshaped on the host to the
    stored view (``data.ring.ring_cell``)."""
    return {
        k: fabric.put_replicated(ring_view(np.asarray(snap.arrays[f"storage/{k}"]), shape))
        for k, (shape, _dtype) in ring_keys.items()
    }


class AsyncSequenceRing:
    """Decoupled (Sebulba) per-env-head sequence ring for the Dreamer family.

    This ring serves CONCURRENT actor threads: the storage, the per-env write
    heads, and the train-key stream all live ON DEVICE in :attr:`state`;
    actors :meth:`pack_rows` their per-env sequence heads into ragged uint8
    blobs (a pure function — nothing on ``self`` is touched, so N writers
    never race), and the single-writer learner commits each blob with ONE
    donated ragged multi-head scatter (:meth:`append`) and trains at its own
    cadence through the append-free program
    (:func:`sheeprl_tpu.data.ring.build_seq_train_step`), sampling windows
    in-graph against the live per-env head validity.

    The host keeps ``pos``/``valid`` mirrors only for grant gating (no
    dispatch may sample while any env is shorter than a window) and
    ``Replay/*`` metrics; the device owns the truth, exactly like
    :class:`~sheeprl_tpu.replay.device_buffer.DeviceReplayBuffer`.
    """

    def __init__(
        self,
        fabric,
        ring_keys: Dict[str, Tuple[tuple, Any]],
        capacity: int,
        n_envs: int,
        local_envs: int,
        seq_len: int,
        stage_rows: int,
        seed: int = 0,
    ) -> None:
        if n_envs % local_envs != 0:
            raise ValueError(
                f"ring env columns ({n_envs}) must be a multiple of the per-actor env batch ({local_envs})"
            )
        self.fabric = fabric
        self.ring_keys = {k: (tuple(shape), jax.numpy.dtype(dtype)) for k, (shape, dtype) in ring_keys.items()}
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self.local_envs = int(local_envs)
        self.seq_len = int(seq_len)
        self.stage_rows = int(stage_rows)
        if self.stage_rows > self.capacity:
            raise ValueError(
                f"stage_rows ({self.stage_rows}) cannot exceed the ring capacity ({self.capacity})"
            )

        self._append_fn, self.append_layout = build_seq_append_step(
            fabric.mesh, self.ring_keys, self.capacity, self.n_envs, self.local_envs, self.stage_rows
        )

        storage, _pos, _valid = init_device_ring(fabric, self.ring_keys, self.capacity, self.n_envs)
        rep = fabric.replicated
        self.state: Dict[str, Any] = {
            "storage": storage,
            "pos": jax.device_put(jax.numpy.zeros((self.n_envs,), jax.numpy.int32), rep),
            "valid": jax.device_put(jax.numpy.zeros((self.n_envs,), jax.numpy.int32), rep),
            "key": jax.device_put(jax.random.PRNGKey(seed), rep),
        }
        # host mirrors: grant gating + metrics only
        self.host_pos = np.zeros(self.n_envs, np.int64)
        self.host_valid = np.zeros(self.n_envs, np.int64)
        self._metrics = {"flushes": 0, "bytes_staged": 0, "dispatch_latency_s": 0.0}

    def instrument_append(self, name: str) -> None:
        """Wrap the append program with a tracecheck entry (one blob bucket =
        one abstract signature)."""
        from sheeprl_tpu.analysis.tracecheck import tracecheck

        self._append_fn = tracecheck.instrument(self._append_fn, name=name, warmup=1)

    # -- actor side (pure) ---------------------------------------------------
    def pack_rows(
        self, rows: List[Tuple[Dict[str, np.ndarray], np.ndarray]], env_offset: int
    ) -> np.ndarray:
        """Pack one actor's staged ``(row dict, env mask)`` pairs — regular
        all-env rows plus ragged reset rows — into ONE append blob. PURE:
        concurrent actor threads each pack their own blob; the learner is the
        ring's only writer. ``env_offset`` is the actor's first env column in
        the full ring."""
        if len(rows) > self.stage_rows:
            raise ValueError(
                f"{len(rows)} rows exceed the append blob capacity (stage_rows={self.stage_rows})"
            )
        values: Dict[str, np.ndarray] = {}
        for k, (shape, dtype) in self.ring_keys.items():
            arr = np.zeros((self.stage_rows, self.local_envs) + shape, np.dtype(str(dtype)))
            for i, (row, _m) in enumerate(rows):
                arr[i] = np.asarray(row[k], dtype=arr.dtype).reshape((self.local_envs,) + shape)
            values[k] = arr
        mask = np.zeros((self.stage_rows, self.local_envs), np.int32)
        for i, (_r, m) in enumerate(rows):
            mask[i] = m
        values["__mask__"] = mask
        values["__offset__"] = np.asarray(int(env_offset), np.int32)
        return pack_burst_blob(self.append_layout, values)

    # -- learner side --------------------------------------------------------
    def append(self, blob) -> None:
        """Commit one staged-on-mesh append blob: the donated ragged
        multi-head scatter dispatch. Host head mirrors advance via
        :meth:`note_append` (the caller knows the per-env counts from the
        queue item — the blob is already on device)."""
        t0 = time.perf_counter()
        self.state = self._append_fn(self.state, blob)
        self._metrics["dispatch_latency_s"] += time.perf_counter() - t0

    def set_key(self, new_key) -> None:
        """Splice the train dispatch's advanced train-key back into the ring
        state (the only piece of ring state the append-free train program
        changes — see :func:`sheeprl_tpu.data.ring.build_seq_train_step`)."""
        self.state = {**self.state, "key": new_key}

    def note_append(self, env_counts: np.ndarray, blob_bytes: int) -> None:
        """Advance the host head mirrors for one committed blob."""
        counts = np.asarray(env_counts, np.int64)
        self.host_pos[:] = (self.host_pos + counts) % self.capacity
        self.host_valid[:] = np.minimum(self.host_valid + counts, self.capacity)
        self._metrics["flushes"] += 1
        self._metrics["bytes_staged"] += int(blob_bytes)

    def ready(self) -> bool:
        """Grant gate: every env column can host at least one sample window
        (the host buffer refuses to sample before that)."""
        return bool(self.host_valid.min() >= self.seq_len)

    def metrics(self) -> Dict[str, float]:
        return {
            "Replay/occupancy": float(self.host_valid.sum()) / (self.capacity * self.n_envs),
            "Replay/size": int(self.host_valid.sum()),
            "Replay/flushes": self._metrics["flushes"],
            "Replay/bytes_staged": self._metrics["bytes_staged"],
            "Replay/dispatch_latency_s": round(self._metrics["dispatch_latency_s"], 4),
        }

    # -- checkpoint ----------------------------------------------------------
    def state_dict(self) -> DeviceReplayState:
        host = jax.device_get(self.state)
        arrays = _storage_to_host(host["storage"], self.ring_keys)
        arrays["pos"] = np.asarray(host["pos"])
        arrays["valid"] = np.asarray(host["valid"])
        arrays["key"] = np.asarray(host["key"])
        meta = {"capacity": self.capacity, "n_envs": self.n_envs, "seq_len": self.seq_len}
        return DeviceReplayState("sequence", arrays, meta)

    def load_state_dict(self, snap: DeviceReplayState) -> "AsyncSequenceRing":
        if snap.kind != "sequence":
            raise ValueError(f"cannot restore a '{snap.kind}' replay snapshot into AsyncSequenceRing")
        if snap.meta["capacity"] != self.capacity or snap.meta["n_envs"] != self.n_envs:
            raise ValueError(
                f"replay snapshot shape mismatch: checkpoint ({snap.meta['capacity']}, "
                f"{snap.meta['n_envs']}) vs configured ({self.capacity}, {self.n_envs})"
            )
        rep = self.fabric.replicated
        self.state = {
            "storage": _storage_from_host(self.fabric, snap, self.ring_keys),
            "pos": jax.device_put(jax.numpy.asarray(snap.arrays["pos"], jax.numpy.int32), rep),
            "valid": jax.device_put(jax.numpy.asarray(snap.arrays["valid"], jax.numpy.int32), rep),
            "key": jax.device_put(jax.numpy.asarray(snap.arrays["key"]), rep),
        }
        self.host_pos = np.asarray(snap.arrays["pos"], np.int64).copy()
        self.host_valid = np.asarray(snap.arrays["valid"], np.int64).copy()
        return self


class SeqBlobWriter:
    """Write-through staging for ONE actor's append blobs.

    The blob ring's segments are exposed as numpy VIEWS into preallocated
    blob byte buffers, so the actor's env loop writes each row's data
    straight into the upload bytes — no per-step row dicts, no pack-time
    copy (the :meth:`DoubleBufferedStager.acquire` idiom applied to the
    ragged append blob; one copy instead of three). Unwritten row slots
    carry stale bytes from an earlier block, which is safe by construction:
    a slot's write mask is zeroed at :meth:`begin`, and the append program
    drops every (row, env) cell whose mask is 0 — stale bytes ride the wire
    but never reach the ring.

    The slot ring exists for correctness, not reuse: on the CPU backend
    ``device_put`` of an aligned numpy array can be ZERO-COPY, so a shipped
    blob may alias its buffer while the queue/learner/XLA still read it —
    size ``slots`` at ``queue_depth + 4`` (queued + the shipped blob the
    actor holds while BLOCKED in ``rollout_q.put`` + learner-dispatched +
    XLA-executing + actor-filling), the DoubleBufferedStager rule plus the
    back-pressured producer's own handle.
    """

    def __init__(self, ring: "AsyncSequenceRing", env_offset: int, slots: int = 6) -> None:
        self.layout = ring.append_layout
        self.local_envs = ring.local_envs
        self.stage_rows = ring.stage_rows
        self._slots = []
        for _ in range(max(2, int(slots))):
            blob = np.zeros(self.layout.nbytes, np.uint8)
            views = {
                name: np.ndarray(shape, dtype, buffer=blob, offset=off)
                for name, off, shape, dtype in self.layout.segments
            }
            views["__offset__"][...] = int(env_offset)
            self._slots.append((blob, views))
        self._idx = 0
        self._blob: Optional[np.ndarray] = None
        self._views: Optional[Dict[str, np.ndarray]] = None
        self._n = 0
        self.begin()

    def begin(self) -> None:
        """Start filling the next slot (mask zeroed, row cursor reset)."""
        self._blob, self._views = self._slots[self._idx]
        self._idx = (self._idx + 1) % len(self._slots)
        self._views["__mask__"][:] = 0
        self._n = 0

    @property
    def rows(self) -> int:
        return self._n

    def row(self, env_mask) -> Dict[str, np.ndarray]:
        """Claim the next row slot: sets its write mask and returns per-key
        ``(local_envs, ...)`` views to write the row's data into."""
        if self._n >= self.stage_rows:
            raise RuntimeError(
                f"append blob holds {self.stage_rows} row slot(s); ship before staging more"
            )
        i = self._n
        self._n += 1
        self._views["__mask__"][i] = env_mask
        return {k: v[i] for k, v in self._views.items() if not k.startswith("__")}

    def ship(self) -> tuple:
        """Finish the blob: returns ``(blob bytes, per-local-env counts)``
        and rotates to the next slot. The caller stages the bytes on the
        mesh (``fabric.put_replicated``) from its own thread."""
        blob = self._blob
        counts = self._views["__mask__"].sum(axis=0).astype(np.int64)
        self.begin()
        return blob, counts
