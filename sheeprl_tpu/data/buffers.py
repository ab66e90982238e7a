"""Host-side replay buffers feeding the device input pipeline.

Capability parity with the reference's data layer
(``sheeprl/data/buffers.py:20-1157``): dict-of-ndarray ring buffers shaped
``(buffer_size, n_envs, ...)``, sequential-window sampling, per-env
independent buffers, and an episode store — all living in host RAM (or
memmapped to disk) as in the reference, because env interaction is a host
concern. The TPU-specific pieces: :func:`put_packed` ships a whole sample
dict to device as ONE pipelined sharded transfer (the algo hot-path entry,
replacing torch conversion), with :func:`to_device` as its single-array
basis; fully device-resident replay — storage in HBM, sampling in-graph —
lives in :mod:`sheeprl_tpu.replay`.

All add/sample index semantics (wrap-around, write-head exclusion, next-obs
shifting, sequence validity, episode eviction, prioritize_ends) deliberately
match the reference so sample-efficiency comparisons hold.
"""

from __future__ import annotations

import logging
import os
import shutil
import uuid
from itertools import compress
from pathlib import Path
from typing import Any, Dict, Optional, Sequence, Type

import numpy as np

from sheeprl_tpu.data.memmap import MemmapArray

__all__ = [
    "ReplayBuffer",
    "SequentialReplayBuffer",
    "EnvIndependentReplayBuffer",
    "EpisodeBuffer",
    "to_device",
    "put_packed",
]

_MEMMAP_MODES = ("r+", "w+", "c", "copyonwrite", "readwrite", "write")


def _normalize_host(array: np.ndarray | MemmapArray, dtype: Any = None, clone: bool = False) -> np.ndarray:
    """The host-side placement rules shared by :func:`to_device` and
    :func:`put_packed`: memmap unwrap, optional cast, float64 downcast."""
    if isinstance(array, MemmapArray):
        array = array.array
    if clone:
        array = np.array(array)
    if dtype is not None:
        array = np.asarray(array, dtype=dtype)
    array = np.asarray(array)
    if array.dtype == np.float64:
        array = array.astype(np.float32)
    return array


def to_device(array: np.ndarray | MemmapArray, dtype: Any = None, sharding: Any = None, clone: bool = False):
    """Move ONE host array onto the accelerator (replaces ``get_tensor``,
    reference: ``buffers.py:1158-1180``). Algo hot paths ship whole sample
    dicts with :func:`put_packed` instead — one pipelined transfer, not one
    dispatch per key."""
    import jax
    import jax.numpy as jnp

    array = _normalize_host(array, dtype=dtype, clone=clone)
    if sharding is not None:
        return jax.device_put(array, sharding)
    return jnp.asarray(array)


def put_packed(samples: Dict[str, Any], sharding: Any = None, dtype: Any = None) -> Dict[str, Any]:
    """Ship a whole sample dict in ONE ``jax.device_put`` (the PR-3 stager
    trick, ``parallel/pipeline.py``): every key is normalized with
    :func:`to_device`'s host-side rules, then the dict goes up as a single
    pipelined sharded transfer instead of K per-key dispatches, each with its
    own per-transfer latency."""
    import jax

    host = {k: _normalize_host(v, dtype=dtype) for k, v in samples.items()}
    return jax.device_put(host, sharding)


class ReplayBuffer:
    """Uniform ring buffer (reference: ``buffers.py:20-361``)."""

    batch_axis: int = 1

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        memmap: bool = False,
        memmap_dir: str | os.PathLike | None = None,
        memmap_mode: str = "r+",
        **kwargs: Any,
    ):
        if buffer_size <= 0:
            raise ValueError(f"buffer_size must be a positive integer (got {buffer_size})")
        if n_envs <= 0:
            raise ValueError(f"n_envs must be a positive integer (got {n_envs})")
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._obs_keys = tuple(obs_keys)
        self._memmap = memmap
        self._memmap_dir = memmap_dir
        self._memmap_mode = memmap_mode
        self._buf: Dict[str, np.ndarray | MemmapArray] = {}
        if self._memmap:
            if self._memmap_mode not in _MEMMAP_MODES:
                raise ValueError(f"Accepted values for memmap_mode are {_MEMMAP_MODES}, got '{memmap_mode}'")
            if self._memmap_dir is None:
                raise ValueError("memmap=True requires a 'memmap_dir'")
            self._memmap_dir = Path(self._memmap_dir)
            self._memmap_dir.mkdir(parents=True, exist_ok=True)
        self._pos = 0
        self._full = False
        self._rng: np.random.Generator = np.random.default_rng()

    # -- properties ----------------------------------------------------------
    @property
    def buffer(self) -> Dict[str, np.ndarray]:
        return self._buf

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def full(self) -> bool:
        return self._full

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def empty(self) -> bool:
        return len(self._buf) == 0

    @property
    def is_memmap(self) -> bool:
        return self._memmap

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)

    # -- add -----------------------------------------------------------------
    def add(self, data: "ReplayBuffer" | Dict[str, np.ndarray], validate_args: bool = False) -> None:
        """Write ``(seq_len, n_envs, ...)`` rows at the head with wrap-around
        (reference index semantics: ``buffers.py:193-221``)."""
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if validate_args:
            self._validate_add(data)
        data_len = next(iter(data.values())).shape[0]
        next_pos = (self._pos + data_len) % self._buffer_size
        if next_pos <= self._pos or (data_len > self._buffer_size and not self._full):
            idxes = np.array(list(range(self._pos, self._buffer_size)) + list(range(0, next_pos)))
        else:
            idxes = np.arange(self._pos, next_pos)
        if data_len > self._buffer_size:
            data_to_store = {k: v[-self._buffer_size - next_pos :] for k, v in data.items()}
        else:
            data_to_store = data
        if self.empty:
            for k, v in data_to_store.items():
                shape = (self._buffer_size, self._n_envs, *v.shape[2:])
                if self._memmap:
                    self._buf[k] = MemmapArray(
                        dtype=v.dtype, shape=shape, filename=Path(self._memmap_dir) / f"{k}.memmap",
                        mode=self._memmap_mode,
                    )
                else:
                    self._buf[k] = np.empty(shape=shape, dtype=v.dtype)
        for k, v in data_to_store.items():
            self._buf[k][idxes] = v
        if self._pos + data_len >= self._buffer_size:
            self._full = True
        self._pos = next_pos

    def _validate_add(self, data: Any) -> None:
        if not isinstance(data, dict):
            raise ValueError(f"'data' must be a dictionary of numpy arrays, got '{type(data)}'")
        shapes = {}
        for k, v in data.items():
            if not isinstance(v, np.ndarray):
                raise ValueError(f"'data' must contain numpy arrays; key '{k}' has type '{type(v)}'")
            if v.ndim < 2:
                raise RuntimeError(
                    f"'data' must have at least 2 dimensions: [sequence_length, n_envs, ...]. Shape of '{k}' is {v.shape}"
                )
            shapes[k] = v.shape[:2]
        if len(set(shapes.values())) > 1:
            raise RuntimeError(f"Every array in 'data' must be congruent in the first 2 dimensions: {shapes}")

    # -- sample --------------------------------------------------------------
    def sample(
        self, batch_size: int, sample_next_obs: bool = False, clone: bool = False, n_samples: int = 1, **kwargs: Any
    ) -> Dict[str, np.ndarray]:
        """Uniform sample of ``(n_samples, batch_size, ...)`` transitions,
        excluding the write head when full and shifting indices for next-obs
        (reference: ``buffers.py:223-288``)."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"need positive batch_size and n_samples (got batch_size={batch_size}, n_samples={n_samples})")
        if not self._full and self._pos == 0:
            raise ValueError("empty buffer: add() at least one transition before sampling")
        if self._full:
            young_stop = self._pos - 1 if sample_next_obs else self._pos
            old_stop = self._buffer_size if young_stop >= 0 else self._buffer_size + young_stop
            eligible_rows = np.array(
                list(range(0, young_stop)) + list(range(self._pos, old_stop)), dtype=np.intp
            )
            batch_idxes = eligible_rows[self._rng.integers(0, len(eligible_rows), size=(batch_size * n_samples,), dtype=np.intp)]
        else:
            newest_allowed = self._pos - 1 if sample_next_obs else self._pos
            if newest_allowed == 0:
                raise RuntimeError(
                    "sample_next_obs needs at least two stored transitions (the shifted-index "
                    "pairing has nothing to pair with yet)"
                )
            batch_idxes = self._rng.integers(0, newest_allowed, size=(batch_size * n_samples,), dtype=np.intp)
        samples = self._get_samples(batch_idxes, sample_next_obs=sample_next_obs, clone=clone)
        return {k: v.reshape(n_samples, batch_size, *v.shape[1:]) for k, v in samples.items()}

    def _get_samples(
        self, batch_idxes: np.ndarray, sample_next_obs: bool = False, clone: bool = False
    ) -> Dict[str, np.ndarray]:
        if self.empty:
            raise RuntimeError("uninitialized buffer: the storage is allocated lazily by the first add()")
        env_idxes = self._rng.integers(0, self._n_envs, size=(len(batch_idxes),), dtype=np.intp)
        flat_idxes = (batch_idxes * self._n_envs + env_idxes).flat
        if sample_next_obs:
            flat_next_idxes = (((batch_idxes + 1) % self._buffer_size) * self._n_envs + env_idxes).flat
        samples: Dict[str, np.ndarray] = {}
        for k, v in self._buf.items():
            arr = np.asarray(v.array if isinstance(v, MemmapArray) else v)
            flat = arr.reshape(-1, *arr.shape[2:])
            samples[k] = np.take(flat, flat_idxes, axis=0)
            if clone:
                samples[k] = samples[k].copy()
            if sample_next_obs and k in self._obs_keys:
                samples[f"next_{k}"] = np.take(flat, flat_next_idxes, axis=0)
                if clone:
                    samples[f"next_{k}"] = samples[f"next_{k}"].copy()
        return samples

    def sample_tensors(
        self,
        batch_size: int,
        clone: bool = False,
        sample_next_obs: bool = False,
        dtype: Any = None,
        sharding: Any = None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        """Sample and ship to device (reference: ``buffers.py:290-326`` with
        ``jax.device_put`` instead of torch conversion)."""
        n_samples = kwargs.pop("n_samples", 1)
        samples = self.sample(batch_size=batch_size, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs)
        return {k: to_device(v, dtype=dtype, sharding=sharding) for k, v in samples.items()}

    # -- conversion / dunder -------------------------------------------------
    def to_tensor(self, dtype: Any = None, clone: bool = False, sharding: Any = None) -> Dict[str, Any]:
        return {k: to_device(v, dtype=dtype, sharding=sharding, clone=clone) for k, v in self._buf.items()}

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Host-side views of the storage, so callers can stage the whole
        batch with ONE sharded ``device_put`` instead of a per-key transfer.
        Zero-copy except for float64 keys, which are downcast (copied) to
        float32 — the same rule :func:`to_device` applies before placement."""
        out: Dict[str, np.ndarray] = {}
        for k, v in self._buf.items():
            arr = np.asarray(v.array if isinstance(v, MemmapArray) else v)
            if arr.dtype == np.float64:
                arr = arr.astype(np.float32)
            out[k] = arr
        return out

    def __getitem__(self, key: str) -> np.ndarray | MemmapArray:
        if not isinstance(key, str):
            raise TypeError(f"buffer keys are strings (got {type(key)})")
        if self.empty:
            raise RuntimeError("uninitialized buffer: the storage is allocated lazily by the first add()")
        return self._buf.get(key)

    def __setitem__(self, key: str, value: np.ndarray | MemmapArray) -> None:
        if not isinstance(value, (np.ndarray, MemmapArray)):
            raise ValueError(f"The value must be an np.ndarray or MemmapArray, got {type(value)}")
        if self.empty:
            raise RuntimeError("uninitialized buffer: the storage is allocated lazily by the first add()")
        if tuple(value.shape[:2]) != (self._buffer_size, self._n_envs):
            raise RuntimeError(
                f"'value' must have leading dims [buffer_size, n_envs, ...]; got shape {value.shape}"
            )
        if self._memmap:
            filename = value.filename if isinstance(value, MemmapArray) else Path(self._memmap_dir) / f"{key}.memmap"
            self._buf[key] = MemmapArray.from_array(value, filename=filename, mode=self._memmap_mode)
        else:
            self._buf[key] = np.copy(value.array if isinstance(value, MemmapArray) else value)


class SequentialReplayBuffer(ReplayBuffer):
    """Samples length-``sequence_length`` contiguous windows per env
    (reference: ``buffers.py:363-528``). Output is
    ``(n_samples, sequence_length, batch_size, ...)``."""

    batch_axis: int = 2

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        batch_dim = batch_size * n_samples
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"need positive batch_size and n_samples (got batch_size={batch_size}, n_samples={n_samples})")
        if not self._full and self._pos == 0:
            raise ValueError("empty buffer: add() at least one transition before sampling")
        if not self._full and self._pos - sequence_length + 1 < 1:
            raise ValueError(f"a {sequence_length}-step window needs at least that many stored rows (have {self._pos})")
        if self._full and sequence_length > len(self):
            raise ValueError(f"The sequence length ({sequence_length}) is greater than the buffer size ({len(self)})")

        if self._full:
            young_stop = self._pos - sequence_length + 1
            old_stop = self._buffer_size if young_stop >= 0 else self._buffer_size + young_stop
            eligible_rows = np.array(
                list(range(0, young_stop)) + list(range(self._pos, old_stop)), dtype=np.intp
            )
            start_idxes = eligible_rows[self._rng.integers(0, len(eligible_rows), size=(batch_dim,), dtype=np.intp)]
        else:
            start_idxes = self._rng.integers(0, self._pos - sequence_length + 1, size=(batch_dim,), dtype=np.intp)
        chunk = np.arange(sequence_length, dtype=np.intp).reshape(1, -1)
        idxes = (start_idxes.reshape(-1, 1) + chunk) % self._buffer_size
        return self._get_seq_samples(idxes, batch_size, n_samples, sequence_length, sample_next_obs, clone)

    def _get_seq_samples(
        self,
        batch_idxes: np.ndarray,
        batch_size: int,
        n_samples: int,
        sequence_length: int,
        sample_next_obs: bool,
        clone: bool,
    ) -> Dict[str, np.ndarray]:
        flat_batch_idxes = np.ravel(batch_idxes)
        n_rows = batch_size * n_samples
        if self._n_envs == 1:
            env_idxes = np.zeros((n_rows * sequence_length,), dtype=np.intp)
        else:
            env_idxes = self._rng.integers(0, self._n_envs, size=(n_rows,), dtype=np.intp)
            env_idxes = np.ravel(np.tile(env_idxes.reshape(-1, 1), (1, sequence_length)))
        flat_idxes = (flat_batch_idxes * self._n_envs + env_idxes).flat
        samples: Dict[str, np.ndarray] = {}
        for k, v in self._buf.items():
            arr = np.asarray(v.array if isinstance(v, MemmapArray) else v)
            flat = arr.reshape(-1, *arr.shape[2:])
            taken = np.take(flat, flat_idxes, axis=0)
            batched = taken.reshape(n_samples, batch_size, sequence_length, *taken.shape[1:])
            samples[k] = np.swapaxes(batched, 1, 2)
            if clone:
                samples[k] = samples[k].copy()
            if sample_next_obs and k in self._obs_keys:
                next_taken = flat[((flat_batch_idxes + 1) % self._buffer_size) * self._n_envs + env_idxes]
                next_batched = next_taken.reshape(n_samples, batch_size, sequence_length, *next_taken.shape[1:])
                samples[f"next_{k}"] = np.swapaxes(next_batched, 1, 2)
                if clone:
                    samples[f"next_{k}"] = samples[f"next_{k}"].copy()
        return samples


class EnvIndependentReplayBuffer:
    """One sub-buffer per environment so ragged per-env writes stay aligned
    (reference: ``buffers.py:529-745``)."""

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        memmap: bool = False,
        memmap_dir: str | os.PathLike | None = None,
        memmap_mode: str = "r+",
        buffer_cls: Type[ReplayBuffer] = ReplayBuffer,
        **kwargs: Any,
    ):
        if buffer_size <= 0:
            raise ValueError(f"buffer_size must be a positive integer (got {buffer_size})")
        if n_envs <= 0:
            raise ValueError(f"n_envs must be a positive integer (got {n_envs})")
        if memmap:
            if memmap_mode not in _MEMMAP_MODES:
                raise ValueError(f"Accepted values for memmap_mode are {_MEMMAP_MODES}")
            if memmap_dir is None:
                raise ValueError("memmap=True requires a 'memmap_dir'")
            memmap_dir = Path(memmap_dir)
        self._buf: Sequence[ReplayBuffer] = [
            buffer_cls(
                buffer_size=buffer_size,
                n_envs=1,
                obs_keys=obs_keys,
                memmap=memmap,
                memmap_dir=memmap_dir / f"env_{i}" if memmap else None,
                memmap_mode=memmap_mode,
                **kwargs,
            )
            for i in range(n_envs)
        ]
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._rng: np.random.Generator = np.random.default_rng()
        self._concat_along_axis = buffer_cls.batch_axis

    @property
    def buffer(self) -> Sequence[ReplayBuffer]:
        return tuple(self._buf)

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def full(self) -> Sequence[bool]:
        return tuple(b.full for b in self._buf)

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def empty(self) -> Sequence[bool]:
        return tuple(b.empty for b in self._buf)

    @property
    def is_memmap(self) -> Sequence[bool]:
        return tuple(b.is_memmap for b in self._buf)

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)
        for i, b in enumerate(self._buf):
            b.seed(None if seed is None else seed + i)

    def add(
        self,
        data: "ReplayBuffer" | Dict[str, np.ndarray],
        indices: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if indices is None:
            indices = tuple(range(self._n_envs))
        elif len(indices) != next(iter(data.values())).shape[1]:
            raise ValueError(
                f"The length of 'indices' ({len(indices)}) must equal the second dim of 'data' "
                f"({next(iter(data.values())).shape[1]})"
            )
        for env_data_idx, env_idx in enumerate(indices):
            env_data = {k: v[:, env_data_idx : env_data_idx + 1] for k, v in data.items()}
            self._buf[env_idx].add(env_data, validate_args=validate_args)

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"need positive batch_size and n_samples (got batch_size={batch_size}, n_samples={n_samples})")
        bs_per_buf = np.bincount(self._rng.integers(0, self._n_envs, (batch_size,)))
        per_buf = [
            b.sample(batch_size=bs, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs)
            for b, bs in zip(self._buf, bs_per_buf)
            if bs > 0
        ]
        samples: Dict[str, np.ndarray] = {}
        for k in per_buf[0].keys():
            samples[k] = np.concatenate([s[k] for s in per_buf], axis=self._concat_along_axis)
        return samples

    def sample_tensors(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        dtype: Any = None,
        sharding: Any = None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        samples = self.sample(
            batch_size=batch_size, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs
        )
        return {k: to_device(v, dtype=dtype, sharding=sharding) for k, v in samples.items()}


class EpisodeBuffer:
    """Whole-episode store with cumulative-length eviction
    (reference: ``buffers.py:746-1157``)."""

    batch_axis: int = 2

    def __init__(
        self,
        buffer_size: int,
        minimum_episode_length: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        prioritize_ends: bool = False,
        memmap: bool = False,
        memmap_dir: str | os.PathLike | None = None,
        memmap_mode: str = "r+",
    ) -> None:
        if buffer_size <= 0:
            raise ValueError(f"buffer_size must be a positive integer (got {buffer_size})")
        if minimum_episode_length <= 0:
            raise ValueError(f"minimum_episode_length must be positive (got {minimum_episode_length})")
        if buffer_size < minimum_episode_length:
            raise ValueError(
                f"The sequence length must be lower than the buffer size, got: bs = {buffer_size} and "
                f"sl = {minimum_episode_length}"
            )
        self._n_envs = n_envs
        self._obs_keys = tuple(obs_keys)
        self._buffer_size = buffer_size
        self._minimum_episode_length = minimum_episode_length
        self._prioritize_ends = prioritize_ends
        self._open_episodes: Sequence[list] = [[] for _ in range(n_envs)]
        self._cum_lengths: list = []
        self._buf: list = []
        self._memmap = memmap
        self._memmap_dir = memmap_dir
        self._memmap_mode = memmap_mode
        self._rng: np.random.Generator = np.random.default_rng()
        if self._memmap:
            if self._memmap_mode not in _MEMMAP_MODES:
                raise ValueError(f"Accepted values for memmap_mode are {_MEMMAP_MODES}")
            if self._memmap_dir is None:
                raise ValueError("memmap=True requires a 'memmap_dir'")
            self._memmap_dir = Path(self._memmap_dir)
            self._memmap_dir.mkdir(parents=True, exist_ok=True)

    # -- properties ----------------------------------------------------------
    @property
    def prioritize_ends(self) -> bool:
        return self._prioritize_ends

    @prioritize_ends.setter
    def prioritize_ends(self, value: bool) -> None:
        self._prioritize_ends = value

    @property
    def buffer(self) -> Sequence[Dict[str, np.ndarray | MemmapArray]]:
        return self._buf

    @property
    def obs_keys(self) -> Sequence[str]:
        return self._obs_keys

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def minimum_episode_length(self) -> int:
        return self._minimum_episode_length

    @property
    def is_memmap(self) -> bool:
        return self._memmap

    @property
    def full(self) -> bool:
        return self._cum_lengths[-1] + self._minimum_episode_length > self._buffer_size if len(self._buf) > 0 else False

    def __len__(self) -> int:
        return self._cum_lengths[-1] if len(self._buf) > 0 else 0

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)

    # -- add -----------------------------------------------------------------
    def add(
        self,
        data: "ReplayBuffer" | Dict[str, np.ndarray],
        env_idxes: Sequence[int] | None = None,
        validate_args: bool = False,
    ) -> None:
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if validate_args:
            if not isinstance(data, dict) or not all(isinstance(v, np.ndarray) for v in data.values()):
                raise ValueError("'data' must be a dictionary of numpy arrays")
            if any(v.ndim < 2 for v in data.values()):
                raise RuntimeError("'data' must have at least 2 dims: [sequence_length, n_envs, ...]")
            if len({v.shape[:2] for v in data.values()}) > 1:
                raise RuntimeError("Every array in 'data' must be congruent in the first 2 dimensions")
            if "terminated" not in data or "truncated" not in data:
                raise RuntimeError(f"The episode must contain the 'terminated' and 'truncated' keys, got: {data.keys()}")
            if env_idxes is not None and (np.array(env_idxes) >= self._n_envs).any():
                raise ValueError(f"The env indices must be in [0, {self._n_envs}), given {env_idxes}")

        if env_idxes is None:
            env_idxes = range(self._n_envs)
        for i, env in enumerate(env_idxes):
            env_data = {k: v[:, i] for k, v in data.items()}
            done = np.logical_or(env_data["terminated"], env_data["truncated"])
            episode_ends = done.nonzero()[0].tolist()
            if len(episode_ends) == 0:
                self._open_episodes[env].append(env_data)
            else:
                episode_ends.append(len(done))
                start = 0
                for ep_end_idx in episode_ends:
                    stop = ep_end_idx
                    episode = {k: env_data[k][start : stop + 1] for k in env_data.keys()}
                    if len(np.logical_or(episode["terminated"], episode["truncated"])) > 0:
                        self._open_episodes[env].append(episode)
                    start = stop + 1
                    should_save = len(self._open_episodes[env]) > 0 and np.logical_or(
                        self._open_episodes[env][-1]["terminated"][-1],
                        self._open_episodes[env][-1]["truncated"][-1],
                    )
                    if should_save:
                        self._save_episode(self._open_episodes[env])
                        self._open_episodes[env] = []

    def _save_episode(self, episode_chunks: Sequence[Dict[str, np.ndarray | MemmapArray]]) -> None:
        if len(episode_chunks) == 0:
            raise RuntimeError("Invalid episode, an empty sequence is given.")
        episode: Dict[str, list] = {k: [] for k in episode_chunks[0].keys()}
        for chunk in episode_chunks:
            for k in chunk.keys():
                episode[k].append(chunk[k])
        episode = {k: np.concatenate(v, axis=0) for k, v in episode.items()}

        ends = np.logical_or(episode["terminated"], episode["truncated"])
        ep_len = ends.shape[0]
        if len(ends.nonzero()[0]) != 1 or not ends[-1]:
            raise RuntimeError(f"The episode must contain exactly one done at the end")
        if ep_len < self._minimum_episode_length:
            raise RuntimeError(f"episode of {ep_len} steps is shorter than the minimum episode length {self._minimum_episode_length}")
        if ep_len > self._buffer_size:
            raise RuntimeError(f"episode of {ep_len} steps exceeds the buffer capacity of {self._buffer_size}")

        if self.full or len(self) + ep_len > self._buffer_size:
            cum_lengths = np.array(self._cum_lengths)
            mask = (len(self) - cum_lengths + ep_len) <= self._buffer_size
            last_to_remove = mask.argmax()
            if self._memmap and self._memmap_dir is not None:
                for _ in range(last_to_remove + 1):
                    dirname = os.path.dirname(self._buf[0][next(iter(self._buf[0].keys()))].filename)
                    for v in self._buf[0].values():
                        del v
                    del self._buf[0]
                    try:
                        shutil.rmtree(dirname)
                    except Exception as e:  # pragma: no cover
                        logging.error(e)
            else:
                self._buf = self._buf[last_to_remove + 1 :]
            cum_lengths = cum_lengths[last_to_remove + 1 :] - cum_lengths[last_to_remove]
            self._cum_lengths = cum_lengths.tolist()
        self._cum_lengths.append(len(self) + ep_len)
        episode_to_store = episode
        if self._memmap:
            episode_dir = Path(self._memmap_dir) / f"episode_{uuid.uuid4()}"
            episode_dir.mkdir(parents=True, exist_ok=True)
            episode_to_store = {}
            for k, v in episode.items():
                episode_to_store[k] = MemmapArray(
                    filename=str(episode_dir / f"{k}.memmap"), dtype=v.dtype, shape=v.shape, mode=self._memmap_mode
                )
                episode_to_store[k][:] = v
        self._buf.append(episode_to_store)

    # -- sample --------------------------------------------------------------
    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        n_samples: int = 1,
        clone: bool = False,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive (got {batch_size})")
        if n_samples <= 0:
            raise ValueError(f"n_samples must be positive (got {n_samples})")
        ep_lens = np.array(self._cum_lengths) - np.array([0] + self._cum_lengths[:-1])
        if sample_next_obs:
            valid_mask = ep_lens > sequence_length
        else:
            valid_mask = ep_lens >= sequence_length
        valid_episodes = list(compress(self._buf, valid_mask))
        if len(valid_episodes) == 0:
            raise RuntimeError(
                f"no stored episode is at least {sequence_length} steps long — nothing to sample"
            )

        chunk = np.arange(sequence_length, dtype=np.intp).reshape(1, -1)
        nsample_per_eps = np.bincount(self._rng.integers(0, len(valid_episodes), (batch_size * n_samples,))).astype(np.intp)
        samples_per_eps: Dict[str, list] = {k: [] for k in valid_episodes[0].keys()}
        if sample_next_obs:
            samples_per_eps.update({f"next_{k}": [] for k in self._obs_keys})
        for i, n in enumerate(nsample_per_eps):
            if n == 0:
                continue
            ep = valid_episodes[i]
            ep_len = np.logical_or(ep["terminated"], ep["truncated"]).shape[0]
            if sample_next_obs:
                ep_len -= 1
            upper = ep_len - sequence_length + 1
            if self._prioritize_ends:
                upper += sequence_length
            start_idxes = np.minimum(
                self._rng.integers(0, upper, size=(n,)).reshape(-1, 1), ep_len - sequence_length, dtype=np.intp
            )
            indices = start_idxes + chunk
            for k in valid_episodes[0].keys():
                arr = np.asarray(ep[k].array if isinstance(ep[k], MemmapArray) else ep[k])
                samples_per_eps[k].append(
                    np.take(arr, indices.flat, axis=0).reshape(n, sequence_length, *arr.shape[1:])
                )
                if sample_next_obs and k in self._obs_keys:
                    samples_per_eps[f"next_{k}"].append(arr[indices + 1])
        samples: Dict[str, np.ndarray] = {}
        for k, v in samples_per_eps.items():
            if len(v) > 0:
                samples[k] = np.moveaxis(
                    np.concatenate(v, axis=0).reshape(n_samples, batch_size, sequence_length, *v[0].shape[2:]), 2, 1
                )
                if clone:
                    samples[k] = samples[k].copy()
        return samples

    def sample_tensors(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        n_samples: int = 1,
        clone: bool = False,
        sequence_length: int = 1,
        dtype: Any = None,
        sharding: Any = None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        samples = self.sample(batch_size, sample_next_obs, n_samples, clone, sequence_length)
        return {k: to_device(v, dtype=dtype, sharding=sharding) for k, v in samples.items()}
