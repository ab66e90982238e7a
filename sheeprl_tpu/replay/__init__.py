"""Device-resident replay subsystem.

The layer between env interaction and the fused train step for off-policy
algorithms: ring storage living in accelerator HBM (sharded or replicated
over the ``dp`` mesh), staged host transitions flushed as ONE packed
transfer, and sampling — uniform, sequential windows, prioritized — running
IN-GRAPH so sample+train is a single dispatch per env step.

- :mod:`~sheeprl_tpu.replay.indices` — host-buffer-bit-compatible index
  arithmetic (wrap-around, write-head exclusion, next-obs shift);
- :mod:`~sheeprl_tpu.replay.sumtree` — in-graph sum-tree for PER;
- :mod:`~sheeprl_tpu.replay.device_buffer` — :class:`DeviceReplayBuffer`
  (scalar-head uniform/PER ring, SAC-shaped) + spillover sizing;
- :mod:`~sheeprl_tpu.replay.driver` — :class:`AsyncSequenceRing`
  (per-env-head sequence ring of the decoupled Dreamer tier).

See ``howto/device_replay.md`` for when to use the device tier vs the host
memmap spillover tier, and the HBM sizing math.
"""

from sheeprl_tpu.replay.device_buffer import (
    DeviceReplayBuffer,
    DeviceReplayState,
    estimate_ring_bytes,
    resolve_device_resident,
    restore_host_buffer,
    restore_host_env_buffer,
)
from sheeprl_tpu.replay.driver import AsyncSequenceRing, SeqBlobWriter

__all__ = [
    "AsyncSequenceRing",
    "DeviceReplayBuffer",
    "DeviceReplayState",
    "SeqBlobWriter",
    "estimate_ring_bytes",
    "resolve_device_resident",
    "restore_host_buffer",
    "restore_host_env_buffer",
]
