"""Multi-host bring-up.

The reference's multi-process story is Lightning spawning one process per GPU
and initializing NCCL/Gloo groups (reference: ``sheeprl/cli.py:186-198``,
``ppo_decoupled.py:645-666``). The TPU-native story is one process per host,
started by the pod runtime (or manually), with ``jax.distributed.initialize``
wiring DCN; chips then appear as one global ``jax.devices()`` list and all
tensor collectives ride ICI via sharded ``jit``.

Wired through the CLI entrypoints (train AND serve) behind the
``fabric.distributed.*`` config block; the ``SHEEPRL_COORDINATOR`` /
``SHEEPRL_NUM_PROCESSES`` / ``SHEEPRL_PROCESS_ID`` env vars remain the
pod-runtime override (one launch command, per-host env) and win over config.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Any, Dict, Optional

import jax

_initialized = False


class CoordinatorConnectError(ConnectionError):
    """``jax.distributed.initialize`` could not reach the coordinator after
    the configured connect-retry budget. Names the coordinator address so a
    pod operator can tell a dead coordinator host from a bad config."""

    def __init__(self, coordinator: str, attempts: int, cause: BaseException) -> None:
        self.coordinator = coordinator
        self.attempts = attempts
        super().__init__(
            f"could not join the jax.distributed runtime at coordinator "
            f"'{coordinator}' after {attempts} attempt(s): {type(cause).__name__}: {cause}"
        )


def maybe_init(
    cfg: Optional[Dict[str, Any]] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize ``jax.distributed`` when running multi-host; returns
    whether THIS call initialized it.

    ``cfg`` is a ``fabric.distributed``-shaped mapping (``enabled``,
    ``coordinator``, ``num_processes``, ``process_id``). Resolution order per
    field: explicit keyword > ``SHEEPRL_*`` env var (the pod runtime's
    per-host override) > config key. ``enabled: false`` never initializes;
    ``enabled: true`` REQUIRES a coordinator (a typed error beats N-1 hosts
    silently training solo); ``enabled: null`` (the default) auto-detects —
    initialize iff a coordinator or process count was provided somewhere.
    No-op when already initialized or single-process.

    Startup ordering is NOT guaranteed in a gang-spawned pod: a worker may
    call this before the coordinator (process 0) is listening. The connect is
    therefore retried with bounded exponential backoff
    (``cfg.connect_retries`` extra attempts, ``cfg.connect_backoff_s`` base
    delay, optional ``cfg.init_timeout_s`` per-attempt jax initialization
    timeout); exhaustion raises :class:`CoordinatorConnectError` naming the
    coordinator address instead of a raw RuntimeError.
    """
    global _initialized
    if _initialized:
        return False
    cfg = dict(cfg or {})
    enabled = cfg.get("enabled")
    if enabled is False:
        return False
    coordinator_address = (
        coordinator_address or os.environ.get("SHEEPRL_COORDINATOR") or cfg.get("coordinator")
    )
    if num_processes is None:
        if "SHEEPRL_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["SHEEPRL_NUM_PROCESSES"])
        elif cfg.get("num_processes") is not None:
            num_processes = int(cfg["num_processes"])
    if process_id is None:
        if "SHEEPRL_PROCESS_ID" in os.environ:
            process_id = int(os.environ["SHEEPRL_PROCESS_ID"])
        elif cfg.get("process_id") is not None:
            process_id = int(cfg["process_id"])
    if coordinator_address is None and num_processes is None:
        if enabled:
            raise ValueError(
                "fabric.distributed.enabled=true but no coordinator was provided — set "
                "fabric.distributed.coordinator (or SHEEPRL_COORDINATOR) so every host "
                "joins the same jax.distributed runtime instead of silently training solo"
            )
        return False  # single host
    # CPU backend: cross-process computations need an explicit collectives
    # implementation (the default "none" raises "Multiprocess computations
    # aren't implemented on the CPU backend" at the first collective). Gloo
    # ships in jaxlib; the flag only shapes CPU client creation, so it is
    # harmless on real accelerators. Must be set BEFORE initialize().
    if not os.environ.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    retries = max(0, int(cfg.get("connect_retries", 3) or 0))
    backoff_s = max(0.0, float(cfg.get("connect_backoff_s", 1.0) or 0.0))
    init_kwargs: Dict[str, Any] = {}
    if cfg.get("init_timeout_s"):
        init_kwargs["initialization_timeout"] = int(cfg["init_timeout_s"])
    for attempt in range(retries + 1):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **init_kwargs,
            )
            break
        except Exception as e:
            if attempt >= retries:
                raise CoordinatorConnectError(str(coordinator_address), retries + 1, e) from e
            delay = backoff_s * (2.0**attempt)
            warnings.warn(
                f"jax.distributed connect to coordinator '{coordinator_address}' failed "
                f"(attempt {attempt + 1}/{retries + 1}): {type(e).__name__}: {e} — "
                f"retrying in {delay:g}s"
            )
            time.sleep(delay)
    _initialized = True
    return True
