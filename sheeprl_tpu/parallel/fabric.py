"""Device-mesh runtime — the TPU-native replacement for Lightning Fabric.

The reference leans on ``lightning.fabric.Fabric`` for device management, DDP
wrapping, precision and launching (reference: ``sheeprl/cli.py:148-198``).
On TPU none of that machinery exists as wrappers around modules: the idiomatic
design is

- one JAX *process per host*, all chips visible through ``jax.devices()``;
- a :class:`jax.sharding.Mesh` laying out chips over named axes
  (``dp``/``fsdp``/``tp``) — data-parallel gradient all-reduce is not a wrapper
  but a consequence of jitting a loss over batch-sharded inputs with
  replicated params (XLA inserts the ``psum`` over ICI);
- precision as a *policy* applied to params/compute dtypes rather than autocast
  contexts.

``Fabric`` here is therefore a small, stateless-ish context object: mesh +
sharding helpers + rank info + RNG seeding + checkpoint IO. Algorithm mains
receive it exactly like reference mains receive a Lightning Fabric.
"""

from __future__ import annotations

import dataclasses
import os
import random
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AcceleratorUnavailableError", "Precision", "Fabric", "get_single_device_fabric"]


class AcceleratorUnavailableError(RuntimeError):
    """``fabric.accelerator`` names a platform this process did not get."""


_PRECISION_ALIASES = {
    "32-true": ("float32", "float32"),
    "32": ("float32", "float32"),
    "bf16-mixed": ("float32", "bfloat16"),
    "bf16-true": ("bfloat16", "bfloat16"),
    "16-mixed": ("float32", "bfloat16"),  # fp16 has no TPU advantage; map to bf16
    "16-true": ("bfloat16", "bfloat16"),
}


@dataclasses.dataclass(frozen=True)
class Precision:
    """Param/compute dtype policy (replaces Fabric precision strings)."""

    param_dtype: jnp.dtype
    compute_dtype: jnp.dtype

    @classmethod
    def from_string(cls, spec: str) -> "Precision":
        if spec not in _PRECISION_ALIASES:
            raise ValueError(f"Unknown precision '{spec}'. Known: {sorted(_PRECISION_ALIASES)}")
        p, c = _PRECISION_ALIASES[spec]
        return cls(param_dtype=jnp.dtype(p), compute_dtype=jnp.dtype(c))

    def cast_to_compute(self, tree: Any) -> Any:
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype) if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            tree,
        )


class Fabric:
    """Mesh + precision + rank context handed to every algorithm ``main``.

    Config surface (group ``fabric`` for UX parity with the reference):

    - ``devices``: chips *per process* to use (int or "auto");
    - ``accelerator``: "auto" (JAX's default platform) | "tpu" (a TPU, or
      :class:`AcceleratorUnavailableError`) | "cpu" (the host CPU devices,
      whatever else is visible);
    - ``precision``: Lightning-style string, mapped to a dtype policy;
    - ``strategy``: "auto" | "ddp" — accepted for config compatibility; the
      mesh is always the mechanism.
    """

    def __init__(
        self,
        devices: int | str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        strategy: str = "auto",
        mesh_axes: Sequence[str] = ("dp",),
        mesh_shape: Optional[Sequence[int]] = None,
        callbacks: Optional[Sequence[Any]] = None,
        device_list: Optional[Sequence[jax.Device]] = None,
    ) -> None:
        # ``accelerator: cpu`` pins the mesh to host CPU devices — the
        # reference benchmark configs run on CPU (``fabric.accelerator: cpu``
        # in sheeprl/configs/exp/ppo_benchmarks.yaml); ``auto`` defers to
        # JAX's default platform (TPU when present); ``tpu`` is a demand.
        # ``device_list`` pins the mesh to an explicit device subset — the
        # Sebulba actor/learner slices carved out by :meth:`partition`.
        accelerator = str(accelerator).lower()
        if accelerator not in ("auto", "tpu", "cpu"):
            raise ValueError(f"Unknown fabric.accelerator '{accelerator}'. Known: auto, tpu, cpu")
        if device_list is not None:
            all_devices = list(device_list)
        elif accelerator == "cpu":
            all_devices = jax.devices("cpu")
        else:
            all_devices = jax.devices()
        if accelerator == "tpu" and all_devices[0].platform != "tpu":
            raise AcceleratorUnavailableError(
                f"fabric.accelerator=tpu, but JAX's default platform here is "
                f"'{all_devices[0].platform}' ({len(all_devices)} x {all_devices[0].device_kind}; "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Run where a chip is visible, "
                "or ask for fabric.accelerator=auto or cpu."
            )
        if devices in ("auto", None, -1) or device_list is not None:
            n = len(all_devices)
        else:
            n = int(devices)
            if n > len(all_devices):
                raise ValueError(f"Requested {n} devices but only {len(all_devices)} are visible")
        self.devices = all_devices[:n]
        self.accelerator = accelerator
        self.strategy = strategy
        self.precision = Precision.from_string(precision)
        self.callbacks = list(callbacks or [])
        self.mesh_axes = tuple(mesh_axes)
        if mesh_shape is None:
            mesh_shape = [n] + [1] * (len(self.mesh_axes) - 1)
        dev_array = np.asarray(self.devices).reshape(tuple(mesh_shape))
        self.mesh = Mesh(dev_array, self.mesh_axes)

    # -- rank info -----------------------------------------------------------
    @property
    def world_size(self) -> int:
        """Number of devices in the mesh (all processes)."""
        return self.mesh.size

    @property
    def global_rank(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        """Number of processes in the ``jax.distributed`` runtime (1 when
        single-host). Pod training spans the mesh over this many workers."""
        return jax.process_count()

    @property
    def node_rank(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def device(self) -> jax.Device:
        return self.devices[0]

    @property
    def local_device(self) -> jax.Device:
        """First mesh device addressable by THIS process (multi-host meshes
        contain devices of every host; a non-local default device would fail
        placement on ranks > 0)."""
        pid = jax.process_index()
        for d in self.devices:
            if d.process_index == pid:
                return d
        return self.devices[0]  # pragma: no cover - single-host always matches

    # -- rng -----------------------------------------------------------------
    def seed_everything(self, seed: int) -> jax.Array:
        """Seed python/numpy and return the root PRNG key
        (replaces ``fabric.seed_everything``)."""
        random.seed(seed)
        np.random.seed(seed)
        os.environ["PYTHONHASHSEED"] = str(seed)
        return jax.random.PRNGKey(seed)

    # -- shardings -----------------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def data_sharding(self) -> NamedSharding:
        """Batch-axis sharding over the ``dp`` mesh axis."""
        return NamedSharding(self.mesh, P("dp"))

    def shard_data(self, tree: Any) -> Any:
        """Place host arrays on device, batch-sharded over ``dp``.

        Multi-host: each process holds ITS shard of the batch (the reference's
        per-rank rollout); the host-local arrays are assembled into one global
        array whose addressable shards stay local — no cross-host transfer.
        """
        if jax.process_count() > 1:  # pragma: no cover - exercised by the 2-process test
            from jax.experimental import multihost_utils
            from jax.sharding import PartitionSpec as _P

            local_spec = _P("dp")
            return jax.tree.map(
                lambda x: multihost_utils.host_local_array_to_global_array(x, self.mesh, local_spec), tree
            )
        # One device_put for the whole pytree: the transfers of every leaf are
        # batched in a single staging call instead of one dispatch per leaf.
        return jax.device_put(tree, self.data_sharding)

    def put_replicated(self, tree: Any) -> Any:
        """Replicate host arrays across the mesh. Multi-host: every process
        must pass the same values (seeded identically, like DDP init)."""
        if jax.process_count() > 1:  # pragma: no cover - exercised by the 2-process test
            from jax.experimental import multihost_utils
            from jax.sharding import PartitionSpec as _P

            return jax.tree.map(
                lambda x: multihost_utils.host_local_array_to_global_array(
                    x, self.mesh, _P()
                ),
                tree,
            )
        rep = self.replicated
        return jax.tree.map(lambda x: jax.device_put(x, rep), tree)

    # -- device-slice partitioning (Sebulba topology) ------------------------
    def partition(self, actor_devices: int | str = "auto") -> tuple["Fabric", "Fabric"]:
        """Split this fabric's devices into disjoint ``(actor, learner)``
        sub-fabrics for a decoupled actor/learner (Sebulba) pipeline.

        ``actor_devices`` is the chip count dedicated to actor-side inference
        (``"auto"``: 1 when more than one device is visible, else 0). Actors
        take devices from the TAIL so the learner keeps device 0 (default
        device, logging, checkpoints). With a single device — or
        ``actor_devices=0`` — both sides TIME-SLICE the same chip(s): the
        actor sub-fabric is a 1-device view of the learner's first device,
        and the overlap is between host env-stepping and device compute
        rather than between device slices.

        The learner sub-fabric keeps this fabric's callbacks (it is the
        checkpoint writer); both inherit the precision policy.
        """
        n_total = len(self.devices)
        if isinstance(actor_devices, str):
            if actor_devices.lower() != "auto":
                raise ValueError(f"actor_devices must be an int or 'auto', got {actor_devices!r}")
            n_act = 1 if n_total > 1 else 0
        else:
            n_act = int(actor_devices)
        if n_act < 0 or n_act >= n_total:
            raise ValueError(
                f"actor_devices ({n_act}) must leave at least one learner device "
                f"(fabric has {n_total}); use 0 (or 'auto' on one chip) to time-slice."
            )

        def _sub(devs, callbacks):
            f = Fabric(
                accelerator=self.accelerator,
                precision="32-true",
                strategy=self.strategy,
                mesh_axes=("dp",),
                callbacks=callbacks,
                device_list=devs,
            )
            f.precision = self.precision
            return f

        if n_act == 0:
            learner = _sub(list(self.devices), self.callbacks)
            actor = _sub([self.devices[0]], [])
        else:
            learner = _sub(list(self.devices[: n_total - n_act]), self.callbacks)
            actor = _sub(list(self.devices[n_total - n_act :]), [])
        if getattr(self, "_grad_reduce_auto", False):
            # the gradient collective runs on the LEARNER mesh: re-resolve the
            # auto wire dtype against it (from_config resolved against the
            # full fabric — a 1-device learner carved from a 2-device fabric
            # must not round gradients over a wire that no longer exists)
            from sheeprl_tpu.parallel.comm import set_grad_reduce_dtype

            set_grad_reduce_dtype("bfloat16" if learner.world_size > 1 else "float32")
        return actor, learner

    # -- launch --------------------------------------------------------------
    def describe(self, cfg: Optional[Mapping[str, Any]] = None) -> str:
        """One line naming what this run got: platform, ``device_kind``,
        device count, mesh shape, the tier each kernel resolved to and, for
        an algorithm with ``algo.hybrid_player``, whether the host player is
        on. Printed by :meth:`launch`; ``chip_smoke.py`` reads it."""
        from sheeprl_tpu.ops import kernels
        from sheeprl_tpu.utils.utils import resolve_hybrid_player

        first = self.devices[0]
        mesh = ",".join(f"{a}={n}" for a, n in self.mesh.shape.items())
        tiers = ",".join(f"{name}={kernels.tier(name)}" for name in kernels.names())
        hp_cfg = ((cfg or {}).get("algo") or {}).get("hybrid_player")
        hybrid = "n/a" if hp_cfg is None else ("on" if resolve_hybrid_player(hp_cfg, self.mesh) else "off")
        return (
            f"fabric: platform={first.platform} device_kind={first.device_kind!r} "
            f"devices={len(self.devices)} mesh=({mesh}) kernels=[{tiers}] hybrid_player={hybrid}"
        )

    def launch(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run ``fn(self, *args)``.

        Unlike Lightning there is no process spawning: JAX multi-host runs are
        started externally (one process per host; ``jax.distributed`` is
        initialized by :func:`sheeprl_tpu.parallel.distributed.maybe_init`).

        The ``default_device`` context pins every *uncommitted* computation
        (scalar ``jnp.asarray``, jitted fns fed plain numpy, …) to this
        fabric's platform. Without it, a CPU-fabric run in a process that
        also sees a chip places stray ops on the chip and pays a
        host↔device copy for each.

        Prints :meth:`describe` on the way in and the compile counters
        (programs, seconds, persistent-cache hits and writes) on the way out.
        """
        from sheeprl_tpu.utils.utils import compile_stats, host_cpu_device

        host_cpu_device()  # a missing CPU platform is a start-up error
        cfg = args[0] if args and isinstance(args[0], Mapping) else None
        if self.is_global_zero:
            print(self.describe(cfg), flush=True)
        before = compile_stats.snapshot()
        try:
            with jax.default_device(self.local_device), self.mesh:
                return fn(self, *args, **kwargs)
        finally:
            if self.is_global_zero:
                programs, seconds, hits, writes = (a - b for a, b in zip(compile_stats.snapshot(), before))
                print(
                    f"compile: programs={programs} seconds={seconds:.1f} cache_hits={hits} "
                    f"cache_writes={writes} cache_dir={jax.config.jax_compilation_cache_dir}",
                    flush=True,
                )

    # -- host-side collectives (control plane) -------------------------------
    def broadcast_obj(self, obj: Any, src: int = 0) -> Any:
        """Object broadcast across processes (DCN control-plane).
        Single-process: identity."""
        if jax.process_count() == 1:
            return obj
        from jax.experimental import multihost_utils  # pragma: no cover

        return multihost_utils.broadcast_one_to_all(obj, is_source=jax.process_index() == src)

    def barrier(self) -> None:
        if jax.process_count() > 1:  # pragma: no cover
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("sheeprl_tpu_barrier")

    # -- callbacks (checkpoint hooks) ---------------------------------------
    def call(self, hook_name: str, **kwargs: Any) -> None:
        for cb in self.callbacks:
            hook = getattr(cb, hook_name, None)
            if hook is not None:
                hook(fabric=self, **kwargs)

    # -- factory -------------------------------------------------------------
    @classmethod
    def from_config(cls, fabric_cfg: Mapping[str, Any], callbacks: Optional[Sequence[Any]] = None) -> "Fabric":
        from sheeprl_tpu.parallel.comm import set_grad_reduce_dtype

        fabric = cls(
            devices=fabric_cfg.get("devices", "auto"),
            accelerator=fabric_cfg.get("accelerator", "auto"),
            precision=str(fabric_cfg.get("precision", "32-true")),
            strategy=str(fabric_cfg.get("strategy", "auto")),
            mesh_axes=tuple(fabric_cfg.get("mesh_axes", ("dp",))),
            mesh_shape=fabric_cfg.get("mesh_shape"),
            callbacks=callbacks,
        )
        # Process-wide gradient-collective wire dtype; must land before any
        # train step traces. from_config is the run boundary, so previous
        # runs' traces don't trip the mid-run-flip warning (parallel/comm.py).
        # ``auto`` (the default) reduces in bf16 whenever there is an actual
        # wire — i.e. the mesh spans more than one device; a single-device
        # "collective" is a no-op, where the cast would round gradients for
        # nothing. ``float32`` is the exactness escape hatch.
        wire = fabric_cfg.get("grad_reduce_dtype", "auto")
        fabric._grad_reduce_auto = wire is None or str(wire).lower() == "auto"
        if fabric._grad_reduce_auto:
            wire = "bfloat16" if fabric.world_size > 1 else "float32"
        set_grad_reduce_dtype(wire, fresh_run=True)
        return fabric


def get_single_device_fabric(fabric: Fabric) -> Fabric:
    """A sibling context pinned to one device, sharing the precision policy
    (reference: ``sheeprl/utils/fabric.py:8-35``) — used for the *player* so
    env-interaction inference never touches the mesh."""
    f = Fabric(
        devices=1,
        accelerator=fabric.accelerator,
        precision="32-true",
        strategy="auto",
        mesh_axes=("dp",),
        callbacks=fabric.callbacks,
    )
    f.precision = fabric.precision
    return f
