"""Misc host-side helpers (reference: ``sheeprl/utils/utils.py``).

Device-side math (gae, symlog, two-hot, lambda returns) lives in
``sheeprl_tpu.ops`` as jittable functions; this module keeps the host-side
pieces: step-accounting (:class:`Ratio`), schedules, config printing/saving.
"""

from __future__ import annotations

import os
import re
import threading
import time
import warnings
from typing import Any, Dict, Mapping, Optional, Sequence

import numpy as np

from sheeprl_tpu.config import DotDict, dotdict, save_config, to_yaml
from sheeprl_tpu.utils import profiler

__all__ = [
    "HostCpuUnavailableError",
    "OneProcessPerChipError",
    "Ratio",
    "compile_stats",
    "enable_compile_cache",
    "host_cpu_device",
    "pin_cpu_platform",
    "refuse_children_on_tpu",
    "polynomial_decay",
    "normalize_array",
    "print_config",
    "save_configs",
    "dotdict",
    "DotDict",
]


def pin_cpu_platform(accelerator: Any) -> None:
    """Pin ``jax_platforms=cpu`` for a ``fabric.accelerator: cpu`` run, BEFORE
    any backend discovery. A chip belongs to one process at a time and
    discovery opens every platform JAX is allowed, so a CPU run that did not
    pin would take the chip from whatever else wants it (and fail at start-up
    when another process already holds it). No-op for accelerator=auto/tpu.
    A config update rather than the ``JAX_PLATFORMS`` variable because the
    caller has usually imported jax already."""
    if accelerator is None or str(accelerator).lower() != "cpu":
        return
    import jax

    jax.config.update("jax_platforms", "cpu")


class HostCpuUnavailableError(RuntimeError):
    """This process may not open the CPU platform next to its accelerator."""


def host_cpu_device():
    """The host CPU device, for the work that stays on the host in a process
    that trains on a chip: the hybrid player, staging, checkpoint pulls.
    ``JAX_PLATFORMS=tpu`` admits no CPU platform; that is a start-up error
    named here (:meth:`Fabric.launch` calls this), not a ``RuntimeError``
    from a worker thread's first transfer."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise HostCpuUnavailableError(
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} (jax_platforms="
            f"{jax.config.jax_platforms!r}) admits no CPU platform, and this program keeps host-side "
            "work (hybrid player, staging, checkpoint pulls) on the CPU device; list it, e.g. "
            f"JAX_PLATFORMS=tpu,cpu. ({e})"
        ) from e


class OneProcessPerChipError(RuntimeError):
    """A launcher was asked for JAX child processes on a host whose default
    platform is a TPU."""


def refuse_children_on_tpu(launcher: str, children: str) -> None:
    """Stop ``launcher`` at start-up when its ``children`` would each open a
    TPU. A chip belongs to one process at a time: the second opener dies in
    libtpu ("Internal error when accessing libtpu multi-process lockfile"),
    after the launcher has already spent its restart budget on it. Children
    pinned to the CPU (``fabric.accelerator=cpu``, which this process has then
    pinned too) are fine."""
    import jax

    if jax.default_backend() != "tpu":
        return
    raise OneProcessPerChipError(
        f"{launcher} starts {children}, and each opens JAX's default platform, which here is a TPU. "
        "A chip belongs to one process at a time, and this launcher does not hand out chips. One "
        "process drives every chip of a host (fabric.devices=N in a plain run/serve); to run the "
        "children on the host CPU instead, pass fabric.accelerator=cpu."
    )


class CompileStats:
    """Process-wide compile counters, fed by ``jax.monitoring``; and the
    spans of JAX's compile pipeline, entered into ``utils.profiler``'s record
    as they end: ``jax.trace`` (jaxpr trace), ``jax.lower`` (jaxpr to MLIR
    module), ``jax.compile`` (backend compile or persistent-cache read), each
    with ``fn`` (JAX's ``fun_name``), ``jax.compile`` also with ``cache``:
    ``hit``, ``miss`` (the persistent cache was asked and did not serve it)
    or ``off`` (not asked)."""

    _SPANS = {
        "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
        "/jax/core/compile/backend_compile_duration": "jax.compile",
    }

    def __init__(self) -> None:
        self._registered = False
        self.programs = 0  # backend compile requests, cache hits included
        self.seconds = 0.0  # wall time inside them
        self.cache_hits = 0  # executables read from the persistent cache
        self.cache_writes = 0  # executables written to it
        self._cache = threading.local()  # what the persistent cache said to this thread's open compile

    def register(self) -> None:
        if self._registered:
            return
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_time_span_listener(self._on_span)
        self._registered = True

    def snapshot(self) -> tuple:
        return (self.programs, self.seconds, self.cache_hits, self.cache_writes)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            self._cache.said = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1
            self._cache.said = "miss"
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self._cache.said = "miss"  # until a hit says otherwise; a compile under the size or time floor writes nothing

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _on_span(self, event: str, start: float, end: float, fun_name: str = "", **_: Any) -> None:
        name = self._SPANS.get(event)
        if name is None:
            return
        # JAX's clock is time.time(); the recorder's is perf_counter (read now: the span ended a moment ago)
        offset = time.perf_counter() - time.time()
        counters = {"fn": fun_name}
        if name == "jax.compile":
            counters["cache"] = getattr(self._cache, "said", None) or "off"
            self._cache.said = None
        profiler.record(name, start + offset, end + offset, **counters)


compile_stats = CompileStats()
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".xla_cache"
)


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache in the ONE directory this
    repository uses. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set JAX has already read it and nothing is set here; otherwise the cache
    lives in ``<checkout>/.xla_cache``, resolved from this package's location:
    one fixed path, so that every process of a checkout finds what the others
    compiled. Called by every CLI verb, ``bench.py``, the scripts under
    ``benchmarks/`` and ``tests/conftest.py`` before their first compile.
    Idempotent."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    # An executable's ``op_name`` metadata is read here (the scope table of
    # ``utils.profiler``, an operator's trace): one compiled before a
    # ``jax.named_scope`` moved must not be served for the program after it,
    # which the default key, blind to metadata, would do.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # ... with source paths relative to the checkout, so that two checkouts of
    # one tree still share what they compile
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", re.escape(os.path.dirname(_REPO_CACHE_DIR) + os.sep)
    )
    compile_stats.register()


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Polynomial schedule (reference: ``sheeprl/utils/utils.py:133-146``)."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


def normalize_array(x: np.ndarray, eps: float = 1e-8, mask: Optional[np.ndarray] = None) -> np.ndarray:
    """Standardize; with a boolean mask only masked entries contribute stats."""
    if mask is None:
        flat = x
        normalized = (flat - flat.mean()) / (flat.std() + eps)
        return normalized
    masked = x[mask]
    return (masked - masked.mean()) / (masked.std() + eps)


class Ratio:
    """Replay-ratio governor controlling gradient steps per env step.

    Semantics match the reference exactly (``sheeprl/utils/utils.py:261-302``,
    itself from Hafner's DreamerV3 ``when.py``) — resume correctness depends on
    ``_prev`` surviving checkpoints.
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: Optional[float] = None

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = step
            repeats = int(step * self._ratio)
            if self._pretrain_steps > 0:
                if step < self._pretrain_steps:
                    warnings.warn(
                        "The number of pretrain steps is greater than the number of current steps. This could lead "
                        f"to a higher ratio than the one specified ({self._ratio}). Setting the 'pretrain_steps' "
                        "equal to the number of current steps."
                    )
                    self._pretrain_steps = step
                repeats = int(self._pretrain_steps * self._ratio)
            return repeats
        repeats = int((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state_dict: Mapping[str, Any]) -> "Ratio":
        self._ratio = state_dict["_ratio"]
        self._prev = state_dict["_prev"]
        self._pretrain_steps = state_dict["_pretrain_steps"]
        return self


def print_config(
    config: Mapping[str, Any],
    fields: Sequence[str] = ("algo", "buffer", "checkpoint", "env", "fabric", "metric"),
    cfg_save_path: Optional[str] = None,
) -> None:
    """Rich tree dump of the main config sections
    (reference: ``sheeprl/utils/utils.py:209-238``)."""
    try:
        import rich.syntax
        import rich.tree
    except ImportError:  # pragma: no cover - rich is available in practice
        print(to_yaml({k: config.get(k) for k in fields if k in config}))
        return
    style = "dim"
    tree = rich.tree.Tree("CONFIG", style=style, guide_style=style)
    for field in fields:
        if field not in config:
            continue
        branch = tree.add(field, style=style, guide_style=style)
        section = config[field]
        content = to_yaml(section) if isinstance(section, Mapping) else str(section)
        branch.add(rich.syntax.Syntax(content, "yaml"))
    rich.print(tree)
    if cfg_save_path is not None:
        with open(os.path.join(cfg_save_path, "config_tree.txt"), "w") as fp:
            rich.print(tree, file=fp)


def save_configs(cfg: Mapping[str, Any], log_dir: str) -> None:
    """Persist the resolved config next to the run artifacts
    (reference: ``sheeprl/utils/utils.py:257-258``)."""
    save_config(cfg, os.path.join(log_dir, "config.yaml"))


def player_zeros(shape, host_device=None):
    """Zero state for a stateful env-side player.

    ``host_device`` set (hybrid/burst host-CPU policy): a committed host
    array, so the policy jit always sees plain committed-CPU avals — an
    ambient-mesh ``jnp.zeros`` would be mesh-typed and flip the jit's cache
    key between resets and steps, retracing (and host-recompiling) the
    policy at every episode end. ``None``: the trainer-mesh default.
    """
    import jax
    import jax.numpy as jnp

    if host_device is not None:
        return jax.device_put(np.zeros(shape, np.float32), host_device)
    return jnp.zeros(shape, jnp.float32)


def player_reset_fn(with_values: bool = False):
    """Jitted partial-reset for a stateful player's ``(actions, recurrent,
    stochastic)`` state. An eager ``.at[idx].set`` triggers a fresh XLA:CPU
    compile per call on AOT-mismatched hosts (~250 ms measured) — per episode
    end, that dominates the env loop; one jitted call hits the jit cache.

    ``with_values`` selects the Dreamer-V3 form where the reset rows take the
    learned initial state instead of zeros.
    """
    import jax

    if with_values:
        return jax.jit(
            lambda a, r, st, i, rec, post: (a.at[i].set(0.0), r.at[i].set(rec), st.at[i].set(post))
        )
    return jax.jit(lambda a, r, st, i: (a.at[i].set(0.0), r.at[i].set(0.0), st.at[i].set(0.0)))


def conv_heavy_compile_options(mesh) -> Optional[Dict[str, Any]]:
    """Low-effort XLA compile options for train graphs dominated by
    odd-spatial-dim VALID-conv gradients (Dreamer-V1/V2's faithful 64→31→14
    conv stacks). On the TPU backend these kernels hit a pathological
    compile path — the effort knobs cut compilation ~5x (measured
    188 s → 34 s for the V1 encoder gradient alone) at negligible runtime
    cost for models this size. CPU compilation is unaffected, so the knobs
    are only applied off-CPU."""
    if mesh.devices.flat[0].platform == "cpu":
        return None
    return {"exec_time_optimization_effort": -1.0, "memory_fitting_effort": -1.0}


def resolve_hybrid_player(hp_cfg: Optional[Mapping[str, Any]], mesh) -> bool:
    """Resolve ``algo.hybrid_player.enabled``: ``"auto"`` turns the host-side
    policy overlap on iff the trainer mesh lives off the host CPU (shared by
    SAC and Dreamer-V3)."""
    enabled = (hp_cfg or {}).get("enabled", "auto")
    platform = mesh.devices.flat[0].platform
    if isinstance(enabled, str):
        enabled = (platform != "cpu") if enabled.lower() == "auto" else enabled.lower() == "true"
    return bool(enabled)
