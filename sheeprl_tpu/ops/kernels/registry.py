"""The kernel dispatch registry — one switch for the whole Pallas tier.

Every kernel in :mod:`sheeprl_tpu.ops.kernels` ships as a triple:

- a **plain-lax reference** — a literal extraction of the inline math the
  call site ran before the kernel existed, so ``ops.backend=lax`` reproduces
  the historical graphs bit-for-bit;
- a **Pallas kernel** wrapped in ``jax.custom_vjp`` (Pallas forward, the
  reference chain re-derived on the backward), or, for the names in
  :data:`COMPILED_BY_XLA`, the same fused form in plain ``jax.numpy`` where
  that measured faster on the chip than a Mosaic body;
- a **registry entry** binding the two under one name.

Call sites go through :func:`dispatch`, which picks the implementation from
the process-global backend (``ops.backend=auto|pallas|lax``) with optional
per-kernel overrides (``ops.kernels.<name>=...``). ``auto`` resolves to the
Pallas tier iff this process's default JAX backend is a TPU and the kernel
is not listed in :data:`AUTO_LAX_ON_TPU` (kernels the TPU compiler refuses,
routed to their lax reference by name). Processes without a TPU keep the
plain-lax references unless a config or test explicitly opts into the
interpret-mode kernel path.

Within the Pallas tier the *lowering platform* picks the code
(:func:`platform_dispatch`): a TPU lowering compiles the Mosaic kernel; a
CPU lowering in a process that holds a TPU (the hybrid host player) takes
the lax reference; the Pallas interpreter runs only in a process with no
TPU at all. :func:`tier` names which of the three a kernel gets.

Backend resolution happens at *trace* time and the chosen value is constant
for the life of the process (it is config, not data), so switching backends
never introduces retraces inside a warmed-up program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax

from sheeprl_tpu.utils.profiler import KERNEL_PREFIX

__all__ = [
    "AUTO_LAX_ON_TPU",
    "COMPILED_BY_XLA",
    "Kernel",
    "UnknownKernelError",
    "UnknownOpsBackendError",
    "VALID_BACKENDS",
    "backend",
    "configure",
    "configure_from_config",
    "dispatch",
    "get",
    "names",
    "overrides",
    "platform_dispatch",
    "register",
    "resolve",
    "tier",
    "use_backend",
]

VALID_BACKENDS: Tuple[str, ...] = ("auto", "pallas", "lax")

# Kernels whose Pallas variant the TPU compiler refuses, with its message.
# ``auto`` routes them to the lax reference on TPU, statically and by name;
# an explicit ``ops.kernels.<name>=pallas`` still reaches the kernel and the
# compiler's error.
AUTO_LAX_ON_TPU: Dict[str, str] = {
    "sumtree_sample": (
        "jax 0.9.0 Pallas-Mosaic gather rule: 'ValueError: Shape mismatch in input, "
        "indices and output' for the (1, 2P) tree against (1, B) draws; with equal "
        "shapes Mosaic stops at 'Not implemented: Multiple source vregs along gather "
        "dimension' (tpu.dynamic_gather reaches 128 lanes or 8 sublanes, not a tree)"
    ),
}

# Kernels whose kernel-tier entry is plain ``jax.numpy`` on every platform,
# with the measurement that decided it: the same body as a Mosaic kernel was
# slower on the chip and was deleted. ``auto`` still resolves them to the
# kernel tier on a TPU (the lax reference stays the literal extraction the
# parity tests compare against); :func:`tier` names them ``xla``, and the
# checks that every other entry lowers to a Mosaic custom call pass them by.
COMPILED_BY_XLA: Dict[str, str] = {
    "two_hot_symlog_loss": (
        "PERF.md finding 31, TPU v5e: one hat-function contraction fused by XLA, the critic's two "
        "losses in one read of their logits, 0.029 ms a gradient step against 0.064 ms as a Mosaic "
        "kernel (block 1024 rows); train_step_ms 18.116 against 18.237"
    ),
}


class UnknownOpsBackendError(ValueError):
    """``ops.backend`` (or a per-kernel override) named a backend the
    registry does not know."""

    def __init__(self, backend: Any, kernel: Optional[str] = None):
        scope = f"kernel '{kernel}'" if kernel else "ops.backend"
        super().__init__(
            f"Unknown ops backend {backend!r} for {scope}; valid backends are "
            f"{', '.join(VALID_BACKENDS)}."
        )
        self.backend = backend
        self.kernel = kernel


class UnknownKernelError(KeyError):
    """A dispatch or override referenced a kernel name that was never
    registered."""

    def __init__(self, name: Any):
        known = ", ".join(sorted(_REGISTRY)) or "<none>"
        super().__init__(f"Unknown kernel '{name}'; registered kernels: {known}.")
        self.name = name


@dataclasses.dataclass(frozen=True)
class Kernel:
    """One registry entry: the lax reference and its Pallas counterpart.

    Both callables share one signature; the reference is also the ground
    truth for the Pallas variant's parity tests and backward pass.
    """

    name: str
    reference: Callable[..., Any]
    pallas: Callable[..., Any]
    doc: str = ""


_REGISTRY: Dict[str, Kernel] = {}
# Seeded from the environment so bench/CI runs can flip the tier without a
# config file; validated lazily (at first resolve) with the named error.
_BACKEND: str = os.environ.get("SHEEPRL_TPU_OPS_BACKEND", "auto")
_OVERRIDES: Dict[str, str] = {}


def register(name: str, *, reference: Callable, pallas: Callable, doc: str = "") -> Kernel:
    """Register a (reference, pallas) pair under ``name`` (module-import
    side effect of each kernel module; duplicate names are a bug)."""
    if name in _REGISTRY:
        raise ValueError(f"Kernel '{name}' registered twice.")
    kernel = Kernel(name=name, reference=reference, pallas=pallas, doc=doc)
    _REGISTRY[name] = kernel
    return kernel


def get(name: str) -> Kernel:
    """The registry entry for ``name`` (named error on unknown kernels)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownKernelError(name) from None


def names() -> Tuple[str, ...]:
    """Sorted names of every registered kernel."""
    return tuple(sorted(_REGISTRY))


def _check_backend(value: Any, kernel: Optional[str] = None) -> str:
    if value not in VALID_BACKENDS:
        raise UnknownOpsBackendError(value, kernel)
    return value


def backend() -> str:
    """The process-global backend selector (``auto`` until configured)."""
    return _BACKEND


def overrides() -> Dict[str, str]:
    """A copy of the per-kernel backend overrides."""
    return dict(_OVERRIDES)


def configure(
    backend: Optional[str] = None,
    overrides: Optional[Mapping[str, str]] = None,
    *,
    reset: bool = False,
) -> None:
    """Set the process-global backend and/or per-kernel overrides.

    Unknown backend strings raise :class:`UnknownOpsBackendError`; override
    keys must name registered kernels (:class:`UnknownKernelError`).
    ``reset=True`` restores the defaults first (used by tests/bench).
    """
    global _BACKEND
    if reset:
        _BACKEND = "auto"
        _OVERRIDES.clear()
    if backend is not None:
        _BACKEND = _check_backend(str(backend))
    for key, value in (overrides or {}).items():
        get(key)
        _OVERRIDES[key] = _check_backend(str(value), kernel=key)


def configure_from_config(ops_cfg: Any) -> None:
    """Wire the ``ops:`` config block (``ops.backend`` + ``ops.kernels``)
    into the registry. Accepts ``None``/missing blocks (defaults stand)."""
    if not ops_cfg:
        return
    if hasattr(ops_cfg, "get"):
        backend = ops_cfg.get("backend")
        kernels = ops_cfg.get("kernels")
    else:  # pragma: no cover - plain-attribute config objects
        backend = getattr(ops_cfg, "backend", None)
        kernels = getattr(ops_cfg, "kernels", None)
    configure(backend=backend, overrides=dict(kernels or {}))


def _process_has_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(name: str, backend: Optional[str] = None) -> str:
    """The concrete backend (``pallas`` or ``lax``) kernel ``name`` will run
    on: explicit per-call ``backend`` > per-kernel override > global knob,
    with ``auto`` meaning Pallas iff ``jax.default_backend() == "tpu"`` and
    the kernel is not in :data:`AUTO_LAX_ON_TPU`."""
    get(name)
    chosen = backend if backend is not None else _OVERRIDES.get(name, _BACKEND)
    chosen = _check_backend(str(chosen), kernel=name)
    if chosen == "auto":
        chosen = "pallas" if _process_has_tpu() and name not in AUTO_LAX_ON_TPU else "lax"
    return chosen


def tier(name: str) -> str:
    """What kernel ``name`` lowers to in this process: ``lax``, ``pallas``
    (Mosaic on TPU lowerings, the lax reference on host-CPU lowerings),
    ``pallas-interpret`` (explicit Pallas opt-in without a TPU) or ``xla``
    (a kernel-tier entry of :data:`COMPILED_BY_XLA`, with or without a TPU)."""
    if resolve(name) == "lax":
        return "lax"
    if name in COMPILED_BY_XLA:
        return "xla"
    return "pallas" if _process_has_tpu() else "pallas-interpret"


def dispatch(name: str, backend: Optional[str] = None) -> Callable[..., Any]:
    """The callable to run for kernel ``name`` under the active backend: the
    chosen implementation (``__wrapped__``) under
    ``jax.named_scope("kernel.<name>")``, so that a compiled program marks the
    same work by the same name whichever tier ran it
    (``utils.profiler.KERNEL_PREFIX``; a scope only writes ``op_name``
    metadata)."""
    kernel = get(name)
    impl = kernel.pallas if resolve(name, backend) == "pallas" else kernel.reference

    @functools.wraps(impl)
    def scoped(*args: Any, **kwargs: Any) -> Any:
        with jax.named_scope(KERNEL_PREFIX + name):
            return impl(*args, **kwargs)

    return scoped


@contextlib.contextmanager
def use_backend(backend: Optional[str] = None, *, reset: bool = False, **kernel_overrides: str):
    """Temporarily reconfigure the registry (tests, the bench lane, and the
    audit runner). ``reset=True`` starts from the defaults — the audit pins
    the registry this way so manifests stay environment-invariant."""
    global _BACKEND
    saved_backend, saved_overrides = _BACKEND, dict(_OVERRIDES)
    try:
        configure(backend=backend, overrides=kernel_overrides, reset=reset)
        yield
    finally:
        _BACKEND = saved_backend
        _OVERRIDES.clear()
        _OVERRIDES.update(saved_overrides)


def platform_dispatch(pallas_forward: Callable[..., Any], reference: Callable[..., Any], *args: Any) -> Any:
    """Run the Pallas tier of one kernel, the code chosen at LOWERING time.

    In a process that holds a TPU, one op can be traced for the chip and for
    a host-CPU player: the TPU lowering compiles ``pallas_forward(*args,
    interpret=False)`` and every other platform lowers ``reference(*args)``.
    A process with no TPU reaches this only through an explicit
    ``ops.backend=pallas`` and runs the Pallas interpreter (the non-interpret
    ``pallas_call`` has no CPU lowering).
    """
    if not _process_has_tpu():
        return pallas_forward(*args, interpret=True)
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(pallas_forward, interpret=False),
        default=reference,
    )
