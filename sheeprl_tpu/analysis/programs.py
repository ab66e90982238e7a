"""The audit program registry: every hot-path program this repo dispatches in
a steady state, declared as something that can be AOT-lowered with ABSTRACT
inputs on a configurable mesh — no env, no training loop, no execution.

Each algorithm module (and the serve engine) registers a builder next to its
program constructors via :func:`register_audit_programs`. A builder takes an
:class:`AuditMesh` and yields :class:`AuditProgram` records: the jitted
callable, example inputs staged exactly the way the driver stages them (same
shardings, same dtypes), and the program's DECLARED contract — donation,
fed-back outputs, output placements, wire dtype, constant budget. The audit
(:mod:`sheeprl_tpu.analysis.audit`) lowers and compiles each program and
fails when the compiled artifact does not match the declaration.

Program names match the tracecheck hot-path names (``ppo.train_step``,
``ppo_anakin.block``, ``serve.bucket[8].greedy``, ...) so the runtime
sentinel and the static gate talk about the same inventory — and so a new
tracecheck registration without an audit registration is visible as a gap.
"""

from __future__ import annotations

import dataclasses
import importlib
import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "AuditMesh",
    "AuditProgram",
    "register_audit_programs",
    "collect_programs",
    "registered_names",
    "AUDIT_SOURCES",
]

#: Modules that register audit programs at import time. Adding a hot path to
#: a new module = add the module here + a builder there; the budget-manifest
#: completeness check then refuses to pass until the manifest covers it.
AUDIT_SOURCES: Tuple[str, ...] = (
    "sheeprl_tpu.algos.ppo.ppo",
    "sheeprl_tpu.algos.ppo.ppo_anakin",
    "sheeprl_tpu.algos.ppo.ppo_anakin_lm",
    "sheeprl_tpu.algos.ppo.ppo_anakin_population",
    "sheeprl_tpu.algos.ppo.ppo_sebulba",
    "sheeprl_tpu.algos.sac.sac",
    "sheeprl_tpu.algos.sac.sac_sebulba",
    "sheeprl_tpu.algos.sac.flywheel",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_sebulba",
    "sheeprl_tpu.serve.engine",
    "sheeprl_tpu.serve.sessions",
    "sheeprl_tpu.ops.kernels.audit",
)


@dataclasses.dataclass(frozen=True)
class AuditMesh:
    """The mesh the audit lowers against. ``devices`` must not exceed the
    process's visible device count (the CLI worker forces a virtual CPU
    platform of the right width before JAX initializes)."""

    devices: int = 2
    axes: Tuple[str, ...] = ("dp",)

    @property
    def spec(self) -> str:
        return ",".join(f"{a}={n}" for a, n in zip(self.axes, (self.devices,)))

    def build(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < self.devices:
            raise RuntimeError(
                f"audit mesh needs {self.devices} devices but only {len(devs)} are visible "
                "(the CLI worker sets --xla_force_host_platform_device_count; in-process "
                "callers must run under a wide-enough virtual platform)"
            )
        shape = (self.devices,) + (1,) * (len(self.axes) - 1)
        return Mesh(np.asarray(devs[: self.devices]).reshape(shape), self.axes)

    @property
    def wire_dtype(self) -> str:
        """The gradient-collective wire dtype the drivers would resolve on
        this mesh (``fabric.grad_reduce_dtype=auto``): bf16 whenever there is
        an actual wire."""
        return "bfloat16" if self.devices > 1 else "float32"


@dataclasses.dataclass
class AuditProgram:
    """One registered hot-path program plus its declared compile contract.

    ``fn`` is the jitted (or jit-able-staged) callable; ``args`` the example
    inputs — concrete committed arrays or ``ShapeDtypeStruct``s carrying the
    shardings the driver stages with. Everything else is the DECLARATION the
    audit holds the compiled artifact to:

    - ``donate_argnums``: argnums whose buffers the program donates; every
      donated byte must come back aliased in the executable (AUD001).
    - ``feedback_outputs``: top-level output indices the driver feeds back as
      inputs in the steady state. Their placements must be PINNED
      (``out_shardings``) — a compiler-chosen placement on a fed-back output
      is the PR 8 silent-recompile class even when it is equivalent (AUD002).
    - ``out_decl``: top-level output index -> ``PartitionSpec`` the placement
      must normalize to (AUD002 drift half).
    - ``wire_dtype``: declared collective wire dtype; under ``bfloat16``,
      f32 collective traffic beyond ``f32_collective_budget`` fails (AUD003).
    - ``constant_budget``: max bytes any single baked-in constant may occupy
      in the optimized executable (AUD004).
    """

    name: str
    fn: Any
    args: Tuple[Any, ...]
    source: str = ""
    donate_argnums: Tuple[int, ...] = ()
    feedback_outputs: Tuple[int, ...] = ()
    out_decl: Dict[int, Any] = dataclasses.field(default_factory=dict)
    mesh: Any = None
    wire_dtype: str = "float32"
    allow_f64: bool = False
    f32_collective_budget: int = 4096
    constant_budget: int = 1 << 20
    donation_slack_bytes: int = 512
    check_input_shardings: bool = True


_REGISTRY: List[Tuple[Tuple[str, ...], Callable[[AuditMesh], Iterable[AuditProgram]]]] = []


def _select_re(pat: str) -> "re.Pattern[str]":
    """``*`` is the ONLY wildcard; everything else is literal. Program names
    contain ``[N]`` (the serve buckets), which fnmatch-style globbing would
    read as a character class and never match literally."""
    return re.compile("^" + ".*".join(re.escape(part) for part in pat.split("*")) + "$")


def _matches(name: str, pat: str) -> bool:
    return name == pat or _select_re(pat).match(name) is not None


def register_audit_programs(*names: str):
    """Register a builder yielding the named audit programs (exact names, or
    ``*``-wildcard patterns like ``sac.*`` — ``*`` is the only wildcard, all
    other characters are literal). The builder runs lazily — only when an
    audit actually selects one of its names."""

    def deco(builder: Callable[[AuditMesh], Iterable[AuditProgram]]):
        _REGISTRY.append((tuple(names), builder))
        return builder

    return deco


def _import_sources() -> None:
    for mod in AUDIT_SOURCES:
        importlib.import_module(mod)


def registered_names() -> List[str]:
    """Every name/pattern the registry declares (patterns verbatim)."""
    _import_sources()
    out: List[str] = []
    for names, _ in _REGISTRY:
        out.extend(names)
    return out


def collect_programs(
    mesh: AuditMesh, select: Optional[Sequence[str]] = None
) -> List[AuditProgram]:
    """Build the selected programs (all, when ``select`` is None). Builders
    whose declared names don't match the selection never run — program setup
    (agent init, ring allocation) is the expensive part of an audit pass."""
    _import_sources()
    sel = list(select) if select else None

    def wanted(declared: Tuple[str, ...]) -> bool:
        if sel is None:
            return True
        # either direction: a selection pattern covering a declared name
        # (`sac.*` -> `sac.train_step`) or a concrete selection matching a
        # declared pattern
        return any(
            _matches(name, pat) or _matches(pat, name) for pat in sel for name in declared
        )

    out: List[AuditProgram] = []
    for names, builder in _REGISTRY:
        if not wanted(names):
            continue
        for prog in builder(mesh):
            if sel is None or any(_matches(prog.name, pat) for pat in sel):
                out.append(prog)
    seen: Dict[str, str] = {}
    for p in out:
        if p.name in seen:
            raise RuntimeError(
                f"duplicate audit program name '{p.name}' (registered by {seen[p.name]} and {p.source})"
            )
        seen[p.name] = p.source
    return out
