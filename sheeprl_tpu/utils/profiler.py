"""The program's profiling seam: the ``jax.profiler`` trace hook, the names
of the device regions, and the in-memory record of what the host does.

Three things live here and nowhere else:

- :class:`TraceProfiler` — iteration-windowed ``jax.profiler`` trace
  (config group ``metric``)::

      profiler:
        enabled: False
        start_iter: 8      # first traced iteration (lets compiles finish)
        num_iters: 4       # how many iterations to capture

  The trace lands in ``<log_dir>/profiler`` and opens in TensorBoard's or
  Perfetto's trace viewer.
- :data:`REGIONS` / :data:`KERNEL_PREFIX` — the ``jax.named_scope`` names
  inside the burst program. A scope only writes ``op_name`` metadata; the
  optimized executable carries it, and :meth:`Recorder.scope_table` joins it
  to the instruction names a device trace shows
  (:func:`sheeprl_tpu.analysis.hlo.op_scopes`).
- :class:`Recorder` (process-wide instance :data:`RECORDER`, functions
  :func:`span`, :func:`snapshot`, :func:`reset`, :func:`register_program`,
  :func:`programs`, :func:`program`, :func:`scope_table`) — a bounded flight
  recorder of host spans on ``time.perf_counter()``. It is always on: a span
  costs two clock reads and one ``deque.append``, the last :data:`CAPACITY`
  spans are kept in memory, and nothing is written anywhere. Each span is
  also entered as a ``jax.profiler.TraceAnnotation`` — a no-op while no
  profiler session runs — so an operator's ``metric.profiler.enabled=True``
  trace shows the same spans on the device trace's clock. After a stall,
  ``snapshot()`` from a debugger or a signal handler says where each thread
  was.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

__all__ = [
    "TraceProfiler",
    "REGIONS",
    "BURST_REGIONS",
    "LM_BLOCK_REGIONS",
    "KERNEL_PREFIX",
    "SPANS",
    "CAPACITY",
    "ROOT",
    "Span",
    "Recorder",
    "RECORDER",
    "span",
    "snapshot",
    "reset",
    "register_program",
    "programs",
    "program",
    "scope_table",
]

#: ``jax.named_scope`` names of the burst program's regions, outermost level.
#: Regions are disjoint: an instruction belongs to the outermost region on
#: its ``op_name`` path. Backward work of a region carries
#: ``transpose(jvp(<region>))``.
BURST_REGIONS: Tuple[str, ...] = (
    "wm.encoder",
    "wm.dynamics",
    "wm.decoder",
    "wm.heads",
    "wm.optim",
    "behaviour.imagination",
    "behaviour.returns",
    "behaviour.heads",
    "behaviour.optim",
    "target.ema",
    "ring.append",
    "ring.sample",
)
#: The same for the on-device PPO block with a language-model policy
#: (``algos/ppo/ppo_anakin_lm.py``): the two rollout phases are outermost
#: there, so the model's own scopes inside them count to the phase; the rest
#: lies in the update.
LM_BLOCK_REGIONS: Tuple[str, ...] = (
    "rollout.prefill",
    "rollout.decode",
    "lm.embed",
    "lm.attn_global",
    "lm.attn_window",
    "lm.attn_mla",
    "lm.moe",
    "lm.ffn_shared",
    "lm.head_loss",
    "ppo.gae",
    "ppo.optim",
    "env.token",
)
REGIONS: Tuple[str, ...] = BURST_REGIONS + LM_BLOCK_REGIONS
#: ``ops.kernels.registry.dispatch(name)`` runs its kernel, whichever tier,
#: under ``jax.named_scope(KERNEL_PREFIX + name)``.
KERNEL_PREFIX = "kernel."

#: Host span names (where each is recorded: PERF.md section 3).
SPANS: Tuple[str, ...] = (
    "iter",
    "player.adopt",
    "player.act",
    "stage",
    "env.step",
    "burst.flush",
    "burst.pack",
    "burst.submit",
    "burst.dispatch",
    "snapshot.refresh",
)

CAPACITY = 65536
#: ``parent=ROOT`` opens a span with no parent and drops whatever an aborted
#: iteration left open on this thread.
ROOT = 0

_TraceAnnotation = None


def _annotation(name: str, counters: Dict[str, Any]):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **counters)


class Span:
    """One host span: ``(name, id, parent, thread, t_start, t_end,
    counters)`` on ``time.perf_counter()``. A context manager; ``start()`` /
    ``stop()`` do the same where a ``with`` block does not fit. It enters the
    record when it ends."""

    __slots__ = ("name", "id", "parent", "thread", "t_start", "t_end", "counters", "_recorder", "_annotation")

    def __init__(self, recorder: "Recorder", name: str, parent: Optional[int], counters: Dict[str, Any]):
        self.name = name
        self.id = next(recorder._ids)
        self.parent = parent
        self.thread = threading.get_ident()
        self.t_start = self.t_end = 0.0
        self.counters = counters
        self._recorder = recorder
        self._annotation = None

    def set(self, **counters: Any) -> None:
        """Counters known only while the span runs (they reach the record,
        not the profiler's annotation, which is written at entry)."""
        self.counters.update(counters)

    def start(self) -> "Span":
        stack = self._recorder._stack()
        if self.parent == ROOT:
            del stack[:]
        elif self.parent is None:
            self.parent = stack[-1].id if stack else ROOT
        stack.append(self)
        self._annotation = _annotation(self.name, self.counters)
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def stop(self) -> None:
        self.t_end = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        stack = self._recorder._stack()
        if self in stack:  # also closes what was left open inside this span
            del stack[stack.index(self) :]
        self._recorder._ring.append(self)

    __enter__ = start

    def __exit__(self, *exc: Any) -> bool:
        self.stop()
        return False

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "thread": self.thread,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "counters": dict(self.counters),
        }


class Recorder:
    """A ring of the last ``capacity`` finished spans, and the compiled
    programs whose scope tables a reader may ask for. Thread-safe: ids come
    from one counter, the open-span stack is per thread, and the ring is a
    ``deque`` (its ``append`` is atomic)."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        self._ring: "collections.deque[Span]" = collections.deque(maxlen=int(capacity))
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._programs: Dict[str, Any] = {}
        self._tables: Dict[str, Tuple[Any, Dict[str, Dict[str, Any]]]] = {}

    def _stack(self) -> List[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, parent: Optional[int] = None, **counters: Any) -> Span:
        """A span named ``name``. Its parent is the innermost span open on
        this thread, or ``parent`` (the id of a span of another thread, or
        :data:`ROOT`)."""
        return Span(self, name, parent, counters)

    def snapshot(self, t0: Optional[float] = None, t1: Optional[float] = None) -> Dict[str, Any]:
        """Finished spans that overlap ``[t0, t1]`` (``perf_counter``
        seconds; ``None`` is open-ended), oldest first, with how many spans
        were started in all, how many the ring holds and how many it can."""
        for _ in range(8):
            try:
                spans = list(self._ring)
                break
            except RuntimeError:  # appended to while copied
                continue
        else:
            spans = []
        recorded = max((s.id for s in spans), default=0)
        out = [
            s.as_dict()
            for s in spans
            if (t0 is None or s.t_end >= t0) and (t1 is None or s.t_start <= t1)
        ]
        return {"spans": out, "counters": {"recorded": recorded, "held": len(spans), "capacity": self._ring.maxlen}}

    def reset(self) -> None:
        """Forget the recorded spans (registered programs stay)."""
        self._ring.clear()

    # -- compiled programs ----------------------------------------------------
    def register_program(self, name: str, compiled: Any) -> None:
        """Keep the ``jax.stages.Compiled`` a runner dispatches under
        ``name`` (a later program of the same name replaces it). Nothing is
        parsed until :meth:`scope_table` is asked."""
        self._programs[name] = compiled

    def programs(self) -> Tuple[str, ...]:
        return tuple(self._programs)

    def program(self, name: str) -> Optional[Any]:
        """The ``Compiled`` registered under ``name``."""
        return self._programs.get(name)

    def scope_table(self, name: str) -> Optional[Dict[str, Dict[str, Any]]]:
        """``{instruction: {"scope", "outer", "backward"}}`` of the optimized
        executable registered under ``name``
        (:func:`sheeprl_tpu.analysis.hlo.op_scopes`), parsed on first demand;
        ``None`` for a name nobody registered."""
        compiled = self._programs.get(name)
        if compiled is None:
            return None
        cached = self._tables.get(name)
        if cached is None or cached[0] is not compiled:
            from sheeprl_tpu.analysis.hlo import op_scopes

            cached = (compiled, op_scopes(compiled.as_text(), regions=REGIONS, kernel_prefix=KERNEL_PREFIX))
            self._tables[name] = cached
        return cached[1]


RECORDER = Recorder()
span = RECORDER.span
snapshot = RECORDER.snapshot
reset = RECORDER.reset
register_program = RECORDER.register_program
programs = RECORDER.programs
program = RECORDER.program
scope_table = RECORDER.scope_table


class TraceProfiler:
    """Iteration-windowed ``jax.profiler`` trace: call :meth:`tick` once per
    training iteration; the trace starts/stops itself around the configured
    window. Safe no-op when disabled."""

    def __init__(self, cfg: Optional[Mapping[str, Any]], log_dir: str):
        prof_cfg = dict(cfg or {})
        self.enabled = bool(prof_cfg.get("enabled", False))
        self.start_iter = int(prof_cfg.get("start_iter", 8))
        self.num_iters = int(prof_cfg.get("num_iters", 4))
        self.trace_dir = os.path.join(log_dir, "profiler")
        self._active = False
        self._done = False

    def tick(self, iter_num: int) -> None:
        if not self.enabled or self._done:
            return
        import jax

        if not self._active and iter_num >= self.start_iter:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._active = True
            self._stop_at = iter_num + self.num_iters
        elif self._active and iter_num >= self._stop_at:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
