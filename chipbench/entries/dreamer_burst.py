"""Tick adapter for the Dreamer-family burst trainers (`algo.hybrid_player`).

The program is driven through `sheeprl_tpu.cli.run_algorithm` and is not
changed. Three of its names are replaced from here before it starts:

- `sheeprl_tpu.utils.profiler.TraceProfiler` (imported inside `main` at call
  time): `tick()` is the one per-iteration call the trainer makes. It
  timestamps every iteration, opens and closes the window, starts and stops
  the profiler trace and ends the run by raising :class:`WindowClosed`.
- `BurstRunner.flush(key, grant_backlog)`: returns the gradient steps it
  granted to one burst. Summed, that is the gradient-step count. Until the
  first training burst the rows it stages are copied, so that the reference
  can rebuild the device ring on its own.
- `BurstRunner._step(carry_rb, job)`: the trainer thread's dispatch. At the
  first training burst, still in set-up, the same compiled burst program is
  dispatched from the same state and the same blob with 1, 2 and 3 of its
  steps granted. What those return is what `correct` compares. (The flush
  buckets need no warm-up of their own: the recipe's flushes carry 16 or 17
  rows, so the window uses the smallest bucket only, which the warm-up
  bursts compile.)

A second trainer brings its own adapter as another file of this directory,
with the same `Adapter` surface (README.md).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np


class WindowClosed(BaseException):
    """Raised from `tick()` when the measured window (and the traced stretch)
    is over. A BaseException so that no `except Exception` of the program
    swallows it."""


def _find_adam(state: Any) -> Any:
    """The `ScaleByAdamState` inside an optax chain state."""
    if hasattr(state, "mu") and hasattr(state, "nu"):
        return state
    if isinstance(state, (tuple, list)):
        for s in state:
            found = _find_adam(s)
            if found is not None:
                return found
    return None


MODULES = (("world_model", "world"), ("actor", "actor"), ("critic", "critic"))


def leaf_names(params: Dict[str, Any]) -> List[str]:
    import jax

    names = []
    for pname, _ in MODULES:
        for path, _leaf in jax.tree_util.tree_leaves_with_path(params[pname]):
            names.append(pname + jax.tree_util.keystr(path))
    return names


def _norms_fn():
    """Jitted on the program's device: per-leaf norms of Adam's first moment
    and of the parameters' change, so that only ~300 floats leave the chip."""
    import jax
    import jax.numpy as jnp

    def norms(c0, cn):
        p0, pn, on = c0[0], cn[0], cn[1]
        mu, dp = [], []
        for pname, oname in MODULES:
            adam = _find_adam(on[oname])
            mu += [jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32)))) for m in jax.tree.leaves(adam.mu)]
            dp += [
                jnp.sqrt(jnp.sum(jnp.square(b.astype(jnp.float32) - a.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p0[pname]), jax.tree.leaves(pn[pname]))
            ]
        return jnp.stack(mu), jnp.stack(dp)

    return jax.jit(norms)


class Adapter:
    def __init__(self, *, seconds: float, trace: bool, trace_dir: str, t_start: float, traffic: Dict[str, Any],
                 program_module: str, make_weights=None, faults: Optional[Dict[str, Any]] = None):
        self.program_module = program_module
        self.make_weights = make_weights  # the benchmark's own weights from the seed, or None
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_dir = trace_dir
        self.t_start = t_start
        warm = traffic.get("warmup", {})
        self.warm_bursts = int(warm.get("bursts_after_first", 4))
        self.trace_bursts = int(traffic.get("trace_bursts", 3))
        self.check_steps = int(traffic.get("check_steps", 3))
        self.faults = faults or {}

        self.lock = threading.Lock()
        self.ticks: List[float] = []  # perf_counter at each iteration's start
        self.flushes: List[Dict[str, Any]] = []  # one record per flush
        self.grants = 0
        self.jobs_submitted = 0
        self.jobs_dispatched = 0
        self.trained_dispatched = 0
        self.last_cum = None  # a small output of the last dispatched burst
        self.just_flushed = False
        self.phase = "prefill"  # prefill -> warmup -> window -> trace -> done
        self.first: Optional[Dict[str, Any]] = None  # what the first training burst returned
        self.staged_rows: List[Any] = []  # (row dict, env mask) until the first training flush
        self.first_flush: Optional[Dict[str, Any]] = None
        self.recording = True
        self.runner = None
        self.window: Dict[str, Any] = {}
        self.trace_info: Dict[str, Any] = {}
        self.compile_stats = None
        self.error: Optional[str] = None
        self.marks: Dict[str, float] = {}  # seconds since process start, of the set-up's stages

    # -- patches --------------------------------------------------------------
    def install(self) -> None:
        import sheeprl_tpu.utils.profiler as profiler_mod
        from sheeprl_tpu.utils import burst as burst_mod
        from sheeprl_tpu.utils.utils import compile_stats

        self.compile_stats = compile_stats
        adapter = self

        class TickProfiler:
            def __init__(self, cfg, log_dir):
                pass

            def tick(self, iter_num: int) -> None:
                adapter.tick(iter_num)

            def close(self) -> None:
                pass

        profiler_mod.TraceProfiler = TickProfiler

        orig_flush = burst_mod.BurstRunner.flush
        orig_step = burst_mod.BurstRunner._step

        def flush(runner, key, grant_backlog):
            return adapter.flush(runner, orig_flush, key, grant_backlog)

        def _step(runner, carry_rb, job):
            return adapter.step(runner, orig_step, carry_rb, job)

        burst_mod.BurstRunner.flush = flush
        burst_mod.BurstRunner._step = _step

        import importlib

        main_mod = importlib.import_module(self.program_module)
        if self.make_weights is not None:
            # The weights are the benchmark's, made from the seed in one jitted
            # call; the program takes them through the arguments `build_agent`
            # has for a restored state.
            import jax
            import jax.numpy as jnp

            orig_build = main_mod.build_agent

            def build_agent(fabric, actions_dim, is_continuous, cfg, obs_space, *_states):
                w = adapter.make_weights()
                return orig_build(fabric, actions_dim, is_continuous, cfg, obs_space, w["world_model"], w["actor"],
                                  w["critic"], jax.tree.map(jnp.copy, w["critic"]))

            main_mod.build_agent = build_agent

        if self.faults.get("half_batch"):
            # test-only: half of the batch left out, the mean taken over the rest
            orig_builder = main_mod.build_burst_train_step

            def build_burst_train_step(gradient_step, mesh, ring, *a, **kw):
                def halved(carry, xs):
                    batch, key = xs
                    return gradient_step(carry, ({k: v[:, : v.shape[1] // 2] for k, v in batch.items()}, key))

                return orig_builder(halved, mesh, ring, *a, **kw)

            main_mod.build_burst_train_step = build_burst_train_step

    # -- main thread: flush ---------------------------------------------------
    def flush(self, runner, orig_flush, key, grant_backlog):
        self.runner = runner
        rec: Dict[str, Any] = {"t0": time.perf_counter(), "rows": len(runner._staged)}
        if self.recording:
            rows = [({k: np.array(v) for k, v in d.items()}, np.array(m)) for d, m in runner._staged]
            self.staged_rows.extend(rows)
            pos, valid = np.array(runner.dev_pos), np.array(runner.dev_valid)
        if self.trace and self.phase == "trace":
            import jax

            with jax.profiler.TraceAnnotation("chipbench.flush"):
                chunk = orig_flush(runner, key, grant_backlog)
        else:
            chunk = orig_flush(runner, key, grant_backlog)
        if self.recording and chunk > 0:
            self.first_flush = {
                "key": np.asarray(key, np.uint32).copy(),
                "pos_before": pos,
                "valid_before": valid,
                "pos_after": np.array(runner.dev_pos),
                "valid_after": np.array(runner.dev_valid),
                "chunk": int(chunk),
                "grad_chunk": int(runner.grad_chunk),
                "rows_total": len(self.staged_rows),
            }
            self.recording = False
        rec["t1"] = time.perf_counter()
        rec["chunk"] = int(chunk)
        with self.lock:
            self.grants += int(chunk)
            self.jobs_submitted += 1
            self.flushes.append(rec)
        self.just_flushed = True
        return chunk

    # -- trainer thread: dispatch ---------------------------------------------
    def step(self, runner, orig_step, carry_rb, job):
        trained = bool(job[-1])
        if trained and self.first is None:
            try:
                carry_rb = self._first_training_burst(runner, carry_rb, job)
            except BaseException as e:  # surfaced by the harness as not correct
                self.error = f"first-burst readings failed: {type(e).__name__}: {e}"
                self.first = {"error": self.error}
        if self.faults.get("state_unchanged") and trained:
            # test-only: a step that returns its state unchanged
            out = (carry_rb, None)
        else:
            out = orig_step(runner, carry_rb, job)
        with self.lock:
            self.jobs_dispatched += 1
            self.trained_dispatched += int(trained)
            self.last_cum = out[0][0][3]
        return out

    def _first_training_burst(self, runner, carry_rb, job):
        carry, rb = carry_rb
        blob = job[0]
        layouts = runner._layouts
        burst_fn = runner._burst_fn
        t0 = time.perf_counter()
        self.marks["first_training_burst"] = t0 - self.t_start
        # the same burst with its first 1, 2, 3 steps granted
        layout = next(l for l in layouts.values() if l.nbytes == blob.shape[0])
        off, shape, dtype = next((o, s, d) for n, o, s, d in layout.segments if n == "__validmask__")
        norms = _norms_fn()
        readings: Dict[str, Any] = {"metrics": [], "names": leaf_names(carry[0])}
        for n in range(1, self.check_steps + 1):
            b = blob.copy()
            vm = np.zeros(shape, dtype)
            vm[:n] = 1.0
            b[off : off + vm.nbytes] = vm.view(np.uint8)
            if self.faults.get("state_unchanged"):
                cn, m = carry, tuple(np.zeros(()) for _ in range(10))
            else:
                cn, rb, m = burst_fn(carry, rb, b)
            mu, dp = norms(carry, cn)
            readings["metrics"].append([float(x) for x in m])
            if n == 1:
                readings["mu_norm_step1"] = np.asarray(mu)
            readings["dp_norm"] = np.asarray(dp)  # the last one stays: after `check_steps`
            del cn
        readings["seconds"] = time.perf_counter() - t0
        self.first = readings
        return (carry, rb)

    # -- main thread: tick ----------------------------------------------------
    def drain(self) -> float:
        """Wait until every burst flushed so far has run on the device."""
        import jax

        t0 = time.perf_counter()
        while True:
            with self.lock:
                done = self.jobs_dispatched >= self.jobs_submitted
                cum = self.last_cum
            if done:
                break
            if self.runner is not None:
                self.runner.raise_if_failed()
            time.sleep(0.0005)
        if cum is not None:
            jax.block_until_ready(cum)
        return time.perf_counter() - t0

    def tick(self, iter_num: int) -> None:
        now = time.perf_counter()
        if not self.ticks:
            self.marks["first_tick"] = now - self.t_start
        self.ticks.append(now)
        head = self.just_flushed  # this tick follows a flush: the head of a burst
        self.just_flushed = False
        if not head:
            return
        if self.phase == "prefill":
            if self.first is not None:
                self.phase = "warmup"
                self.warm_from = self.trained_dispatched
                self.marks["readings_taken"] = now - self.t_start
            return
        if self.phase == "warmup":
            if self.trained_dispatched - self.warm_from >= self.warm_bursts:
                drained = self.drain()
                self.window = {
                    "drain_open_s": drained,
                    "t_open": time.perf_counter(),
                    "tick_open": len(self.ticks),
                    "grants_open": self.grants,
                    "compile_open": self.compile_stats.snapshot(),
                    "flush_open": len(self.flushes),
                }
                self.phase = "window"
            return
        if self.phase == "window":
            if now - self.window["t_open"] >= self.seconds:
                drained = self.drain()
                self.window.update(
                    drain_close_s=drained,
                    t_close=time.perf_counter(),
                    tick_close=len(self.ticks),
                    grants_close=self.grants,
                    compile_close=self.compile_stats.snapshot(),
                    flush_close=len(self.flushes),
                )
                if not self.trace:
                    self.phase = "done"
                    raise WindowClosed()
                import jax

                jax.profiler.start_trace(self.trace_dir)
                self.trace_info = {
                    "t_start": time.perf_counter(),
                    "grants_start": self.grants,
                    "flush_start": len(self.flushes),
                    "tick_start": len(self.ticks),
                }
                self.phase = "trace"
            return
        if self.phase == "trace":
            if len(self.flushes) - self.trace_info["flush_start"] >= self.trace_bursts:
                import jax

                self.drain()
                self.trace_info.update(
                    t_stop=time.perf_counter(),
                    grants_stop=self.grants,
                    flush_stop=len(self.flushes),
                    tick_stop=len(self.ticks),
                )
                jax.profiler.stop_trace()
                self.trace_info["t_written"] = time.perf_counter()
                self.phase = "done"
                raise WindowClosed()

    # -- after the run --------------------------------------------------------
    def after_run(self) -> None:
        """Let the trainer thread finish what is queued and stop it (the
        program's own `close`), so that the device state can be freed."""
        if self.runner is not None:
            try:
                self.runner.close()
            except BaseException as e:  # pragma: no cover - reported, not fatal
                self.error = self.error or f"runner.close failed: {type(e).__name__}: {e}"
        self.runner = None
        self.last_cum = None

    def window_counts(self) -> Dict[str, Any]:
        w = self.window
        seconds = w["t_close"] - w["t_open"]
        policy_iters = w["tick_close"] - w["tick_open"]
        grants = w["grants_close"] - w["grants_open"]
        flushes = self.flushes[w["flush_open"] : w["flush_close"]]
        return {
            "seconds": seconds,
            "t_open": w["t_open"],
            "t_close": w["t_close"],
            "policy_iters": policy_iters,
            "grants": grants,
            "bursts": len(flushes),
            "compile_open": list(w["compile_open"]),
            "compiles": w["compile_close"][0] - w["compile_open"][0],
            "compile_seconds": w["compile_close"][1] - w["compile_open"][1],
            "drain_open_s": w["drain_open_s"],
            "drain_close_s": w["drain_close_s"],
            "setup_s": w["t_open"] - self.t_start,
            "setup_marks": dict(self.marks),
        }

    def host_step_gaps(self) -> List[float]:
        """Gaps between consecutive ticks of the window that no flush (a
        possibly blocked `submit`) lies between."""
        w = self.window
        ticks = self.ticks[w["tick_open"] - 1 : w["tick_close"]]
        blocked = [(f["t0"], f["t1"]) for f in self.flushes[w["flush_open"] : w["flush_close"]]]
        gaps, j = [], 0
        for a, b in zip(ticks[:-1], ticks[1:]):
            while j < len(blocked) and blocked[j][1] < a:
                j += 1
            if j < len(blocked) and blocked[j][0] < b and blocked[j][1] > a:
                continue
            gaps.append(b - a)
        return gaps
