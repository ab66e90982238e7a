"""Tick adapter for the on-device (Anakin) PPO trainer with a language-model
policy (`algo.lm`; sheeprl_tpu/algos/ppo/ppo_anakin.py + ppo_anakin_lm.py).

The program is driven through `sheeprl_tpu.cli.run_algorithm` and is not
changed. Three of its names are replaced from here before it starts:

- `sheeprl_tpu.utils.profiler.TraceProfiler` (imported inside `main` at call
  time): `tick()` is the one per-block call the trainer makes, before it
  dispatches a block. The trainer waits for a block's metrics before the next
  tick, so at a tick everything dispatched so far has run on the device. A
  tick is one block of `iters_per_block` iterations (1 in this traffic); it
  opens and closes the window, starts and stops the profiler trace and ends
  the run by raising :class:`WindowClosed`.
- `ppo_anakin._RegisteredBlock.__call__`: the block's dispatch. Each call is
  recorded (its clock, the gradient steps it ran: the length of the per-step
  losses it returned). At the first call, still in set-up, the same compiled
  block is dispatched from the same state with 1, 2 and 3 of its gradient
  steps granted (its last input), and then with all of them, which is what
  the trainer goes on from. The block donates its state, so each dispatch
  starts from the benchmark's weights made anew from the seed, a fresh
  optimizer state and host copies of the rest. What the granted dispatches
  returned is kept for `correct`: the prompts and sampled tokens, the
  rollout's log-probabilities and values (`algo.ferry_rollout`, which the
  traffic sets), the per-step losses and gradient norms, the routed layer's
  counters, and, computed at once on the device against the weights made anew,
  the norm and a 32-number sketch (sums over contiguous chunks) of Adam's
  first moment and of the parameters' change, per leaf and, for the routed
  experts' leaves, per expert.
- `ppo_anakin_lm.build_lm_agent`: takes the benchmark's weights, made from the
  seed by the reference's `init_params`, as a restored state.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np


class WindowClosed(BaseException):
    """Raised from `tick()` when the measured window (and the traced stretch)
    is over. A BaseException so that no `except Exception` of the program
    swallows it."""


def _find_adam(state: Any) -> Any:
    """The `ScaleByAdamState` inside an optax state."""
    if hasattr(state, "mu") and hasattr(state, "nu"):
        return state
    if hasattr(state, "inner_state"):
        return _find_adam(state.inner_state)
    if isinstance(state, (tuple, list)):
        for s in state:
            found = _find_adam(s)
            if found is not None:
                return found
    return None


GRANTS = (1, 2, 3)  # the gradient steps granted to the dispatches that `correct` reads
SKETCH = 32  # numbers a leaf's (or an expert's) sketch has


def _entries(x):
    """`(entries, n)` rows of a leaf: one row a routed expert for the experts' `(E, ., .)` leaves, else one row."""
    return x.reshape(x.shape[0], -1) if x.ndim == 3 else x.reshape(1, -1)


def _readings_fn():
    """Jitted on the program's device: per entry (a leaf, or one expert of an
    experts' leaf) the norm and the sketch of Adam's first moment and of the
    parameters' change, so that only a few thousand floats leave the device."""
    import jax
    import jax.numpy as jnp

    def sketch(rows):  # (entries, n) -> (entries, SKETCH): sums over contiguous chunks (the last takes the rest)
        n = rows.shape[1]
        if n < SKETCH:
            return jnp.pad(rows, ((0, 0), (0, SKETCH - n)))
        chunk = n // SKETCH
        head = rows[:, : chunk * SKETCH].reshape(rows.shape[0], SKETCH, chunk).sum(axis=2)
        return head.at[:, -1].add(rows[:, chunk * SKETCH :].sum(axis=1))

    def read(tree):
        rows = [_entries(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]
        return (jnp.concatenate([jnp.sqrt(jnp.sum(r * r, axis=1)) for r in rows]),
                jnp.concatenate([sketch(r) for r in rows]))

    def readings(p0, p, mu):
        return read(mu), read(jax.tree.map(lambda a, b: b - a, p0, p))

    return jax.jit(readings)


def entry_names(params):
    import jax

    names = []
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        names += [f"{name}[{e}]" for e in range(x.shape[0])] if x.ndim == 3 else [name]
    return names


class Adapter:
    def __init__(self, *, seconds: float, trace: bool, trace_dir: str, t_start: float, traffic: Dict[str, Any],
                 program_module: str, make_weights=None, faults: Optional[Dict[str, Any]] = None):
        self.program_module = program_module
        self.make_weights = make_weights
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_dir = trace_dir
        self.t_start = t_start
        self.warm_blocks = int(traffic.get("warmup", {}).get("blocks_after_first", 1))
        self.trace_blocks = int(traffic.get("trace_bursts", 1))
        self.faults = faults or {}

        self.ticks: List[float] = []
        self.flushes: List[Dict[str, Any]] = []  # one record per dispatched block
        self.grants = 0
        self.response_len = 0
        self.phase = "first"  # first -> warmup -> window -> trace -> done
        self.first: Optional[Dict[str, Any]] = None
        self.first_flush: Optional[Dict[str, Any]] = None
        self.staged_rows: List[Any] = []
        self.window: Dict[str, Any] = {}
        self.trace_info: Dict[str, Any] = {}
        self.compile_stats = None
        self.error: Optional[str] = None
        self.marks: Dict[str, float] = {}

    # -- patches --------------------------------------------------------------
    def install(self) -> None:
        import importlib

        import sheeprl_tpu.utils.profiler as profiler_mod
        from sheeprl_tpu.algos.ppo import ppo_anakin_lm
        from sheeprl_tpu.utils.utils import compile_stats

        self.compile_stats = compile_stats
        adapter = self
        main_mod = importlib.import_module(self.program_module)

        class TickProfiler:
            def __init__(self, cfg, log_dir):
                pass

            def tick(self, iter_num: int) -> None:
                adapter.tick(iter_num)

            def close(self) -> None:
                pass

        profiler_mod.TraceProfiler = TickProfiler

        orig_call = main_mod._RegisteredBlock.__call__

        def call(block, *args):
            return adapter.dispatch(block, orig_call, args)

        main_mod._RegisteredBlock.__call__ = call

        if self.make_weights is not None:
            orig_build = ppo_anakin_lm.build_lm_agent

            def build_lm_agent(fabric, cfg, jenv, agent_state=None):
                adapter.response_len = int(jenv.response_len)
                return orig_build(fabric, cfg, jenv, adapter.make_weights())

            ppo_anakin_lm.build_lm_agent = build_lm_agent

    # -- the block's dispatch -------------------------------------------------
    def dispatch(self, block, orig_call, args):
        rec: Dict[str, Any] = {"t0": time.perf_counter(), "rows": 0}
        if self.first is None:
            self.marks["first_block_dispatched"] = rec["t0"] - self.t_start
            try:
                out = self._first_block(block, orig_call, args)
            except BaseException as e:  # the state is donated and gone: nothing to go on from
                self.error = f"first-block readings failed: {type(e).__name__}: {e}"
                self.first = {"error": self.error}
                raise
        else:
            out = orig_call(block, *args)
        metrics = out[-1]
        rec["t1"] = time.perf_counter()
        rec["chunk"] = int(np.prod(metrics["pg_steps"].shape))
        rec["iters"] = int(metrics["pg_steps"].shape[0])
        rec["counters"] = {k: np.asarray(metrics[k]).tolist() for k in
                           ("moe_local_assignments", "moe_rollout_assignments", "moe_max_expert_load", "moe_dropped")}
        self.grants += rec["chunk"]
        self.flushes.append(rec)
        return out

    def _first_block(self, block, orig_call, args):
        """The trainer's first dispatch: the block with 1, 2 and 3 gradient
        steps granted, each from the run's first state, read for `correct`;
        then with all of them, whose outputs go back to the trainer."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        state, rest, grant_all = args[:7], args[7:-1], args[-1]
        shardings = jax.tree.map(lambda x: x.sharding, state)
        # what a fresh state is made from: the seed's weights; zeros for the optimizer's moments (checked to be zeros
        # now: a fresh run) and host copies of its scalars; host copies of the envs' state and keys
        moments = [x for x in jax.tree.leaves(state[1]) if x.size > 4096]
        if any(bool(jnp.any(x != 0)) for x in moments):
            raise RuntimeError("the optimizer's moments are not zeros at the first dispatch")
        optimizer = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype) if x.size > 4096 else np.asarray(x), state[1])
        envs = jax.device_get(state[2:])
        train_key = np.asarray(rest[0]).copy()
        del moments

        def fresh():
            zeros = jax.tree.map(
                lambda x, sharding: jnp.zeros(x.shape, x.dtype, device=sharding) if isinstance(x, jax.ShapeDtypeStruct) else x,
                optimizer, shardings[1])
            return jax.device_put((self.make_weights(), zeros, *envs), shardings)

        read, granted = _readings_fn(), []
        for i, grant in enumerate(GRANTS):
            out = orig_call(block, *(state if i == 0 else fresh()), *rest, jax.device_put(np.int32(grant), grant_all.sharding))
            params, opt_state, metrics = out[0], out[1], jax.device_get(out[-1])
            p0 = self.make_weights()
            (mu_norm, mu_sketch), (dp_norm, dp_sketch) = jax.device_get(read(p0, params, _find_adam(opt_state).mu))
            if self.faults.get("state_unchanged"):  # test-only: read as if the block had returned its state as it was
                dp_norm, dp_sketch = 0.0 * dp_norm, 0.0 * dp_sketch
            granted.append({"metrics": metrics, "mu_norm": mu_norm, "mu_sketch": mu_sketch, "dp_norm": dp_norm,
                            "dp_sketch": dp_sketch})
            names, entries = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)], entry_names(params)
            for x in jax.tree.leaves((out[:7], p0)):
                x.delete()
            del out, params, opt_state, p0
        last = granted[-1]["metrics"]
        if "rollout" not in last:
            raise RuntimeError("the block returned no rollout record: the traffic has to set algo.ferry_rollout=True")
        steps = len(GRANTS)
        self.first = {
            "names": names, "entries": entries, "train_key": train_key, "grants": list(GRANTS),
            "rollout": {k: np.asarray(v[0]) for k, v in last["rollout"].items()},  # the block's first iteration
            # the same inputs give the same rollout: sampled tokens that differ between the dispatches
            "rollout_repeats_differ": float(sum(np.sum(g["metrics"]["rollout"]["tokens"] != last["rollout"]["tokens"])
                                                for g in granted[:-1])),
            "losses": np.stack([np.asarray(last[k][0][:steps], np.float64) for k in ("pg_steps", "v_steps", "ent_steps",
                                                                                      "grad_norm_steps")], axis=1),
            "held_assignments": np.asarray(last["moe_local_assignments"][0], np.float64),
            "moe_dropped": float(sum(np.asarray(g["metrics"]["moe_dropped"]).sum() for g in granted)),
            **{k: np.stack([np.asarray(g[k], np.float64) for g in granted]) for k in ("mu_norm", "mu_sketch", "dp_norm",
                                                                                       "dp_sketch")},
        }
        self.first["seconds"] = time.perf_counter() - t0
        self.first_flush = {"rows_total": 0}
        self.marks["readings_taken"] = time.perf_counter() - self.t_start
        return orig_call(block, *fresh(), *rest, grant_all)

    # -- main thread: tick ----------------------------------------------------
    def tick(self, iter_num: int) -> None:
        now = time.perf_counter()
        if not self.ticks:
            self.marks["first_tick"] = now - self.t_start
        self.ticks.append(now)
        if self.phase == "first":
            if self.first is not None:
                self.phase = "warmup"
                self.warm_from = len(self.flushes)
            else:
                return
        if self.phase == "warmup":
            if len(self.flushes) - self.warm_from >= self.warm_blocks:
                self.window = {
                    "drain_open_s": 0.0, "t_open": time.perf_counter(), "tick_open": len(self.ticks),
                    "grants_open": self.grants, "compile_open": self.compile_stats.snapshot(),
                    "flush_open": len(self.flushes),
                }
                self.phase = "window"
            return
        if self.phase == "window":
            if now - self.window["t_open"] >= self.seconds:
                self.window.update(
                    drain_close_s=0.0, t_close=time.perf_counter(), tick_close=len(self.ticks),
                    grants_close=self.grants, compile_close=self.compile_stats.snapshot(),
                    flush_close=len(self.flushes),
                )
                if not self.trace:
                    self.phase = "done"
                    raise WindowClosed()
                import jax

                jax.profiler.start_trace(self.trace_dir)
                self.trace_info = {"t_start": time.perf_counter(), "grants_start": self.grants,
                                   "flush_start": len(self.flushes), "tick_start": len(self.ticks)}
                self.phase = "trace"
            return
        if self.phase == "trace":
            if len(self.flushes) - self.trace_info["flush_start"] >= self.trace_blocks:
                import jax

                self.trace_info.update(t_stop=time.perf_counter(), grants_stop=self.grants,
                                       flush_stop=len(self.flushes), tick_stop=len(self.ticks))
                jax.profiler.stop_trace()
                self.trace_info["t_written"] = time.perf_counter()
                self.phase = "done"
                raise WindowClosed()

    # -- after the run --------------------------------------------------------
    def after_run(self) -> None:
        pass

    def window_counts(self) -> Dict[str, Any]:
        w = self.window
        blocks = self.flushes[w["flush_open"] : w["flush_close"]]
        return {
            "seconds": w["t_close"] - w["t_open"], "t_open": w["t_open"], "t_close": w["t_close"],
            # a policy step is one sampled token of one env: iterations x the response's length
            "policy_iters": sum(b["iters"] for b in blocks) * self.response_len,
            "grants": w["grants_close"] - w["grants_open"], "bursts": len(blocks),
            "compile_open": list(w["compile_open"]), "compiles": w["compile_close"][0] - w["compile_open"][0],
            "compile_seconds": w["compile_close"][1] - w["compile_open"][1],
            "drain_open_s": w["drain_open_s"], "drain_close_s": w["drain_close_s"],
            "setup_s": w["t_open"] - self.t_start, "setup_marks": dict(self.marks),
        }

    def host_step_gaps(self) -> List[float]:
        """The host does nothing between blocks but the trainer's bookkeeping:
        the gaps between one block's return and the next one's dispatch."""
        w = self.window
        blocks = self.flushes[w["flush_open"] : w["flush_close"]]
        return [b["t0"] - a["t1"] for a, b in zip(blocks[:-1], blocks[1:])]
