"""Host-policy snapshot + trainer-thread burst dispatch (TPU-native; the
``algo.hybrid_player`` machinery shared by the Dreamer burst paths).

Two pieces:

- :class:`HostSnapshot` — the player's parameter subset packed into ONE
  bf16 vector for the device→host pull (one transfer instead of one
  blocking pull per leaf), unpacked on the host CPU where the policy runs.
- :class:`BurstRunner` — the staging rows + bounded job queue + trainer
  thread that dispatches ring bursts (see ``data/ring.py``) without ever
  blocking the env loop on the device; the queue bound is the backpressure.

Algorithm mains keep ownership of grant accounting (``Ratio``), metric
names, timers and checkpoint layout — the runner only moves data.
"""

from __future__ import annotations

import queue as _queue
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from sheeprl_tpu.analysis.lockstats import sync_lock
from sheeprl_tpu.data.ring import (
    BlobLayout,
    effective_stage_buckets,
    make_blob_layouts,
    pack_burst_blob,
    ring_cell,
    ring_view,
)
from sheeprl_tpu.utils import profiler
from sheeprl_tpu.utils.utils import host_cpu_device

__all__ = [
    "HostSnapshot",
    "TrainerThread",
    "BurstRunner",
    "HybridPlayerHarness",
    "DREAMER_METRIC_NAMES",
    "dreamer_ring_keys",
    "dreamer_stage_sizes",
    "init_device_ring",
]


def dreamer_stage_sizes(train_every: int, n_envs: int, buffer_size: int):
    """Staging-row capacity and flush-upload buckets for the Dreamer burst
    paths. A flush normally carries ``train_every`` step rows plus the odd
    ragged reset row, so the first bucket covers the common case and the cap
    leaves 4x headroom for a backed-up trainer queue; every distinct bucket
    is one extra trace/compile of the burst program."""
    slack = n_envs + 2
    stage_max = min(4 * train_every + slack, buffer_size)
    return stage_max, (train_every + slack, 2 * train_every + slack)

# Order matches the metrics tuple every Dreamer gradient_step returns.
DREAMER_METRIC_NAMES = (
    "Loss/world_model_loss", "Loss/observation_loss", "Loss/reward_loss",
    "Loss/state_loss", "Loss/continue_loss", "State/kl", "State/post_entropy",
    "State/prior_entropy", "Loss/policy_loss", "Loss/value_loss",
)


def dreamer_ring_keys(observation_space, cnn_keys, mlp_keys, actions_dim, with_is_first: bool):
    """Ring storage spec for a Dreamer family: pixel keys stay uint8 in HBM,
    vectors/action/reward/terminated are float32; ``is_first`` only for the
    families whose dynamic scan consumes it (V2/V3)."""
    specs = {}
    for k in cnn_keys:
        specs[k] = (tuple(observation_space[k].shape), jnp.uint8)
    for k in mlp_keys:
        specs[k] = (tuple(observation_space[k].shape), jnp.float32)
    specs["actions"] = ((int(np.sum(actions_dim)),), jnp.float32)
    specs["rewards"] = ((1,), jnp.float32)
    specs["terminated"] = ((1,), jnp.float32)
    if with_is_first:
        specs["is_first"] = ((1,), jnp.float32)
    return specs


def init_device_ring(fabric, ring_keys, capacity: int, n_envs: int, rb=None):
    """Allocate the device ring in its stored view, ``(capacity, n_envs) +
    ring_cell(shape)`` per key (``data/ring.py``), optionally mirroring
    restored per-env host buffers (checkpoint resume). The mirror assembles
    each key host-side, env-shaped, reshapes it there and ships it in ONE
    transfer — per-env ``.at[:, e].set`` updates would copy the full ring
    once per env per key. Returns ``(rb_dev, pos, valid)``."""
    dev_pos = np.zeros(n_envs, np.int64)
    dev_valid = np.zeros(n_envs, np.int64)
    rb_dev = {}
    if rb is None:
        # Materialize the (possibly hundreds-of-MB) empty ring ON the device:
        # a host jnp.zeros + device_put would allocate it in host memory
        # first and copy all of it host→device.
        alloc = jax.jit(
            lambda: {
                k: jnp.zeros((capacity, n_envs) + ring_cell(shape), dtype)
                for k, (shape, dtype) in ring_keys.items()
            },
            out_shardings={k: fabric.replicated for k in ring_keys},
        )
        rb_dev = alloc()
    else:
        for k, (shape, dtype) in ring_keys.items():
            host = np.zeros((capacity, n_envs) + shape, np.dtype(dtype))
            for e, sub in enumerate(rb.buffer):
                host[:, e] = np.asarray(sub.buffer[k][:, 0], dtype=host.dtype)
            rb_dev[k] = fabric.put_replicated(jnp.asarray(ring_view(host, shape)))
        for e, sub in enumerate(rb.buffer):
            dev_pos[e] = sub._pos
            dev_valid[e] = capacity if sub.full else sub._pos
    return rb_dev, dev_pos, dev_valid


class HostSnapshot:
    """Packed bf16 params snapshot for the host-CPU player.

    ``subset_fn(params)`` selects the leaves the policy needs (encoder +
    recurrent/representation/transition models + actor); everything else
    (decoders, critics, optimizer state) never crosses the wire.
    """

    def __init__(self, subset_fn: Callable[[Any], Any], params: Any, wire_dtype=jnp.bfloat16):
        self.host_device = host_cpu_device()
        # Pull the subset once to build the unravel spec — as ONE pipelined
        # batch of transfers, not leaf-by-leaf blocking pulls.
        subset_host = jax.device_put(subset_fn(params), self.host_device)
        jax.block_until_ready(subset_host)
        _, unravel = ravel_pytree(jax.tree.map(np.asarray, subset_host))
        self._pack = jax.jit(lambda p: ravel_pytree(subset_fn(p))[0].astype(wire_dtype))
        self._unpack = jax.jit(lambda v: unravel(v.astype(jnp.float32)))
        self._slot: list = [None]
        self._refresh_thread: Optional[threading.Thread] = None
        # supervised persistent refresh worker (attach_supervisor): the
        # pending slot is newest-wins, the worker owns the blocking pull
        self._pending: list = [None]
        self._pending_lock = sync_lock("HostSnapshot._pending_lock")
        self._refresh_worker = None

    def pull(self, params: Any) -> Any:
        """Blocking pack → pull → unpack (initialization / trainer thread)."""
        return self._unpack(jax.device_put(self._pack(params), self.host_device))

    def refresh(self, params: Any) -> None:
        """Store a fresh packed snapshot (called on the trainer thread; the
        blocking pull is fine there)."""
        self._slot[0] = jax.device_put(self._pack(params), self.host_device)

    def attach_supervisor(self, supervisor, name: str = "snapshot-refresh") -> None:
        """Run the device→host pulls on ONE persistent supervised worker
        instead of one-shot raw daemon threads: a pull that dies
        (``ThreadKilled`` chaos, a transport error) is restarted through the
        supervisor's restart→degrade→abort ladder instead of silently
        freezing the host policy snapshot at its last version. Crash-only
        supervision (``lease_s=None``) — a device pull waits for whatever
        the device has queued ahead of it, so it carries no lease."""
        if self._refresh_worker is not None:
            return
        self._refresh_worker = supervisor.spawn(name=name, target=self._refresh_loop, lease_s=None)

    def _refresh_loop(self, ctx) -> None:
        import time as _time

        from sheeprl_tpu.fault.inject import fault_point

        while not ctx.cancelled:
            with self._pending_lock:
                packed = self._pending[0]
            if packed is None:
                _time.sleep(0.02)
                continue
            ctx.beat()
            fault_point("burst.snapshot.refresh")  # chaos: kill-thread mid-pull
            with profiler.span("snapshot.refresh"):  # waits for the burst that made `packed`
                placed = jax.device_put(packed, self.host_device)
            self._slot[0] = placed
            with self._pending_lock:
                # a crash before this point leaves the pending pull in place,
                # so the restarted generation re-runs it (newest-wins: a
                # fresher refresh_async may already have replaced it)
                if self._pending[0] is packed:
                    self._pending[0] = None

    def refresh_async(self, params: Any) -> bool:
        """Kick off the device→host pull off-thread so the caller never
        waits on the wire. Skipped (returns False) while a previous pull is
        still in flight. With :meth:`attach_supervisor` the pull rides the
        supervised refresh worker; otherwise a one-shot thread
        (single-caller-thread contract: the check-then-act on
        ``_refresh_thread`` is not locked, so exactly ONE thread may call
        this per snapshot instance — the trainer thread in the BurstRunner
        wiring)."""
        if self._refresh_worker is not None:
            with self._pending_lock:
                if self._pending[0] is not None:
                    return False
                self._pending[0] = self._pack(params)
            return True
        if self._refresh_thread is not None and self._refresh_thread.is_alive():
            return False
        packed = self._pack(params)
        # graft-sync: disable-next-line=GS004 — one-shot fallback pull for callers
        # that never attach_supervisor(); the supervised refresh worker above is
        # the production path, and a dead one-shot pull only delays a snapshot
        self._refresh_thread = threading.Thread(
            target=lambda: self._slot.__setitem__(0, jax.device_put(packed, self.host_device)),
            daemon=True,
        )
        self._refresh_thread.start()
        return True

    def poll(self) -> Optional[Any]:
        """Main thread: the latest snapshot unpacked on the host, or None."""
        packed, self._slot[0] = self._slot[0], None
        if packed is None:
            return None
        with profiler.span("player.adopt"):
            # waited for here, where it is named, and not inside the player's
            # next forward, which needs these weights either way
            return jax.block_until_ready(self._unpack(packed))


class TrainerThread:
    """Bounded-queue SUPERVISED trainer worker: jobs go in, ``step_fn(carry,
    job)`` runs off the env loop, and the newest carry/metrics are readable
    at any time. The queue bound is the backpressure (at most ``maxsize``
    bursts in flight).

    The worker runs under a :class:`~sheeprl_tpu.fault.supervisor.Supervisor`
    (``fault.supervisor``-shaped ``supervisor_cfg``) with crash-only
    supervision (``lease_s=None`` — a burst dispatch's duration is unbounded,
    the same contract as the serve workers): a crash — including the
    un-swallowable ``ThreadKilled`` chaos action, which the old raw daemon
    thread died silently on — re-homes nothing (the carry lives in shared
    state and was not advanced by the failed step) and re-dispatches the
    in-flight job from the fresh generation; past the restart budget the
    ladder degrades/aborts and the next :meth:`submit`/:meth:`check`
    surfaces the typed supervision error instead of blocking the env loop
    against a dead consumer forever. Note the retry re-submits the SAME job
    against the SAME carry (``step_fn`` is functional over its carry), so a
    restart never double-applies a burst.

    :class:`BurstRunner` composes this with ring staging; SAC's flat
    transition ring drives it directly. The snapshot refresh worker
    (:meth:`HostSnapshot.attach_supervisor`) shares this supervisor via
    :attr:`supervisor`.
    """

    def __init__(
        self,
        step_fn: Callable[[Any, Any], Tuple[Any, Any]],
        carry: Any,
        on_step: Optional[Callable[[Any, Any], None]] = None,
        maxsize: int = 2,
        supervisor_cfg: Optional[Dict[str, Any]] = None,
        name: str = "burst-trainer",
    ) -> None:
        from sheeprl_tpu.fault.supervisor import Supervisor

        self._step_fn = step_fn
        self._on_step = on_step
        self._state = {"carry": carry, "metrics": None}
        self._lock = sync_lock("TrainerThread._lock")
        self._q: "_queue.Queue" = _queue.Queue(maxsize=maxsize)
        self._inflight: list = [None]  # job being (re)dispatched, survives a restart
        self._done = threading.Event()
        self.supervisor = Supervisor.from_config(supervisor_cfg or {}, name=name)
        self.supervisor.spawn(name=name, target=self._worker, lease_s=None)

    @property
    def carry(self) -> Any:
        with self._lock:
            return self._state["carry"]

    @property
    def metrics(self) -> Optional[Any]:
        with self._lock:
            return self._state["metrics"]

    @property
    def queue_depth(self) -> int:
        """Jobs submitted and not yet taken by the worker."""
        return self._q.qsize()

    def check(self) -> None:
        """One supervision pass (restart due workers, escalate): raises the
        typed supervision error once the ladder is exhausted."""
        self.supervisor.check()

    # old name, kept for symmetry with the pre-supervision API
    raise_if_failed = check

    def submit(self, job: Any) -> None:
        """Enqueue a burst job; back-pressure keeps driving supervision so a
        dead/degraded trainer escalates instead of deadlocking the env loop
        against a full queue nobody drains. The ``burst.submit`` span is the
        time the caller spends blocked here."""
        with profiler.span("burst.submit"):
            while True:
                self.check()
                try:
                    self._q.put(job, timeout=0.2)
                    return
                except _queue.Full:
                    continue

    def _worker(self, ctx) -> None:
        from sheeprl_tpu.fault.inject import fault_point

        while not ctx.cancelled:
            job = self._inflight[0]
            if job is None:
                try:
                    job = self._q.get(timeout=0.1)
                except _queue.Empty:
                    continue
                if job is None:  # close() sentinel: drained, expected exit
                    ctx.retire()
                    self._done.set()
                    return
                self._inflight[0] = job
            ctx.beat()
            fault_point("burst.trainer.step")  # chaos: kill-thread mid-burst
            carry, metrics = self._step_fn(self._state["carry"], job)
            with self._lock:
                self._state["carry"] = carry
                if metrics is not None:
                    self._state["metrics"] = metrics
            self._inflight[0] = None
            if self._on_step is not None:
                self._on_step(carry, metrics)

    def close(self) -> Any:
        while True:  # a dead consumer + full queue must escalate, not block
            self.check()
            try:
                self._q.put(None, timeout=0.2)
                break
            except _queue.Full:
                continue
        # drive supervision while draining: a crash mid-drain escalates (and
        # its restart re-dispatches the in-flight job) instead of hanging here
        while not self._done.wait(0.2):
            self.check()
        self.supervisor.join()
        # Joining the worker only drains the Python queue; the last dispatched
        # burst may still be executing on-device (JAX dispatch is async).
        # Block so wall-clock accounting sees a finished program, not our
        # own in-flight work.
        carry = self._state["carry"]
        jax.block_until_ready(carry)
        return carry


class _BucketPrograms:
    """``burst_fn`` lowered and compiled explicitly, once per flush bucket —
    the work its ``jax.jit`` does implicitly at a bucket's first call, no
    second trace or compile — so that the executable every burst runs is in
    hand: it is registered with the recorder (``profiler.register_program``),
    whose ``scope_table`` joins a device trace's instruction names to the
    program's region names. Called like ``burst_fn``. A callable with no
    ``lower`` (a test's fake) is called as it is."""

    def __init__(self, burst_fn: Callable) -> None:
        self._fn = burst_fn
        self._name = getattr(burst_fn, "__name__", type(burst_fn).__name__)
        self._compiled: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def program_name(self, feed: Tuple[Any, ...]) -> str:
        """``<function>/<leading size of the feed>``: the blob's bytes on the
        packed path, the staged rows on the unpacked one. One name per flush
        bucket (``make_blob_layouts`` keeps blob lengths unique)."""
        return f"{self._name}/{int(np.shape(jax.tree.leaves(feed)[0])[0])}"

    def __call__(self, carry: Any, rb: Any, *feed: Any) -> Any:
        if not hasattr(self._fn, "lower"):
            return self._fn(carry, rb, *feed)
        name = self.program_name(feed)
        compiled = self._compiled.get(name)
        if compiled is None:
            with self._lock:
                compiled = self._compiled.get(name)
                if compiled is None:
                    compiled = self._fn.lower(carry, rb, *feed).compile()
                    self._compiled[name] = compiled
                    profiler.register_program(name, compiled)
        return compiled(carry, rb, *feed)


class BurstRunner:
    """Staging + dispatch for a device-ring burst step.

    ``burst_fn(carry, rb, blob)`` is the jitted function from
    :func:`data.ring.build_burst_train_step` (``rb`` the ring as
    :func:`init_device_ring` stores it, ``blob`` one packed flush; without
    ``blob_layouts`` the feed is the unpacked ``staged, staged_mask, pos,
    valid_n, key, valid``, which only a test's fake ``burst_fn`` takes);
    ``carry`` holds the training handles (params/opts/...) and is readable
    at any time via :attr:`carry` (at most one burst stale — checkpoints
    accept that the same way the reference's decoupled SAC does).
    """

    def __init__(
        self,
        burst_fn: Callable,
        carry: Any,
        rb_dev: Dict[str, jax.Array],
        ring_keys: Dict[str, Tuple[tuple, Any]],
        n_envs: int,
        capacity: int,
        grad_chunk: int,
        stage_max: int,
        seq_len: int,
        snapshot: Optional[HostSnapshot] = None,
        snapshot_every: int = 4,
        params_of: Callable[[Any], Any] = lambda carry: carry[0],
        stage_buckets: Optional[Tuple[int, ...]] = None,
        blob_layouts: Optional[Dict[int, "BlobLayout"]] = None,
        supervisor_cfg: Optional[Dict[str, Any]] = None,
    ) -> None:
        self._burst_fn = _BucketPrograms(burst_fn)
        self._layouts = blob_layouts
        self._params_of = params_of
        self._ring_keys = ring_keys
        self._n_envs = int(n_envs)
        self._capacity = int(capacity)
        self.grad_chunk = int(grad_chunk)
        self._stage_max = int(stage_max)
        self._seq_len = int(seq_len)
        self._snapshot = snapshot
        self._snapshot_every = max(1, int(snapshot_every))
        # Upload sizes: each flush pads the staged rows to the smallest
        # bucket that fits (one jit trace per bucket). Without buckets every
        # flush ships the full ``stage_max`` staging array — for a pixel ring
        # that is ~4x the bytes actually staged.
        self._stage_buckets = list(effective_stage_buckets(stage_buckets, self._stage_max))

        self.dev_pos = np.zeros(self._n_envs, np.int64)
        self.dev_valid = np.zeros(self._n_envs, np.int64)
        self._staged: list = []  # (data dict, env mask) per ring row
        self._bursts = 0  # trained bursts; worker-thread-only state
        self._flushes = 0  # burst sequence number; main-thread-only state
        self._thread = TrainerThread(self._step, (carry, rb_dev), supervisor_cfg=supervisor_cfg)
        if snapshot is not None:
            # the refresh pulls ride the trainer's supervisor: a dead pull is
            # restarted, never a silently frozen host policy
            snapshot.attach_supervisor(self._thread.supervisor)

    # -- ring-state restore (checkpoint resume) ------------------------------
    def set_ring_state(self, pos: np.ndarray, valid: np.ndarray) -> None:
        self.dev_pos[:] = pos
        self.dev_valid[:] = valid

    # -- staging -------------------------------------------------------------
    def stage(self, row: Dict[str, np.ndarray], env_mask: np.ndarray) -> None:
        self._staged.append((row, env_mask))

    def stage_step(self, step_data: Dict[str, np.ndarray]) -> None:
        """Stage a regular all-envs row from ``(1, n_envs, ...)`` step data."""
        self.stage(
            {k: np.asarray(step_data[k][0]) for k in self._ring_keys},
            np.ones(self._n_envs, np.int32),
        )

    def stage_reset(self, reset_data: Dict[str, np.ndarray], env_idxes) -> None:
        """Stage a ragged reset row: only the done envs advance their heads
        (mirrors ``EnvIndependentReplayBuffer.add(data, env_idxes)``)."""
        row = {}
        env_mask = np.zeros(self._n_envs, np.int32)
        env_mask[env_idxes] = 1
        for k, (shape, dtype) in self._ring_keys.items():
            full_row = np.zeros((self._n_envs,) + shape, dtype)
            full_row[env_idxes] = np.asarray(reset_data[k][0])
            row[k] = full_row
        self.stage(row, env_mask)

    def patch_last(self, env_idx: int, updates: Dict[str, float]) -> None:
        """In-place edit of the most recent staged row for one env (the
        truncation patch on env-restart)."""
        if self._staged:
            for k, v in updates.items():
                self._staged[-1][0][k][env_idx] = v

    @property
    def staged_count(self) -> int:
        return len(self._staged)

    def staging_full(self) -> bool:
        return len(self._staged) >= self._stage_max - 1 - self._n_envs

    # -- trainer-thread handles ----------------------------------------------
    @property
    def carry(self) -> Any:
        return self._thread.carry[0]

    @property
    def metrics(self) -> Optional[Any]:
        return self._thread.metrics

    def raise_if_failed(self) -> None:
        self._thread.raise_if_failed()

    def _step(self, carry_rb, job):
        """One job of :meth:`flush`: ``(*feed, (burst, flush span id, bucket),
        trained)``. The ``burst.dispatch`` span is how long dispatch holds
        the trainer thread: first the compile, later the runtime's own
        back-pressure once enough bursts are in flight."""
        carry, rb = carry_rb
        *feed, (burst, flush_span, bucket), trained = job
        with profiler.span(
            "burst.dispatch", parent=flush_span, burst=burst, bucket=bucket,
            program=self._burst_fn.program_name(feed),
        ):
            carry, rb, metrics = self._burst_fn(carry, rb, *feed)
        if trained:
            self._bursts += 1
            if self._snapshot is not None and self._bursts % self._snapshot_every == 0:
                # Non-blocking: the packed device→host pull waits for this
                # burst to finish on the device, and would stall the training
                # pipeline if this thread waited on it; the refresh worker
                # owns the wait.
                self._snapshot.refresh_async(self._params_of(carry))
            return (carry, rb), metrics
        return (carry, rb), None  # append-only bursts produce junk metrics

    # -- dispatch ------------------------------------------------------------
    def flush(self, key, grant_backlog: int) -> int:
        """Package the staged rows + up to ``grad_chunk`` grants into one
        burst job. Returns the number of grants consumed (0 while any env is
        still shorter than a sample window)."""
        self._flushes += 1
        with profiler.span("burst.flush", burst=self._flushes) as flush_span:
            with profiler.span("burst.pack"):
                job, chunk, env_counts, counters = self._pack(key, grant_backlog, (self._flushes, flush_span.id))
            # `blob_bytes`: host-to-device bytes of this burst, bucket padding
            # included; `queue_depth`: jobs the trainer thread has not yet taken
            flush_span.set(chunk=chunk, queue_depth=self._thread.queue_depth, **counters)
            self._thread.submit(job)
            self.dev_pos[:] = (self.dev_pos + env_counts) % self._capacity
            self.dev_valid[:] = np.minimum(self.dev_valid + env_counts, self._capacity)
        return chunk

    def _pack(self, key, grant_backlog: int, burst: Tuple[int, int]):
        """The numpy packing of one flush: ``(job, chunk, rows written per
        env, counters of the flush span)``."""
        n_rows = len(self._staged)
        size = next(b for b in self._stage_buckets if b >= n_rows)
        arrs = {}
        for k, (shape, dtype) in self._ring_keys.items():
            arr = np.zeros((size, self._n_envs) + shape, dtype)
            for i, (data, _m) in enumerate(self._staged):
                arr[i] = data[k]
            arrs[k] = arr
        mask = np.zeros((size, self._n_envs), np.int32)
        for i, (_d, m) in enumerate(self._staged):
            mask[i] = m
        self._staged.clear()
        # Hold grants while any env is still shorter than a sample window
        # (the host buffer refuses to sample in that state).
        env_counts = mask.sum(axis=0)
        ready = (self.dev_valid + env_counts).min() >= self._seq_len
        chunk = min(self.grad_chunk, grant_backlog) if ready else 0
        validmask = np.zeros((self.grad_chunk,), np.float32)
        validmask[:chunk] = 1.0
        meta = (*burst, size)
        if self._layouts is not None:
            # One uint8 blob = one host→device transfer per flush instead
            # of eight, each with its own per-transfer latency on the
            # trainer thread.
            layout = self._layouts[size]
            values = dict(arrs)
            values["__mask__"] = mask
            values["__pos__"] = self.dev_pos
            values["__valid_n__"] = self.dev_valid
            values["__key__"] = np.asarray(key, np.uint32)
            values["__validmask__"] = validmask
            # Fresh blob per flush: the queued job must not alias a buffer a
            # later flush would overwrite while this one is still in flight.
            blob = pack_burst_blob(layout, values)
            job = (blob, meta, chunk > 0)
            blob_bytes = blob.nbytes
        else:
            job = (
                arrs, jnp.asarray(mask), jnp.asarray(self.dev_pos, jnp.int32),
                jnp.asarray(self.dev_valid, jnp.int32), key, jnp.asarray(validmask),
                meta, chunk > 0,
            )
            blob_bytes = sum(a.nbytes for a in arrs.values()) + mask.nbytes + validmask.nbytes
        return job, chunk, env_counts, {"rows": n_rows, "bucket": size, "blob_bytes": blob_bytes}

    def close(self) -> Any:
        """Stop the trainer thread and return the final carry."""
        return self._thread.close()[0]


class HybridPlayerHarness:
    """One-call orchestration of the hybrid host-player burst path for the
    Dreamer-family mains (dreamer_v1/v2/v3 and the three p2e exploration
    entry points).

    Owns everything the six mains used to instantiate by hand — ring spec,
    device-ring allocation (with checkpoint mirror), packed-bf16 host
    snapshot, :class:`BurstRunner`, grant accounting, and the per-flush
    metric fan-out — so a main keeps only its algorithm-specific pieces:
    the player-subset fn, the carry tuple, the metric names, and the host
    player construction (from :attr:`host_device`).

    The train-key stream is ``PRNGKey(cfg.seed)`` split once per flush and
    the host action stream is ``PRNGKey(cfg.seed + 17)`` — the exact streams
    the open-coded blocks used, so refactored runs are bit-identical.
    """

    def __init__(
        self,
        fabric,
        cfg,
        *,
        observation_space,
        cnn_keys,
        mlp_keys,
        actions_dim,
        capacity: int,
        seq_len: int,
        batch_size: int,
        policy_steps_per_iter: int,
        make_burst_fn: Callable[[Dict[str, int]], Callable],
        player_subset: Callable[[Any], Any],
        carry: Any,
        rb=None,
        with_is_first: bool = True,
        metric_names: Optional[Tuple[str, ...]] = None,
        aggregator=None,
        params_of: Callable[[Any], Any] = lambda c: c[0],
    ) -> None:
        hp_cfg = cfg.algo.get("hybrid_player") or {}
        train_every = max(1, int(hp_cfg.get("train_every", 16)))
        snapshot_every = max(1, int(hp_cfg.get("snapshot_every", 4)))
        n_envs = int(cfg.env.num_envs)

        self.grad_chunk = max(1, int(round(cfg.algo.replay_ratio * policy_steps_per_iter * train_every)))
        stage_max, stage_buckets = dreamer_stage_sizes(train_every, n_envs, capacity)
        buckets = effective_stage_buckets(stage_buckets, stage_max)
        self.ring_keys = dreamer_ring_keys(
            observation_space, cnn_keys, mlp_keys, actions_dim, with_is_first=with_is_first
        )
        # ring_keys + stage_buckets switch build_burst_train_step to the
        # packed single-upload job; the layouts here are the same ones the
        # device side derives (both call make_blob_layouts on these args).
        burst_fn = make_burst_fn(
            {
                "capacity": capacity,
                "n_envs": n_envs,
                "grad_chunk": self.grad_chunk,
                "seq_len": seq_len,
                "batch_size": batch_size,
                "ring_keys": self.ring_keys,
                "stage_buckets": buckets,
                "stage_max": stage_max,
            }
        )
        blob_layouts = make_blob_layouts(self.ring_keys, n_envs, self.grad_chunk, buckets)
        rb_dev, dev_pos, dev_valid = init_device_ring(fabric, self.ring_keys, capacity, n_envs, rb=rb)

        params = params_of(carry)
        self.snapshot = HostSnapshot(player_subset, params)
        self.host_device = self.snapshot.host_device
        self.host_params = self.snapshot.pull(params)
        self._host_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 17), self.host_device)
        # Train-key stream on the host CPU device: threefry is platform-
        # deterministic (bit-identical split results), and a host-resident
        # key lets the packed flush read its bytes without a device pull.
        self._rng = jax.device_put(jax.random.PRNGKey(cfg.seed), self.host_device)

        self.runner = BurstRunner(
            burst_fn,
            carry,
            rb_dev,
            self.ring_keys,
            n_envs=n_envs,
            capacity=capacity,
            grad_chunk=self.grad_chunk,
            stage_max=stage_max,
            seq_len=seq_len,
            snapshot=self.snapshot,
            snapshot_every=snapshot_every,
            params_of=params_of,
            stage_buckets=stage_buckets,
            blob_layouts=blob_layouts,
            supervisor_cfg=(cfg.get("fault") or {}).get("supervisor"),
        )
        self.runner.set_ring_state(dev_pos, dev_valid)

        self._metric_names = metric_names
        self._aggregator = aggregator
        # Late-bound {metric_name: () -> value} extras (e.g. the V1/P2E
        # exploration amount, whose host player exists only after __init__).
        self.extra_metrics: Dict[str, Callable[[], Any]] = {}

        self.grant_backlog = 0
        self.gradient_steps = 0  # cumulative per-rank gradient steps
        self.train_steps = 0  # burst dispatches that actually trained

    # -- host player ---------------------------------------------------------
    def poll(self) -> Any:
        """Adopt the newest trainer-thread snapshot, if one has landed."""
        fresh = self.snapshot.poll()
        if fresh is not None:
            self.host_params = fresh
        return self.host_params

    def host_key(self):
        self._host_rng, subkey = jax.random.split(self._host_rng)
        return subkey

    # -- staging (delegates) -------------------------------------------------
    def stage_step(self, step_data) -> None:
        self.runner.stage_step(step_data)

    def stage_reset(self, reset_data, env_idxes) -> None:
        self.runner.stage_reset(reset_data, env_idxes)

    def patch_last(self, env_idx: int, updates: Dict[str, float]) -> None:
        self.runner.patch_last(env_idx, updates)

    @property
    def carry(self) -> Any:
        return self.runner.carry

    # -- grant accounting + dispatch -----------------------------------------
    def grant(self, n: int) -> None:
        self.grant_backlog += int(n)

    def flush(self) -> int:
        from sheeprl_tpu.utils.metric import SumMetric
        from sheeprl_tpu.utils.timer import timer

        with timer("Time/train_time", SumMetric):
            self._rng, train_key = jax.random.split(self._rng)
            chunk = self.runner.flush(train_key, self.grant_backlog)
            latest = self.runner.metrics
            agg = self._aggregator
            if agg and not agg.disabled and latest is not None:
                pairs = latest.items() if isinstance(latest, dict) else zip(self._metric_names, latest)
                for name, value in pairs:
                    if name in agg:
                        agg.update(name, value)
                for name, value_fn in self.extra_metrics.items():
                    if name in agg:
                        agg.update(name, value_fn())
        self.grant_backlog -= chunk
        if chunk > 0:
            self.gradient_steps += chunk
            self.train_steps += 1
        return chunk

    def pump(self) -> None:
        """Dispatch while a full grant chunk (or a full staging buffer) is
        pending — the per-iteration train section of every burst main."""
        while self.grant_backlog >= self.grad_chunk or self.runner.staging_full():
            consumed = self.flush()
            if consumed == 0 or self.grant_backlog < self.grad_chunk:
                break

    def finish(self) -> Any:
        """Flush the tail (grants that can never execute are abandoned with
        the run), stop the trainer thread, and return the final carry."""
        while self.runner.staged_count or self.grant_backlog:
            if self.flush() == 0 and not self.runner.staged_count:
                break
        return self.runner.close()
