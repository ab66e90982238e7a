"""Server assembly: in-process client, socket front end, CLI entry glue.

:class:`PolicyServer` wires one checkpoint's :class:`ServePolicy` into the
full tier — AOT bucket engine, micro-batching scheduler, versioned weight
store, optional checkpoint-dir watcher, optional JSON-lines TCP front end —
and owns their lifecycles. :class:`PolicyClient` is the in-process caller
(the same interface a Sebulba actor thread would use as its batched-inference
backend: GA3C's predictor queue); the socket front end is a thin adapter
mapping one newline-delimited JSON request to one client call.

Wire protocol (one JSON object per line, both directions)::

    -> {"obs": {"state": [[...]]}, "n": 1}
    <- {"actions": [[...]], "version": 3}
    <- {"error": "..."}                       # per-request failure
    -> {"health": true}
    <- {"status": "ok", "ready": true, ...}   # liveness/readiness probe

    # stateful policies (graft-sessions): name your session; the server
    # carries your recurrent/latent state between requests
    -> {"obs": {...}, "session_id": "user-42"}
    -> {"obs": {...}, "session_id": "user-42", "reset": true}  # new episode

    # flywheel feedback (graft-flywheel, optional): reward/done grade the
    # PREVIOUS action served on this stream (the session, else this
    # connection) — completed transitions feed the live learner; omitting
    # them serves identically, the rows are just counted feedback_missing
    -> {"obs": {...}, "reward": 0.7, "done": false}

``obs`` leaves are RAW env observations (the server applies the algorithm's
own normalization via ``ServePolicy.prepare``); ``n`` (default 1) is the
number of batched rows in the request. ``session_id`` (stateful policies
only) binds the request to a server-side state row; ``reset`` restarts that
session's state from the policy's initial state before stepping.

Supervision: the scheduler worker and the checkpoint watcher run under one
:class:`~sheeprl_tpu.fault.supervisor.Supervisor` (config ``serve.
supervisor``) with a monitor thread — a crashed worker is restarted (the
scheduler recovers its in-flight batch: zero admitted requests dropped), and
the ``{"health": true}`` probe reports engine/scheduler/watcher/store
liveness, queue depth, weight-version staleness and per-worker restart
counts. ``serve_policy`` (the CLI body) installs SIGTERM/SIGINT handlers
that run a GRACEFUL DRAIN: stop accepting, settle every admitted request
through ``scheduler.stop(drain=True)``, then exit 0.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import socketserver
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from sheeprl_tpu.fault.supervisor import Supervisor
from sheeprl_tpu.serve.engine import BucketEngine, JitEngine, default_buckets
from sheeprl_tpu.serve.policy import ServePolicy, StatefulServePolicy
from sheeprl_tpu.serve.scheduler import RequestScheduler, ServeStats
from sheeprl_tpu.serve.sessions import SessionEngine, default_session_buckets
from sheeprl_tpu.serve.weights import CheckpointWatcher, WeightStore

__all__ = ["PolicyClient", "PolicyServer", "install_drain_handlers", "serve_policy"]


class PolicyClient:
    """In-process client: raw env obs in, env-format actions out.

    ``act`` prepares the observation (the algorithm's own host-side
    normalization), submits it to the scheduler and blocks for the result —
    concurrent callers are micro-batched into shared engine dispatches.

    ``timeout_s`` is the client-side default wait bound: per-call ``timeout``
    / ``submit_timeout`` of ``None`` fall back to it, and its expiry raises
    the typed :class:`~sheeprl_tpu.serve.scheduler.ServeTimeoutError`. The
    previous default (wait forever) meant a hung worker pinned the caller
    for the life of the process; ``None`` keeps that behavior for callers
    that explicitly want an unbounded wait.
    """

    def __init__(
        self,
        policy: ServePolicy,
        scheduler: RequestScheduler,
        timeout_s: Optional[float] = None,
        stream: Optional[str] = None,
    ) -> None:
        self.policy = policy
        self.scheduler = scheduler
        self.timeout_s = timeout_s
        # flywheel stream identity for session-less callers: feedback pairs
        # with the previous action served to THIS client object
        self.stream = stream if stream is not None else f"client-{id(self):x}"

    def act(
        self,
        obs: Dict[str, np.ndarray],
        n: int = 1,
        timeout: Optional[float] = None,
        submit_timeout: Optional[float] = None,
        session_id: Optional[str] = None,
        reset: bool = False,
        reward: Any = None,
        done: Any = None,
        stream: Optional[str] = None,
    ) -> Tuple[np.ndarray, int]:
        """Actions (``(n, action_dim)``) + the weight version that produced
        them. ``timeout`` bounds the wait for the result; ``submit_timeout``
        bounds the backpressure wait for queue space (both default to the
        client's ``timeout_s``). On a stateful server ``session_id`` carries
        this caller's recurrent/latent state between calls (``n`` must be 1
        — one user, one state row) and ``reset`` restarts it for a new
        episode. ``reward``/``done`` (optional, flywheel servers) are
        feedback on the PREVIOUS action this stream was served — a scalar or
        ``n`` values; they never change what this call returns. ``stream``
        overrides the feedback-pairing identity (the TCP front end passes
        one per connection); it defaults to the session, else this client."""
        timeout = self.timeout_s if timeout is None else timeout
        submit_timeout = self.timeout_s if submit_timeout is None else submit_timeout
        prepared = self.policy.prepare(obs, n)
        if stream is None:
            stream = session_id if session_id is not None else self.stream
        req = self.scheduler.submit(
            prepared,
            timeout=submit_timeout,
            session_id=session_id,
            reset=reset,
            reward=reward,
            done=done,
            stream=stream,
        )
        return self.scheduler.result(req, timeout=timeout)


class _JsonLineHandler(socketserver.StreamRequestHandler):
    def handle(self) -> None:  # one connection, many newline-framed requests
        server: "_TcpFrontEnd" = self.server  # type: ignore[assignment]
        # session-less feedback pairs against THIS connection's stream
        conn_stream = f"conn-{self.client_address[0]}:{self.client_address[1]}"
        for raw in self.rfile:
            line = raw.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
                if msg.get("health"):
                    resp = server.health_fn()
                    self.wfile.write((json.dumps(resp) + "\n").encode())
                    self.wfile.flush()
                    continue
                obs = {k: np.asarray(v) for k, v in msg["obs"].items()}
                n = int(msg.get("n", 1))
                session_id = msg.get("session_id")
                if session_id is not None:
                    session_id = str(session_id)
                # submit_timeout: under sustained overload the request must
                # error out (backpressure made visible), not pin this
                # connection's thread forever — serve_config.yaml promises it
                actions, version = server.client.act(
                    obs,
                    n=n,
                    timeout=server.request_timeout_s,
                    submit_timeout=server.request_timeout_s,
                    session_id=session_id,
                    reset=bool(msg.get("reset", False)),
                    reward=msg.get("reward"),
                    done=msg.get("done"),
                    stream=session_id if session_id is not None else conn_stream,
                )
                resp = {"actions": np.asarray(actions).tolist(), "version": int(version)}
            except Exception as e:  # per-request: report, keep the connection
                resp = {"error": f"{type(e).__name__}: {e}"}
            try:
                self.wfile.write((json.dumps(resp) + "\n").encode())
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):  # client went away
                return


class _TcpFrontEnd(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(
        self,
        addr,
        client: PolicyClient,
        request_timeout_s: float = 30.0,
        health_fn: Optional[Callable[[], Dict[str, Any]]] = None,
    ) -> None:
        super().__init__(addr, _JsonLineHandler)
        self.client = client
        self.request_timeout_s = request_timeout_s
        self.health_fn = health_fn or (lambda: {"status": "unknown"})


class PolicyServer:
    """One checkpoint, fully assembled and lifecycle-managed.

    ``serve_cfg`` mirrors the ``serve:`` block of ``serve_config.yaml``
    (buckets, mode, max_wait_ms, max_batch, queue_bound, host/port, watch
    options); any mapping with those keys works. ``engine="naive"`` swaps in
    the per-request jit-dispatch :class:`JitEngine` — the bench baseline.
    """

    def __init__(
        self,
        policy: ServePolicy,
        serve_cfg: Optional[Dict[str, Any]] = None,
        watch_dir: "str | None" = None,
        engine: str = "aot",
        stats: Optional[ServeStats] = None,
    ) -> None:
        cfg = dict(serve_cfg or {})
        self.policy = policy
        self.stats = stats or ServeStats()
        mode = str(cfg.get("mode", "greedy"))
        if mode not in ("greedy", "sample"):
            raise ValueError(f"serve.mode must be greedy|sample, got {mode!r}")
        buckets = cfg.get("buckets") or default_buckets()
        stateful = isinstance(policy, StatefulServePolicy)
        if stateful:
            # graft-sessions: per-user state rows behind the same admission
            # tier. serve.session.* sizes the cache and (optionally) its own
            # bucket ladder; a "naive" baseline is session.buckets=[1] +
            # max_batch=1, not the JitEngine (state must never retrace).
            if engine != "aot":
                raise ValueError(
                    "stateful policies serve through the AOT session engine; for a naive "
                    "per-session baseline use serve.session.buckets=[1] with serve.max_batch=1"
                )
            scfg = dict(cfg.get("session") or {})
            self.engine: Any = SessionEngine(
                policy,
                buckets=scfg.get("buckets") or default_session_buckets(),
                mode=mode,
                max_sessions=int(scfg.get("max_sessions", 1024)),
                ttl_s=float(scfg.get("ttl_s", 300.0)),
                sweep_every_s=float(scfg.get("sweep_every_s", 1.0)),
            )
        elif engine == "aot":
            self.engine = BucketEngine(policy, buckets=buckets, mode=mode)
        elif engine == "naive":
            self.engine = JitEngine(policy, mode=mode)
        else:
            raise ValueError(f"engine must be 'aot' or 'naive', got {engine!r}")
        self.weights = WeightStore(policy.params, policy.params_from_state, stats=self.stats)
        max_wait_ms = cfg.get("max_wait_ms", 5.0)
        self.scheduler = RequestScheduler(
            self.engine,
            self.weights,
            max_wait_s=float(max_wait_ms) / 1e3,
            max_batch=cfg.get("max_batch"),
            queue_bound=int(cfg.get("queue_bound", 256)),
            greedy=mode == "greedy",
            seed=int(cfg.get("seed", 0) or 0),
            stats=self.stats,
            sessions=self.engine.cache if stateful else None,
        )
        self.client = PolicyClient(policy, self.scheduler, timeout_s=cfg.get("client_timeout_s"))
        self._request_timeout_s = float(cfg.get("request_timeout_s", 30.0) or 30.0)
        # staleness alarm: weights older than this flip the probe to degraded
        # (Serve/weights_stale counts the ok->stale transitions) so a wedged
        # publisher is VISIBLE instead of silently serving old weights forever
        _max_stale = cfg.get("max_staleness_s")
        self._max_staleness_s = float(_max_stale) if _max_stale else None
        self._was_stale = False
        self._watch_publish_current = bool(cfg.get("watch_publish_current", False))
        # one supervisor over the serving workers (scheduler + watcher):
        # restart-on-crash with in-flight recovery, health-probe visibility
        self.supervisor = Supervisor.from_config(
            dict(cfg.get("supervisor") or {}), name="serve", max_restarts=3, backoff=0.25
        )
        self.watcher: Optional[CheckpointWatcher] = None
        if watch_dir is not None:
            self.watcher = CheckpointWatcher(
                watch_dir,
                self.weights,
                poll_s=float(cfg.get("watch_poll_s", 2.0)),
                stats=self.stats,
                quarantine_after=int(cfg.get("watcher_quarantine_after", 3)),
            )
        self._tcp: Optional[_TcpFrontEnd] = None
        self._tcp_thread: Optional[threading.Thread] = None
        self._host = str(cfg.get("host", "127.0.0.1"))
        self._port = cfg.get("port", None)
        self._draining = False
        # graft-flywheel: best-effort trajectory logging behind the resolve
        # path. Misconfiguration fails HERE — at build time, before a socket
        # binds — never in the middle of serving traffic.
        self.flywheel = None
        self.learner_probe: Optional[Callable[[], Dict[str, Any]]] = None  # wired by serve_policy/fleet
        fly = dict(cfg.get("flywheel") or {})
        if fly.get("enabled"):
            from sheeprl_tpu.serve.flywheel import FlywheelConfigError, TrajectoryLog
            from sheeprl_tpu.utils.registry import (
                registered_flywheel_ingest_names,
                resolve_flywheel_ingest,
            )

            if resolve_flywheel_ingest(str(policy.name)) is None:
                raise FlywheelConfigError(
                    f"serve.flywheel is enabled but the algorithm named '{policy.name}' has no "
                    f"registered learner-ingest builder. Algorithms with flywheel support: "
                    f"{', '.join(registered_flywheel_ingest_names())}."
                )
            if not fly.get("dir"):
                raise FlywheelConfigError(
                    "serve.flywheel.enabled=True needs serve.flywheel.dir (the shared spool "
                    "directory the learner tails); `serve --flywheel` derives it from the "
                    "checkpoint dir automatically"
                )
            self.flywheel = TrajectoryLog(
                fly["dir"],
                policy.obs_spec,
                int(policy.action_dim),
                replica=str(fly.get("replica") or f"replica-{os.getpid()}"),
                block_rows=int(fly.get("block_rows", 256) or 256),
                queue_blocks=int(fly.get("queue_blocks", 8) or 8),
                flush_s=float(fly.get("flush_s", 0.25) or 0.25),
                max_streams=int(fly.get("max_streams", 4096) or 4096),
            )
            self.scheduler.flywheel = self.flywheel
            self.stats._flywheel_fn = self.flywheel.snapshot

    # -- lifecycle ----------------------------------------------------------- #

    @property
    def address(self) -> Optional[Tuple[str, int]]:
        """Bound (host, port) of the socket front end, if one is up."""
        return self._tcp.server_address[:2] if self._tcp is not None else None

    def start(self, with_socket: Optional[bool] = None) -> "PolicyServer":
        self.scheduler.start(supervisor=self.supervisor)
        if self.watcher is not None:
            # publish_current (serve.watch_publish_current; fleet replicas set
            # it): adopt the newest complete save immediately, so a RESPAWNED
            # replica rejoins the fleet on the freshest weights instead of
            # the checkpoint it was originally launched from
            self.watcher.start(publish_current=self._watch_publish_current, supervisor=self.supervisor)
        self.supervisor.start_monitor(poll_s=0.5)
        want_socket = (self._port is not None) if with_socket is None else with_socket
        if want_socket:
            port = int(self._port or 0)
            self._tcp = _TcpFrontEnd(
                (self._host, port),
                self.client,
                request_timeout_s=self._request_timeout_s,
                health_fn=self.health,
            )
            # graft-sync: disable-next-line=GS004 — socketserver accept loop; its
            # lifecycle is serve_forever/shutdown, a supervised respawn would
            # re-bind the listening socket out from under live clients
            self._tcp_thread = threading.Thread(target=self._tcp.serve_forever, name="serve-tcp", daemon=True)
            self._tcp_thread.start()
        return self

    def health(self) -> Dict[str, Any]:
        """Liveness/readiness snapshot (also served over the socket as
        ``{"health": true}``): per-component liveness, queue depth, weight
        version + staleness, supervisor restart counters, drain state."""
        sched_alive = self.scheduler.worker_alive()
        watcher_alive = self.watcher.alive() if self.watcher is not None else None
        fatal = self.supervisor.fatal
        staleness = self.weights.staleness_s
        stale = self._max_staleness_s is not None and staleness > self._max_staleness_s
        if stale and not self._was_stale:
            self.stats.add("weights_stale", 1)
        self._was_stale = stale
        healthy = sched_alive and watcher_alive in (None, True) and fatal is None and not stale
        status = "draining" if self._draining else ("ok" if healthy else "degraded")
        workers = self.supervisor.snapshot()
        out: Dict[str, Any] = {
            "status": status,
            # ready == this process can usefully take NEW traffic
            "ready": bool(sched_alive and not self._draining),
            "engine": {
                "kind": type(self.engine).__name__,
                "buckets": [int(b) for b in (self.engine.buckets or ())],
            },
            "scheduler": {
                "alive": bool(sched_alive),
                "queue_depth": int(self.scheduler._q.qsize()),
                "restarts": int(workers.get("serve-scheduler", {}).get("restarts", 0)),
            },
            "weights": {
                "version": int(self.weights.version),
                # fleet-comparable weight identity: per-replica version
                # counters restart at 0 on a respawn, the published
                # checkpoint STEP does not — the router's rolling-swap
                # monotonicity rides this field
                "step": int(self.watcher._last_step) if self.watcher is not None else int(self.weights.version),
                "staleness_s": round(staleness, 3),
                "stale": bool(stale),
            },
            "supervisor": {"fatal": str(fatal) if fatal is not None else None, "workers": workers},
        }
        if self.watcher is not None:
            out["watcher"] = {
                "alive": bool(watcher_alive),
                "errors": int(self.stats.watcher_errors),
                "published": int(self.watcher.published),
                "quarantined": [str(p) for p in sorted(self.watcher.quarantined)],
                "restarts": int(workers.get("serve-ckpt-watcher", {}).get("restarts", 0)),
            }
        if self.flywheel is not None:
            fl = self.flywheel.snapshot()
            out["flywheel"] = {
                "rows_logged": int(fl["rows_logged"]),
                "rows_shed": int(fl["rows_shed"]),
                "feedback_missing": int(fl["feedback_missing"]),
                "feedback_orphans": int(fl["feedback_orphans"]),
                "transport_depth": int(fl["transport_depth"]),
                "rows_spooled": int(fl["rows_spooled"]),
                "spool_bytes": int(fl["spool_bytes"]),
                "errors": int(fl["errors"]),
                "replica": str(self.flywheel.replica),
            }
            if self.learner_probe is not None:
                out["flywheel"]["learner"] = self.learner_probe()
        cache = getattr(self.engine, "cache", None)
        if cache is not None:
            s = cache.snapshot()
            out["sessions"] = {
                "live": int(s["live"]),
                "peak": int(s["peak"]),
                "max_sessions": int(s["max_sessions"]),
                "opened": int(s["opened"]),
                "evictions": int(s["evicted_lru"] + s["evicted_ttl"]),
                "ttl_evictions": int(s["evicted_ttl"]),
                "resets": int(s["resets"]),
                "client_resets": int(s["client_resets"]),
                "state_bytes": int(s["state_bytes"]),
                "ttl_s": float(s["ttl_s"]),
            }
        return out

    def stop(self) -> None:
        """Graceful drain: stop accepting (socket down, submits closed),
        settle every admitted request, then tear the workers down."""
        self._draining = True
        if self._tcp is not None:
            self._tcp.shutdown()
            self._tcp.server_close()
            self._tcp = None
        # stop restarts BEFORE joining workers: a crash racing shutdown must
        # fall through to the scheduler's straggler settlement, not respawn
        self.supervisor.request_stop()
        self.supervisor.stop_monitor()
        if self.watcher is not None:
            self.watcher.stop()
        self.scheduler.stop(drain=True)
        if self.flywheel is not None:
            # AFTER the drain: the settled stragglers' rows still spool
            self.flywheel.close()

    def __enter__(self) -> "PolicyServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def request_over_socket(addr: Tuple[str, int], obs: Dict[str, Any], n: int = 1, timeout: float = 30.0) -> Dict[str, Any]:
    """One request/response round trip over the JSON-lines protocol (test &
    example helper — real clients keep one connection open for many
    requests)."""
    with socket.create_connection(addr, timeout=timeout) as sock:
        payload = {"obs": {k: np.asarray(v).tolist() for k, v in obs.items()}, "n": n}
        sock.sendall((json.dumps(payload) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = sock.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf.decode())


def install_drain_handlers(
    event: threading.Event, signals: Tuple[int, ...] = (signal.SIGTERM, signal.SIGINT)
) -> Callable[[], None]:
    """Install handlers that flag ``event`` for a graceful drain; returns a
    restore callable. A no-op off the main thread (Python only delivers
    signals there). SIGTERM — the orchestrator's shutdown verb (k8s,
    systemd, a TPU-pod preemption notice) — previously killed the process
    mid-batch; now it stops accepting, settles every admitted request and
    exits 0."""
    if threading.current_thread() is not threading.main_thread():
        return lambda: None

    def _handler(signum, frame) -> None:
        # flag FIRST; then announce via os.write — a print() here can raise
        # "reentrant call" if the signal lands while the main thread holds
        # the stdout buffer lock, and must never cost us the drain flag
        event.set()
        try:
            name = signal.Signals(signum).name
            os.write(
                1,
                f"serve: received {name} — graceful drain "
                "(stop accepting, settle admitted requests, exit 0)\n".encode(),
            )
        except OSError:  # stdout gone (orchestrator tore the pipe down)
            pass

    previous = {s: signal.signal(s, _handler) for s in signals}

    def _restore() -> None:
        for s, h in previous.items():
            try:
                signal.signal(s, h)
            except (ValueError, TypeError):  # interpreter tearing down
                pass

    return _restore


def resolve_builder_state(builder, state: Dict[str, Any], checkpoint_path, algo_name: str):
    """What of the loaded checkpoint does this builder get? Builders that
    declare a ``full_state`` parameter receive the whole state (the
    population builder reads ``best_member`` from it; the dreamer family
    checkpoints its models as top-level trees with no ``agent`` key and
    rebuilds from the full state). For everyone else the ``agent`` tree is
    REQUIRED: a missing one on a builder that can only consume it would
    silently serve random-init weights — fail loudly instead."""
    import inspect

    wants_full_state = False
    try:
        wants_full_state = "full_state" in inspect.signature(builder).parameters
    except (TypeError, ValueError):  # pragma: no cover - exotic callables
        pass
    builder_kwargs = {"full_state": state} if wants_full_state else {}
    agent_state = state.get("agent")
    if agent_state is None and not wants_full_state:
        raise RuntimeError(
            f"checkpoint {checkpoint_path} has no 'agent' state and the "
            f"'{algo_name}' policy builder does not accept full_state — refusing to "
            "serve untrained random-init weights"
        )
    return agent_state, builder_kwargs


def serve_policy(fabric, cfg: Dict[str, Any], state: Dict[str, Any], builder) -> None:
    """CLI entrypoint body: build the policy from the checkpoint and serve.

    Runs until ``serve.max_requests`` requests have been answered (None →
    forever), SIGTERM/SIGINT (graceful drain via :func:`install_drain_handlers`
    → ``PolicyServer.stop`` → ``scheduler.stop(drain=True)``, exit 0) or
    KeyboardInterrupt; prints a ``Serve/*`` stats snapshot every
    ``serve.log_every_s`` seconds and once on shutdown.
    """
    import gymnasium as gym

    from sheeprl_tpu.envs.factory import make_env
    from sheeprl_tpu.utils.logger import get_log_dir
    from sheeprl_tpu.utils.utils import refuse_children_on_tpu

    flywheel = (cfg.get("serve") or {}).get("flywheel") or {}
    if flywheel.get("enabled") and flywheel.get("learner", True):  # before anything is built
        refuse_children_on_tpu("serve --flywheel", "a learner process beside the server")
    log_dir = get_log_dir(cfg, cfg.root_dir, cfg.run_name) if cfg.get("root_dir") and cfg.get("run_name") else None
    env = make_env(cfg, cfg.seed, 0, log_dir, "serve", vector_env_idx=0)()
    observation_space = env.observation_space
    action_space = env.action_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    env.close()

    agent_state, builder_kwargs = resolve_builder_state(
        builder, state, cfg.get("checkpoint_path"), str(cfg.algo.name)
    )
    policy = builder(fabric, cfg, observation_space, action_space, agent_state, **builder_kwargs)
    serve_cfg = dict(cfg.get("serve", {}))
    watch_dir = None
    if serve_cfg.get("watch"):
        from pathlib import Path

        watch_dir = str(Path(cfg.checkpoint_path).parent)
    fly_cfg = dict(serve_cfg.get("flywheel") or {})
    if fly_cfg.get("enabled"):
        from pathlib import Path

        # the spool dir defaults to a sibling of the served checkpoint so
        # `serve --flywheel` is one flag: replicas spool there, the learner
        # tails it, and the published checkpoints land in the watched dir
        if not fly_cfg.get("dir"):  # the composed config carries dir: null
            fly_cfg["dir"] = str(Path(cfg.checkpoint_path).parent / "flywheel")
        if not fly_cfg.get("replica"):
            fly_cfg["replica"] = f"replica-{os.getpid()}"
        serve_cfg["flywheel"] = fly_cfg
    server = PolicyServer(policy, serve_cfg, watch_dir=watch_dir)
    learner_sup = None
    if fly_cfg.get("enabled") and fly_cfg.get("learner", True):
        from sheeprl_tpu.serve.flywheel import LearnerSupervisor

        learner_sup = LearnerSupervisor(cfg, fly_cfg["dir"])
        server.learner_probe = learner_sup.probe
    max_requests = serve_cfg.get("max_requests")
    log_every_s = float(serve_cfg.get("log_every_s", 10.0) or 10.0)
    drain = threading.Event()
    restore_handlers = install_drain_handlers(drain)
    server.start()
    addr = server.address
    if addr is not None:
        print(f"serving {cfg.algo.name} on {addr[0]}:{addr[1]} (buckets={list(server.engine.buckets) or 'jit'})")
    try:
        last_log = time.perf_counter()
        while not drain.is_set():
            drain.wait(0.2)
            if learner_sup is not None:
                # status-mtime heartbeat + the supervisor engine: a wedged
                # learner is SIGKILLed and respawned from HERE, while the
                # serve tier above keeps answering untouched
                learner_sup.tick()
            now = time.perf_counter()
            if now - last_log >= log_every_s:
                print(json.dumps({**server.stats.snapshot(), **server.engine.stats()}))
                last_log = now
            if max_requests is not None and server.stats.requests >= int(max_requests):
                break
    except KeyboardInterrupt:  # raw ^C with handlers already restored/absent
        pass
    finally:
        server.stop()  # graceful drain: nothing admitted is dropped
        if learner_sup is not None:
            learner_sup.stop()
        restore_handlers()
        print(json.dumps({**server.stats.snapshot(), **server.engine.stats()}))
        if drain.is_set():
            print("serve: drained cleanly")
