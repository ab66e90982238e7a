"""graft-fleet: replicated policy serving behind a health-routed front end.

One :class:`~sheeprl_tpu.serve.server.PolicyServer` is a process; a
production tier serving millions of users is N replica processes where
whole-process death, slow replicas and mid-swap kills are routine operating
conditions (Sample Factory, arXiv 2006.11751; Podracer's pod topology,
arXiv 2104.06272). :class:`FleetRouter` is the front end over that fleet —
it speaks the SAME newline-delimited JSON protocol as a single server, so a
client cannot tell (and does not care) whether it is talking to one replica
or thirty:

- **least-loaded routing among READY replicas.** Readiness comes from each
  replica's existing ``{"health": true}`` probe, polled on a cadence by the
  router's health loop; load is the router's live in-flight count per
  replica (tie-broken by the probe's queue depth).
- **session-sticky routing with counted re-homing.** A stateful session's
  replica owns its slab row, so every request for ``session_id`` goes to
  its HOME replica. When that replica dies the session is re-homed to a
  survivor and the re-init is **counted** (``sessions_rehomed``) and
  **client-visible**: the first re-homed request is forwarded with the
  protocol's existing ``reset`` semantics and the response carries
  ``"rehomed": true`` — a re-homed stream restarts visibly from its initial
  state, never silently from wrong state.
- **bounded retry-on-failover.** A connection-level failure to a replica
  (it died mid-request) re-routes the request to a survivor within a
  per-request ``retry_budget``; stateless requests are idempotent
  (at-least-once), session requests re-home-with-reset as above.
- **fleet-wide load shedding.** When no READY replica has capacity (all at
  ``max_inflight``, or none ready), the router answers with the existing
  ``ServeOverloadedError`` backpressure error instead of queueing
  unboundedly; a replica's own overload answer is retried once toward a
  less-loaded survivor, then propagated.
- **rolling swaps with fleet-monotone versions.** Every replica watches the
  SAME checkpoint dir (its own
  :class:`~sheeprl_tpu.serve.weights.CheckpointWatcher`), so a new complete
  save rolls across the fleet as each replica's poll fires. Per-replica
  version counters are local (they restart on a respawn); the router keys
  monotonicity on the published checkpoint STEP (the probe's
  ``weights.step``): each connection carries a version floor, routing
  prefers replicas at-or-above it, and every response is annotated with a
  non-decreasing ``fleet_version`` — a client never observes weights going
  backwards across replicas.
- **supervised replica lifecycle.** With a
  :class:`~sheeprl_tpu.fault.procsup.ProcessSupervisor` the router's health
  loop feeds probe successes in as liveness beats and drives ``check()``:
  a SIGKILLed replica is detected (rc = -9, distinct from a hang), its
  sessions are re-homed eagerly, and the respawned process re-publishes the
  newest complete save (``serve.watch_publish_current``). The process-tier
  chaos actions (``kill-replica`` / ``hang-replica``,
  :func:`~sheeprl_tpu.fault.inject.set_replica_chaos`) arm against this
  loop's ``serve.fleet.tick`` fault point.
- **drain honors the PR 10 SIGTERM contract end-to-end.** ``stop()`` closes
  router admission, settles the in-flight routed requests, SIGTERMs each
  replica (each runs its own graceful drain and exits 0), and the fleet CLI
  exits 0.

Config rides ``serve.fleet.*`` (``serve_config.yaml``); the operator guide
is ``howto/serving.md#the-serve-fleet``.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import socketserver
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from sheeprl_tpu.analysis.lockstats import sync_lock, sync_rlock
from sheeprl_tpu.fault import inject
from sheeprl_tpu.fault.inject import fault_point
from sheeprl_tpu.fault.procsup import ProcessSupervisor
from sheeprl_tpu.fault.supervisor import SupervisionError

__all__ = [
    "FleetReplicaError",
    "ReplicaEndpoint",
    "FleetRouter",
    "free_port",
    "replica_command",
    "serve_fleet",
]


class FleetReplicaError(RuntimeError):
    """Connection-level failure talking to one replica (dial, read, timeout,
    or a torn/unparseable response). The router's failover path catches
    this; it never reaches a client unless the retry budget is exhausted."""

    def __init__(self, replica: str, detail: str, timed_out: bool = False) -> None:
        self.replica = replica
        self.timed_out = timed_out
        super().__init__(f"replica '{replica}': {detail}")


def free_port(host: str = "127.0.0.1") -> int:
    """One OS-assigned free TCP port (the replica-port picker)."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class ReplicaEndpoint:
    """One replica's client-side face: pooled JSON-lines connections with
    connect/read timeouts, plus the router-maintained health view.

    The timeout is the fleet's half of the hung-replica bugfix: a replica
    that accepts connections but never answers (wedged dispatch, SIGSTOP)
    fails the caller with a typed :class:`FleetReplicaError` inside
    ``request_timeout_s`` instead of pinning the router thread forever.
    """

    def __init__(
        self,
        name: str,
        host: str,
        port: int,
        connect_timeout_s: float = 2.0,
        request_timeout_s: float = 30.0,
    ) -> None:
        self.name = name
        self.host = host
        self.port = int(port)
        self.connect_timeout_s = float(connect_timeout_s)
        self.request_timeout_s = float(request_timeout_s)
        self._pool: List[socket.socket] = []
        self._pool_lock = sync_lock("ReplicaEndpoint._pool_lock")
        # router-maintained view (written by the health loop / failover path)
        self.ready = False
        self.status = "unknown"
        self.version = -1
        self.step = -1  # published checkpoint step: the fleet-comparable id
        self.queue_depth = 0
        self.health: Dict[str, Any] = {}
        self.consecutive_failures = 0
        self.inflight = 0  # router-tracked concurrent requests
        self.probe_inflight = False  # one probe per endpoint at a time

    @property
    def address(self) -> Tuple[str, int]:
        return (self.host, self.port)

    # -- connection pool ------------------------------------------------------
    def _checkout(self) -> Tuple[socket.socket, bool]:
        with self._pool_lock:
            if self._pool:
                return self._pool.pop(), True
        sock = socket.create_connection(self.address, timeout=self.connect_timeout_s)
        return sock, False

    def _checkin(self, sock: socket.socket) -> None:
        with self._pool_lock:
            self._pool.append(sock)

    def close(self) -> None:
        """Drop every pooled connection (a respawned replica's old sockets
        are dead; the next request dials fresh)."""
        with self._pool_lock:
            pool, self._pool = self._pool, []
        for sock in pool:
            try:
                sock.close()
            except OSError:
                pass

    # -- one request/response round trip --------------------------------------
    def _round_trip(self, sock: socket.socket, line: bytes, timeout_s: float) -> Dict[str, Any]:
        sock.settimeout(timeout_s)
        sock.sendall(line)
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = sock.recv(65536)
            if not chunk:
                raise ConnectionResetError("replica closed the connection mid-response")
            buf += chunk
        return json.loads(buf.decode())

    def _attempt(self, sock: socket.socket, line: bytes, timeout_s: float) -> Dict[str, Any]:
        """One round trip on ``sock``; on ANY failure the socket is closed
        and a typed :class:`FleetReplicaError` raised (``timed_out`` set for
        read timeouts — the wedged-replica signal)."""
        try:
            return self._round_trip(sock, line, timeout_s)
        except socket.timeout as e:
            try:
                sock.close()
            except OSError:
                pass
            raise FleetReplicaError(self.name, f"no response within {timeout_s}s", timed_out=True) from e
        except (OSError, ValueError) as e:
            try:
                sock.close()
            except OSError:
                pass
            raise FleetReplicaError(self.name, f"{type(e).__name__}: {e}") from e

    def request(self, payload: Dict[str, Any], timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """One JSON-lines round trip. A non-timeout failure on a POOLED
        socket retries once on a fresh dial (the pooled socket may simply be
        stale from a respawn); a timeout never retries — that would double
        the wait, and it means the replica is wedged, not the socket."""
        timeout_s = self.request_timeout_s if timeout_s is None else float(timeout_s)
        line = (json.dumps(payload) + "\n").encode()
        try:
            sock, pooled = self._checkout()
        except OSError as e:  # dial refused/unreachable: the replica is gone
            raise FleetReplicaError(self.name, f"{type(e).__name__}: {e}") from e
        try:
            resp = self._attempt(sock, line, timeout_s)
        except FleetReplicaError as first:
            if not pooled or first.timed_out:
                raise
            try:  # stale pooled socket: one fresh dial before giving up
                sock = socket.create_connection(self.address, timeout=self.connect_timeout_s)
            except OSError as e:
                raise FleetReplicaError(self.name, f"{type(e).__name__}: {e}") from e
            resp = self._attempt(sock, line, timeout_s)
        self._checkin(sock)
        return resp

    def probe(self, timeout_s: float) -> Dict[str, Any]:
        """One ``{"health": true}`` round trip (never pooled with request
        traffic beyond the shared pool; cheap either way)."""
        return self.request({"health": True}, timeout_s=timeout_s)


class _ConnState:
    """Per-client-connection routing state: the weight-version floor that
    makes ``fleet_version`` monotone for this client."""

    __slots__ = ("floor",)

    def __init__(self) -> None:
        self.floor = -1


class _RouterHandler(socketserver.StreamRequestHandler):
    def handle(self) -> None:  # one connection, many newline-framed requests
        server: "_RouterTcp" = self.server  # type: ignore[assignment]
        conn = _ConnState()
        for raw in self.rfile:
            line = raw.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
                if msg.get("health"):
                    resp = server.router.health()
                else:
                    # tracked: router drain waits for in-flight handler
                    # requests to settle before tearing anything down
                    resp = server.router._serve_tracked(msg, conn)
            except Exception as e:  # per-request: report, keep the connection
                resp = {"error": f"{type(e).__name__}: {e}"}
            try:
                self.wfile.write((json.dumps(resp) + "\n").encode())
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):  # client went away
                return


class _RouterTcp(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, router: "FleetRouter") -> None:
        super().__init__(addr, _RouterHandler)
        self.router = router


class FleetRouter:
    """Health-routed front end over N replica endpoints (module docstring).

    ``fleet_cfg`` mirrors the ``serve.fleet`` block of ``serve_config.yaml``
    (``health_poll_s``, ``health_timeout_s``, ``retry_budget``,
    ``max_inflight``, ``request_timeout_s``, plus the supervision knobs the
    :class:`~sheeprl_tpu.fault.procsup.ProcessSupervisor` reads). With
    ``procsup`` the router drives the supervision engine from its health
    loop; with ``owns_replicas`` its ``stop()`` also drains the replica
    processes (the fleet CLI path).
    """

    def __init__(
        self,
        endpoints: List[ReplicaEndpoint],
        fleet_cfg: Optional[Dict[str, Any]] = None,
        procsup: Optional[ProcessSupervisor] = None,
        owns_replicas: bool = False,
        host: str = "127.0.0.1",
        port: Optional[int] = 0,
    ) -> None:
        if not endpoints:
            raise ValueError("a fleet needs at least one replica endpoint")
        cfg = dict(fleet_cfg or {})
        self.endpoints = list(endpoints)
        self._by_name = {ep.name: ep for ep in self.endpoints}
        if len(self._by_name) != len(self.endpoints):
            raise ValueError("replica endpoint names must be unique")
        self.procsup = procsup
        self.owns_replicas = bool(owns_replicas)
        self.health_poll_s = float(cfg.get("health_poll_s", 0.25) or 0.25)
        self.health_timeout_s = float(cfg.get("health_timeout_s", 2.0) or 2.0)
        self.retry_budget = max(0, int(cfg.get("retry_budget", 2)))
        self.max_inflight = max(1, int(cfg.get("max_inflight", 64)))
        self.request_timeout_s = float(cfg.get("request_timeout_s", 30.0) or 30.0)
        self._host = host
        self._port = port
        self._lock = sync_rlock("FleetRouter._lock")
        self.counters: Dict[str, int] = {
            "requests": 0,
            "routed": 0,
            "retries": 0,
            "shed": 0,
            "replica_errors": 0,
            "replica_overloads": 0,
            "sessions_rehomed": 0,
            "version_fallbacks": 0,  # served below a connection's floor (honestly annotated)
        }
        self._session_home: Dict[str, str] = {}
        self._pending_reset: set = set()
        self._deaths_seen: Dict[str, int] = {}
        self._rr = 0  # rotating tie-break over equally-loaded replicas
        self._tick_errors = 0  # unexpected health-tick failures (visible, not silent)
        self.fatal: Optional[BaseException] = None
        self._draining = False
        self._stop = threading.Event()
        self._tcp: Optional[_RouterTcp] = None
        self._tcp_thread: Optional[threading.Thread] = None
        self._health_thread: Optional[threading.Thread] = None
        self._frontend_inflight = 0

    # -- lifecycle ------------------------------------------------------------
    @property
    def address(self) -> Optional[Tuple[str, int]]:
        """Bound (host, port) of the router front end, if one is up."""
        return self._tcp.server_address[:2] if self._tcp is not None else None

    def start(self, with_socket: Optional[bool] = None) -> "FleetRouter":
        if self.procsup is not None:
            # process-tier chaos: kill-replica / hang-replica actions target
            # THIS fleet's replicas (first live one, deterministic order)
            inject.set_replica_chaos(kill=self._chaos_kill, hang=self._chaos_hang)
        # graft-sync: disable-next-line=GS004 — the health loop DRIVES the process
        # supervisor's check(); it cannot ride the engine it is the heartbeat of
        self._health_thread = threading.Thread(target=self._health_loop, name="fleet-health", daemon=True)
        self._health_thread.start()
        want_socket = (self._port is not None) if with_socket is None else with_socket
        if want_socket:
            self._tcp = _RouterTcp((self._host, int(self._port or 0)), self)
            # graft-sync: disable-next-line=GS004 — socketserver accept loop; its
            # lifecycle is serve_forever/shutdown, a supervised respawn would
            # re-bind the listening socket out from under live clients
            self._tcp_thread = threading.Thread(target=self._tcp.serve_forever, name="fleet-tcp", daemon=True)
            self._tcp_thread.start()
        return self

    def wait_ready(self, n: Optional[int] = None, timeout_s: float = 180.0) -> bool:
        """Block until ``n`` replicas (default: all) are READY; False on
        timeout. Startup convenience — replicas pay imports + AOT compiles
        before their first probe can succeed."""
        want = len(self.endpoints) if n is None else int(n)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if sum(1 for ep in self.endpoints if ep.ready) >= want:
                return True
            time.sleep(0.05)
        return sum(1 for ep in self.endpoints if ep.ready) >= want

    def stop(self, drain_replicas: Optional[bool] = None) -> None:
        """Graceful fleet drain, outermost-first: stop router admission
        (socket down), settle the in-flight routed requests, then — when the
        router owns the processes — SIGTERM each replica so every one runs
        its own PR 10 drain and exits 0."""
        with self._lock:
            # serve_request reads _draining under the lock; an unguarded
            # write here was graft-sync GS001's first real catch
            self._draining = True
        if self._tcp is not None:
            self._tcp.shutdown()
            self._tcp.server_close()
            self._tcp = None
        # settle: every request already inside a handler finishes its routed
        # round trip (bounded by the per-request timeout + retries)
        deadline = time.monotonic() + self.request_timeout_s * (1 + self.retry_budget) + 5.0
        while time.monotonic() < deadline:
            with self._lock:
                if self._frontend_inflight == 0:
                    break
            time.sleep(0.01)
        self._stop.set()
        if self._health_thread is not None:
            self._health_thread.join(timeout=5.0)
            self._health_thread = None
        drain = self.owns_replicas if drain_replicas is None else bool(drain_replicas)
        if drain and self.procsup is not None:
            self.procsup.terminate_all()
        for ep in self.endpoints:
            ep.close()

    def __enter__(self) -> "FleetRouter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- health loop ----------------------------------------------------------
    def _health_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.health_tick()
            except Exception:  # the loop itself must never die — but COUNT it
                with self._lock:
                    self._tick_errors += 1
            self._stop.wait(self.health_poll_s)

    def _probe_one(self, ep: ReplicaEndpoint) -> None:
        with self._lock:
            if ep.probe_inflight:  # a wedged replica must not pile probes up
                return
            ep.probe_inflight = True
        try:
            health = ep.probe(self.health_timeout_s)
        except FleetReplicaError:
            with self._lock:
                ep.consecutive_failures += 1
                ep.ready = False
                ep.status = "unreachable"
                ep.probe_inflight = False
            ep.close()
            return
        with self._lock:
            ep.consecutive_failures = 0
            ep.probe_inflight = False
            ep.health = health
            ep.status = str(health.get("status", "unknown"))
            ep.ready = bool(health.get("ready", False))
            weights = health.get("weights") or {}
            ep.version = int(weights.get("version", -1))
            # step only ever advances: a replica mid-respawn briefly
            # reports -1, which must not un-know a published step
            ep.step = max(ep.step, int(weights.get("step", -1)))
            ep.queue_depth = int((health.get("scheduler") or {}).get("queue_depth", 0))
        if self.procsup is not None:
            self.procsup.beat(ep.name)

    def health_tick(self) -> None:
        """One poll pass: probe every replica CONCURRENTLY, feed liveness
        beats, drive the process supervisor, re-home the sessions of any
        replica that died since the last pass. Probes must not run serially:
        a wedged replica burning its probe timeout would delay every later
        replica's beat, and with enough wedged replicas a HEALTHY one's
        lease could expire purely from tick scheduling — a false
        hang-SIGKILL. Exposed for deterministic tests."""
        fault_point("serve.fleet.tick")  # chaos: kill-replica / hang-replica
        if len(self.endpoints) == 1:
            self._probe_one(self.endpoints[0])
        else:
            # fire-and-forget with the per-endpoint probe_inflight guard: the
            # tick must NOT wait on the slowest probe either — a wedged
            # replica's probe burning its timeout would hold back this tick's
            # (and the next ticks') beats for every healthy replica, whose
            # leases would then expire from scheduling alone. Beats land
            # asynchronously as each probe completes.
            for ep in self.endpoints:
                # graft-sync: disable-next-line=GS004 — deliberate fire-and-forget
                # probe (bounded by probe_inflight + the probe timeout): a probe
                # is itself the liveness signal, supervising it would be circular
                threading.Thread(target=self._probe_one, args=(ep,), daemon=True).start()
        if self.procsup is not None:
            try:
                self.procsup.check()
            except SupervisionError as e:
                self.fatal = e
            for handle in self.procsup.replicas():
                if handle.deaths > self._deaths_seen.get(handle.name, 0):
                    self._deaths_seen[handle.name] = handle.deaths
                    ep = self._by_name.get(handle.name)
                    if ep is not None:
                        with self._lock:
                            ep.ready = False
                            ep.status = "dead"
                        ep.close()
                        self._rehome_all(handle.name)

    def _chaos_kill(self) -> None:
        for handle in self.procsup.replicas() if self.procsup else ():
            if handle.is_alive():
                os.kill(handle.pid(), signal.SIGKILL)
                return

    def _chaos_hang(self) -> None:
        for handle in self.procsup.replicas() if self.procsup else ():
            if handle.is_alive():
                os.kill(handle.pid(), signal.SIGSTOP)
                return

    # -- session re-homing -----------------------------------------------------
    def _rehome_all(self, dead_name: str) -> None:
        """Eagerly un-home every session living on a dead replica: each is
        COUNTED once and flagged for a client-visible reset on its next
        request (lazy target assignment — the survivor is picked when the
        session next speaks, by then the fleet state is current)."""
        with self._lock:
            sids = [sid for sid, home in self._session_home.items() if home == dead_name]
            for sid in sids:
                del self._session_home[sid]
                self._pending_reset.add(sid)
                self.counters["sessions_rehomed"] += 1

    # -- routing ---------------------------------------------------------------
    def _pick(self, floor: int, exclude: set) -> Optional[ReplicaEndpoint]:
        """Least-loaded among READY replicas at-or-above the caller's version
        floor (fall back to the highest-step READY replica when none clears
        it — the floor then ratchets no further than what exists). None when
        nothing is ready or everything ready is at ``max_inflight``."""
        with self._lock:
            ready = [ep for ep in self.endpoints if ep.ready and ep.name not in exclude]
            if not ready:
                return None
            eligible = [ep for ep in ready if ep.step >= floor]
            if not eligible:
                top = max(ep.step for ep in ready)
                eligible = [ep for ep in ready if ep.step == top]
            open_eps = [ep for ep in eligible if ep.inflight < self.max_inflight]
            if not open_eps:
                return None
            # least-loaded, with a rotating tie-break: serial traffic (every
            # request seeing inflight == 0 everywhere) must still spread over
            # the fleet instead of pinning the lexicographically-first name
            best = min((ep.inflight, ep.queue_depth) for ep in open_eps)
            cands = [ep for ep in open_eps if (ep.inflight, ep.queue_depth) == best]
            self._rr += 1
            return cands[self._rr % len(cands)]

    def _session_pick(self, session_id: str, floor: int, exclude: set) -> Optional[ReplicaEndpoint]:
        """Sticky: the session's home replica while it is READY (stickiness
        trumps load — its slab row lives there; a full home sheds rather
        than re-homes). A dead/unready/excluded home re-homes the session to
        a survivor, counted + reset-flagged."""
        with self._lock:
            home = self._session_home.get(session_id)
            ep = self._by_name.get(home) if home is not None else None
            if ep is not None and ep.ready and ep.name not in exclude:
                return ep if ep.inflight < self.max_inflight else None
            target = self._pick(floor, exclude)
            if target is None:
                return None
            if home is not None and target.name != home:
                # an ACTUAL re-home (the home existed and is gone) — first
                # assignment of a brand-new session is not one
                self._pending_reset.add(session_id)
                self.counters["sessions_rehomed"] += 1
            self._session_home[session_id] = target.name
            return target

    def serve_request(self, msg: Dict[str, Any], conn: Optional[_ConnState] = None) -> Dict[str, Any]:
        """Route one protocol request; returns the response object (the
        router's own errors use the protocol's ``{"error": ...}`` shape)."""
        conn = conn or _ConnState()
        session_id = msg.get("session_id")
        if session_id is not None:
            session_id = str(session_id)
        with self._lock:
            self.counters["requests"] += 1
            if self._draining:
                return {"error": "ServeClosedError: fleet router is draining"}
        exclude: set = set()
        budget = self.retry_budget
        while True:
            if session_id is not None:
                target = self._session_pick(session_id, conn.floor, exclude)
            else:
                target = self._pick(conn.floor, exclude)
            if target is None:
                # fleet-wide load shedding: no READY replica with capacity —
                # propagate the tier's existing backpressure error instead of
                # queueing unboundedly inside the router
                with self._lock:
                    self.counters["shed"] += 1
                return {"error": "ServeOverloadedError: no ready replica with capacity (fleet backpressure)"}
            payload = dict(msg)
            rehomed = False
            if session_id is not None:
                with self._lock:
                    rehomed = session_id in self._pending_reset
                if rehomed:
                    payload["reset"] = True
            with self._lock:
                target.inflight += 1
            try:
                resp = target.request(payload, timeout_s=self.request_timeout_s)
            except FleetReplicaError as e:
                with self._lock:
                    target.inflight -= 1
                    self.counters["replica_errors"] += 1
                    # fast failover: stop routing here until a probe succeeds
                    target.ready = False
                    target.status = "unreachable"
                target.close()
                if session_id is not None:
                    # the home is gone mid-request: re-home on the retry (the
                    # pending reset, if any, stays pending — it was not
                    # delivered)
                    with self._lock:
                        if self._session_home.get(session_id) == target.name:
                            del self._session_home[session_id]
                            self._pending_reset.add(session_id)
                            self.counters["sessions_rehomed"] += 1
                exclude.add(target.name)
                if budget > 0:
                    budget -= 1
                    with self._lock:
                        self.counters["retries"] += 1
                    continue
                return {"error": f"FleetReplicaError: {e}"}
            with self._lock:
                target.inflight -= 1
            if isinstance(resp, dict) and "error" in resp:
                err = str(resp["error"])
                if "ServeOverloadedError" in err:
                    # replica-level backpressure: one bounded sidestep toward
                    # a less-loaded survivor, then propagate fleet-wide
                    with self._lock:
                        self.counters["replica_overloads"] += 1
                    exclude.add(target.name)
                    if budget > 0:
                        budget -= 1
                        with self._lock:
                            self.counters["retries"] += 1
                        continue
                elif "ServeClosedError" in err:
                    # the replica is DRAINING (its admission closed while its
                    # open connections still answer): fail over exactly like
                    # a dead replica — it will not take this request, ever
                    with self._lock:
                        self.counters["replica_errors"] += 1
                        target.ready = False
                        target.status = "draining"
                    target.close()
                    if session_id is not None:
                        with self._lock:
                            if self._session_home.get(session_id) == target.name:
                                del self._session_home[session_id]
                                self._pending_reset.add(session_id)
                                self.counters["sessions_rehomed"] += 1
                    exclude.add(target.name)
                    if budget > 0:
                        budget -= 1
                        with self._lock:
                            self.counters["retries"] += 1
                        continue
                return resp
            # success: consume the delivered reset, annotate, ratchet floor.
            # fleet_version is the replica's known published step — HONEST:
            # when the floor-fallback path had to serve from a replica below
            # this connection's floor (every at-or-above replica died before
            # the swap propagated), the client SEES the dip (and
            # version_fallbacks counts it) rather than being told a step the
            # weights never had. The floor itself only ever ratchets up.
            with self._lock:
                self.counters["routed"] += 1
                if rehomed:
                    self._pending_reset.discard(session_id)
                fleet_version = target.step
                if fleet_version < conn.floor:
                    self.counters["version_fallbacks"] += 1
                else:
                    conn.floor = fleet_version
            out = dict(resp)
            out["replica"] = target.name
            out["fleet_version"] = int(fleet_version)
            if rehomed:
                out["rehomed"] = True
            return out

    # front-end inflight accounting rides serve_request via the TCP handler;
    # in-process callers (tests, the bench) call serve_request directly.
    def _serve_tracked(self, msg: Dict[str, Any], conn: _ConnState) -> Dict[str, Any]:
        with self._lock:
            self._frontend_inflight += 1
        try:
            return self.serve_request(msg, conn)
        finally:
            with self._lock:
                self._frontend_inflight -= 1

    # -- aggregated health -----------------------------------------------------
    def health(self) -> Dict[str, Any]:
        """The fleet-wide probe answer: router status + counters + one entry
        per replica (probe snapshot, fleet-comparable step, supervision
        counters when a process supervisor is attached)."""
        with self._lock:
            ready_n = sum(1 for ep in self.endpoints if ep.ready)
            all_ok = all(ep.ready and ep.status == "ok" for ep in self.endpoints)
            replicas: Dict[str, Any] = {
                ep.name: {
                    "ready": bool(ep.ready),
                    "status": ep.status,
                    "address": f"{ep.host}:{ep.port}",
                    "version": int(ep.version),
                    "step": int(ep.step),
                    "inflight": int(ep.inflight),
                    "queue_depth": int(ep.queue_depth),
                    "consecutive_failures": int(ep.consecutive_failures),
                }
                for ep in self.endpoints
            }
            counters = dict(self.counters)
            fleet_version = max((ep.step for ep in self.endpoints), default=-1)
        if self.procsup is not None:
            snap = self.procsup.snapshot()
            for name, info in snap.items():
                if name in replicas:
                    replicas[name]["proc"] = info
            degraded_procs = any(info.get("state") == "degraded" for info in snap.values())
        else:
            degraded_procs = False
        if self._draining:
            status = "draining"
        elif ready_n == 0:
            status = "down"
        elif all_ok and not degraded_procs and self.fatal is None:
            status = "ok"
        else:
            status = "degraded"
        return {
            "status": status,
            "ready": ready_n > 0 and not self._draining,
            "fleet": {
                "replicas": len(self.endpoints),
                "ready": ready_n,
                "fleet_version": int(fleet_version),
                "fatal": str(self.fatal) if self.fatal is not None else None,
                "tick_errors": int(self._tick_errors),
                **counters,
            },
            "replicas": replicas,
        }


# -- the fleet CLI body --------------------------------------------------------
def replica_command(
    cfg: Any,
    checkpoint_path: str,
    host: str,
    port: int,
    name: Optional[str] = None,
) -> List[str]:
    """The ``sheeprl_tpu serve`` invocation for ONE replica: same checkpoint,
    its own port, watching the shared checkpoint dir with
    ``watch_publish_current`` so a respawn rejoins on the newest complete
    save. Only scalar serve knobs that survive a CLI round trip are
    forwarded; everything else re-derives from the checkpoint's own run
    config exactly like a hand-started ``serve``.

    With the flywheel enabled each replica logs into the SHARED spool dir
    under its fleet name (spool headers carry the attribution) but never
    spawns its own learner — the fleet parent owns the single supervised
    learner process for the whole fleet."""
    serve_cfg = dict(cfg.get("serve", {}) or {})
    cmd = [
        sys.executable,
        "-m",
        "sheeprl_tpu",
        "serve",
        f"checkpoint_path={checkpoint_path}",
        f"serve.host={host}",
        f"serve.port={port}",
        "serve.fleet.replicas=0",  # a replica must never recurse into a fleet
        "serve.watch=True",
        "serve.watch_publish_current=True",
        f"fabric.accelerator={(cfg.get('fabric') or {}).get('accelerator', 'auto')}",
    ]
    if cfg.get("seed") is not None:
        cmd.append(f"seed={int(cfg['seed'])}")
    for key in ("mode", "max_wait_ms", "max_batch", "queue_bound", "watch_poll_s", "max_staleness_s", "log_every_s"):
        if serve_cfg.get(key) is not None:
            cmd.append(f"serve.{key}={serve_cfg[key]}")
    if serve_cfg.get("buckets"):
        cmd.append("serve.buckets=[" + ",".join(str(int(b)) for b in serve_cfg["buckets"]) + "]")
    fly = dict(serve_cfg.get("flywheel", {}) or {})
    if fly.get("enabled") and fly.get("dir"):
        cmd.append("serve.flywheel.enabled=True")
        cmd.append(f"serve.flywheel.dir={fly['dir']}")
        cmd.append(f"serve.flywheel.replica={name or f'replica-{port}'}")
        cmd.append("serve.flywheel.learner=False")  # ONE learner, owned by the fleet parent
        for key in ("block_rows", "queue_blocks", "flush_s", "max_streams"):
            if fly.get(key) is not None:
                cmd.append(f"serve.flywheel.{key}={fly[key]}")
    return cmd


def serve_fleet(cfg: Any) -> None:
    """CLI entrypoint body (``sheeprl_tpu serve --fleet N`` /
    ``serve_fleet``): spawn N supervised replica processes on the same
    checkpoint dir, stand the router front end over them, run until SIGTERM
    / SIGINT (graceful fleet drain, exit 0) or ``serve.max_requests``."""
    from sheeprl_tpu.serve.server import install_drain_handlers
    from sheeprl_tpu.utils.utils import refuse_children_on_tpu

    refuse_children_on_tpu("serve --fleet", "N replica processes on this host")
    serve_cfg = dict(cfg.get("serve", {}) or {})
    fleet_cfg = dict(serve_cfg.get("fleet", {}) or {})
    n = int(fleet_cfg.get("replicas", 0) or 0)
    if n < 2:
        raise ValueError(f"serve.fleet.replicas must be >= 2 for fleet serving, got {n}")
    checkpoint_path = cfg.get("checkpoint_path")
    if not checkpoint_path:
        raise ValueError("You must specify the checkpoint path to serve")
    host = str(serve_cfg.get("host", "127.0.0.1"))
    inject.arm_from_cfg(cfg)  # the seeded chaos schedule (fault.chaos.events)
    fly_cfg = dict(serve_cfg.get("flywheel", {}) or {})
    if fly_cfg.get("enabled"):
        # resolve the shared spool dir ONCE, before any replica spawns, so
        # every replica and the single fleet-owned learner agree on it
        from pathlib import Path

        if not fly_cfg.get("dir"):
            fly_cfg["dir"] = str(Path(os.path.abspath(str(checkpoint_path))).parent / "flywheel")
        serve_cfg["flywheel"] = fly_cfg
        cfg["serve"] = serve_cfg
    procsup = ProcessSupervisor.from_config(fleet_cfg, name="serve-fleet")
    endpoints: List[ReplicaEndpoint] = []
    for i in range(n):
        port = free_port(host)
        name = f"replica-{i}"
        cmd = replica_command(cfg, str(checkpoint_path), host, port, name=name)
        endpoints.append(
            ReplicaEndpoint(
                name,
                host,
                port,
                request_timeout_s=float(fleet_cfg.get("request_timeout_s", 30.0) or 30.0),
            )
        )
        procsup.spawn(name, _spawner(cmd))
    router = FleetRouter(
        endpoints,
        fleet_cfg=fleet_cfg,
        procsup=procsup,
        owns_replicas=True,
        host=host,
        port=serve_cfg.get("port", 0),
    )
    learner_sup = None
    if fly_cfg.get("enabled") and fly_cfg.get("learner", True):
        # ONE supervised learner for the whole fleet: N replicas spool into
        # the shared dir, this process owns (and ticks) the learner's lease
        from sheeprl_tpu.serve.flywheel import LearnerSupervisor

        learner_sup = LearnerSupervisor(cfg, fly_cfg["dir"])
    drain = threading.Event()
    restore_handlers = install_drain_handlers(drain)
    router.start()
    addr = router.address
    if addr is not None:
        print(f"serving fleet of {n} replicas on {addr[0]}:{addr[1]} (router; replicas on {[ep.port for ep in endpoints]})")
    max_requests = serve_cfg.get("max_requests")
    log_every_s = float(serve_cfg.get("log_every_s", 10.0) or 10.0)
    try:
        last_log = time.perf_counter()
        while not drain.is_set():
            drain.wait(0.2)
            if learner_sup is not None:
                learner_sup.tick()
            now = time.perf_counter()
            if now - last_log >= log_every_s:
                print(json.dumps(router.health()))
                last_log = now
            if max_requests is not None and router.counters["requests"] >= int(max_requests):
                break
    except KeyboardInterrupt:
        pass
    finally:
        router.stop()  # drain router admission -> drain each replica -> exit 0
        if learner_sup is not None:
            learner_sup.stop()
        restore_handlers()
        print(json.dumps(router.health()))
        if drain.is_set():
            print("serve: drained cleanly")


def _spawner(cmd: List[str]) -> Callable[[], subprocess.Popen]:
    def spawn() -> subprocess.Popen:
        return subprocess.Popen(cmd)

    return spawn
