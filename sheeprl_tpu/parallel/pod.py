"""Pod launcher: fault-tolerant multi-host training under gang supervision.

``sheeprl_tpu run --pod N ...`` (or ``fabric.pod.workers=N``) spawns N worker
processes that each call ``jax.distributed`` init via
:func:`~sheeprl_tpu.parallel.distributed.maybe_init` and run the ordinary
training entrypoint over ONE process-spanning ``dp`` mesh — the Podracer pod
topology (arXiv 2104.06272), with CPU CI proxying each "host" by a worker
process owning ``fabric.pod.devices_per_worker`` virtual devices
(``tests/test_utils/test_multiprocess.py`` is the 2-process seed).

The launcher itself never touches JAX. It is a process manager wrapping
:class:`~sheeprl_tpu.fault.podsup.PodSupervisor`:

- **liveness = heartbeat files.** Each worker runs a tiny daemon thread that
  touches ``$SHEEPRL_POD_HEARTBEAT`` every ``beat_s`` (and the training loop
  writes the completed global step into it each iteration). The launcher
  polls mtimes into :meth:`PodSupervisor.beat`; a SIGSTOPped or wedged
  worker stops touching and is SIGKILLed at lease expiry, counted as a
  ``hang`` — distinct from an external SIGKILL (``kills``).
- **recovery = gang restart with checkpoint-step fencing.** On any abnormal
  worker death the supervisor drains the survivors and calls back into
  :meth:`PodLauncher._on_gang_restart`: a FRESH coordinator port is chosen
  (the old coordinator may have died holding the socket), the newest
  complete checkpoint is resolved and pinned as ``checkpoint.resume_from``
  (fresh start when none exists yet), and the resumed step is FENCED —
  every restart's resume step must be >= the previous fence, so the global
  step is monotone and never double-counted across generations
  (:class:`StepFenceError` otherwise). Counters restore from the
  checkpoint, so a killed run converges to the same final counters as its
  fault-free twin.
- **SIGTERM drains outermost-first.** The launcher stops supervising,
  SIGTERMs the workers (each checkpoints at its next iteration boundary and
  exits 0 — see the ``drain_requested`` plumbing below), and exits 0.
- **chaos-drillable.** ``kill-host`` / ``hang-host`` actions armed from the
  seeded ``fault.chaos.events`` schedule fire at the launcher's fault
  points and SIGKILL / SIGSTOP a live worker. ``train.pod.tick`` counts
  supervision ticks (wall-clock, ``tick_s`` apart); ``train.pod.step``
  counts observed heartbeat step advances (one per completed worker
  iteration) — use the latter for drills so the injection lands mid-run
  regardless of how warm the XLA compile cache is.

Worker-side helpers (heartbeat thread, SIGTERM drain flag, per-iteration
step beats) live in this module too and activate only under
``SHEEPRL_POD_RANK``; they are wired through ``cli.run_algorithm`` so every
training entrypoint gets them.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from sheeprl_tpu.fault import inject
from sheeprl_tpu.fault.podsup import PodSupervisor

__all__ = [
    "PodLauncher",
    "StepFenceError",
    "run_pod",
    "pod_worker_active",
    "maybe_start_worker_runtime",
    "drain_requested",
    "beat_step",
]

COORDINATOR_ENV = "SHEEPRL_COORDINATOR"
NUM_PROCESSES_ENV = "SHEEPRL_NUM_PROCESSES"
PROCESS_ID_ENV = "SHEEPRL_PROCESS_ID"
RANK_ENV = "SHEEPRL_POD_RANK"
HEARTBEAT_ENV = "SHEEPRL_POD_HEARTBEAT"
BEAT_S_ENV = "SHEEPRL_POD_BEAT_S"

TICK_POINT = "train.pod.tick"
STEP_POINT = "train.pod.step"


class StepFenceError(RuntimeError):
    """A gang restart resolved a resume checkpoint BEHIND the previous
    generation's fence — resuming from it would replay (double-count)
    already-trained steps."""


# --------------------------------------------------------------------------- #
# worker side: heartbeat + drain runtime (active only under SHEEPRL_POD_RANK)
# --------------------------------------------------------------------------- #

_drain_event = threading.Event()
_worker_started = False
_hb_path: Optional[str] = None


def pod_worker_active() -> bool:
    """True when this process is a pod worker (spawned by the launcher)."""
    return RANK_ENV in os.environ


def drain_requested() -> bool:
    """True once the pod launcher SIGTERMed this worker: the training loop
    should checkpoint at its next iteration boundary and exit 0."""
    return _drain_event.is_set()


def beat_step(step: int) -> None:
    """Training-loop beat: record the completed global step in the heartbeat
    file. The mtime keeps the lease alive; the CONTENT change is the
    launcher's "first post-restart train step" signal (the MTTR clock of the
    ``pod_restart`` bench lane). No-op outside a pod worker."""
    if _hb_path is None:
        return
    tmp = _hb_path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(str(int(step)))
        os.replace(tmp, _hb_path)
    except OSError:
        pass


def maybe_start_worker_runtime() -> bool:
    """Start the pod worker runtime when running under the launcher:
    a daemon heartbeat thread touching ``$SHEEPRL_POD_HEARTBEAT`` every
    ``$SHEEPRL_POD_BEAT_S`` seconds, and a SIGTERM handler raising the drain
    flag (the launcher's outermost-first drain: stop admission at the
    launcher, checkpoint-and-exit here). Idempotent; returns whether the
    runtime is active."""
    global _worker_started, _hb_path
    if not pod_worker_active():
        return False
    if _worker_started:
        return True
    _worker_started = True
    _hb_path = os.environ.get(HEARTBEAT_ENV) or None
    if _hb_path is not None:
        beat_s = max(0.05, float(os.environ.get(BEAT_S_ENV, "0.5") or 0.5))
        hb_path = _hb_path

        def _beat_loop() -> None:
            while not _drain_event.wait(beat_s):
                try:
                    os.utime(hb_path)
                except OSError:
                    try:
                        Path(hb_path).touch()
                    except OSError:
                        pass

        # graft-sync: disable-next-line=GS004 — deliberately unsupervised: the
        # heartbeat is the SIGNAL the pod supervisor watches; supervising it
        # from inside the watched process would be circular. Daemon + no shared
        # state beyond the drain Event and an os.utime on a dedicated file.
        threading.Thread(target=_beat_loop, name="pod-heartbeat", daemon=True).start()
    try:

        def _on_sigterm(signum, frame):  # noqa: ARG001
            _drain_event.set()

        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # not the main thread / exotic platform
        pass
    return True


# --------------------------------------------------------------------------- #
# launcher side
# --------------------------------------------------------------------------- #


def _free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class PodLauncher:
    """Gang-supervised pod of N training worker processes (module docstring).

    ``argv`` is the user's original hydra-style override list (WITHOUT the
    ``--pod`` flag); each worker re-composes its own config from it plus the
    launcher's per-worker pins.
    """

    def __init__(self, cfg: Any, argv: List[str]) -> None:
        pod_cfg = dict((cfg.get("fabric") or {}).get("pod") or {})
        self.workers = int(pod_cfg.get("workers", 0) or 0)
        if self.workers < 2:
            raise ValueError(
                f"pod training needs fabric.pod.workers >= 2, got {self.workers} — "
                "drop the --pod flag for a single-process run"
            )
        self.cfg = cfg
        self.pod_cfg = pod_cfg
        self.argv = [a for a in argv if not a.startswith("checkpoint.resume_from=")]
        self.user_resume = next(
            (a.split("=", 1)[1] for a in argv if a.startswith("checkpoint.resume_from=")), None
        )
        dpw = pod_cfg.get("devices_per_worker")
        self.devices_per_worker = int(dpw) if dpw else None
        self.host = str(pod_cfg.get("coordinator_host", "127.0.0.1") or "127.0.0.1")
        self.beat_s = float(pod_cfg.get("beat_s") or max(0.1, float(pod_cfg.get("lease_s", 30.0) or 30.0) / 4.0))
        self.tick_s = max(0.02, float(pod_cfg.get("tick_s", 0.25) or 0.25))
        self.join_s = float(pod_cfg.get("join_s", 30.0) or 30.0)
        self.dir = Path(tempfile.mkdtemp(prefix="sheeprl-pod-"))
        # experiment checkpoint root — the same resolution as
        # cli.resolve_resume_latest, used for gang-respawn resume + fencing
        self.ckpt_root = Path(cfg.get("log_root", "logs/runs")) / str(cfg.root_dir)
        self.sup = PodSupervisor.from_config(
            pod_cfg,
            name="train-pod",
            lease_s=30.0,
            grace_s=120.0,
            max_restarts=2,
            backoff=0.5,
            escalation="degrade",
            join_s=self.join_s,
        )
        self.sup.on_gang_restart = self._on_gang_restart
        # mutable launch context read by the spawn closures (a gang restart
        # mutates it before the new generation spawns)
        self._port = _free_port(self.host)
        self._resume: Optional[str] = self.user_resume
        self.fences: List[int] = []
        self._hb_paths = {rank: self.dir / f"heartbeat_{rank}" for rank in range(self.workers)}
        self._hb_mtime: Dict[int, float] = {}
        self._hb_content: Dict[int, str] = {}
        self._fault_t: Optional[float] = None  # chaos-injection timestamp
        self._pending_restart: Optional[Dict[str, Any]] = None
        self.restart_log: List[Dict[str, Any]] = []

    # -- worker launch --------------------------------------------------------
    def worker_command(self, rank: int) -> List[str]:
        cmd = [sys.executable, "-m", "sheeprl_tpu", "run", *self.argv]
        # a worker must never recurse into a pod (also pinned by RANK_ENV)
        cmd.append("fabric.pod.workers=0")
        if self.devices_per_worker is not None and not any(
            a.startswith("fabric.devices=") for a in self.argv
        ):
            # CPU proxy: the mesh must span every worker's virtual devices
            cmd.append(f"fabric.devices={self.workers * self.devices_per_worker}")
        if self._resume:
            cmd.append(f"checkpoint.resume_from={self._resume}")
        return cmd

    def worker_env(self, rank: int) -> Dict[str, str]:
        env = dict(os.environ)
        env[COORDINATOR_ENV] = f"{self.host}:{self._port}"
        env[NUM_PROCESSES_ENV] = str(self.workers)
        env[PROCESS_ID_ENV] = str(rank)
        env[RANK_ENV] = str(rank)
        env[HEARTBEAT_ENV] = str(self._hb_paths[rank])
        env[BEAT_S_ENV] = str(self.beat_s)
        if self.devices_per_worker is not None:
            flags = [
                f
                for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            ]
            flags.append(f"--xla_force_host_platform_device_count={self.devices_per_worker}")
            env["XLA_FLAGS"] = " ".join(flags)
        return env

    def _spawner(self, rank: int) -> Callable[[], subprocess.Popen]:
        def spawn() -> subprocess.Popen:
            hb = self._hb_paths[rank]
            # empty the file, not just touch: the previous generation's last
            # step may be re-reached verbatim after resume, and the MTTR
            # signal is a CONTENT change
            hb.write_text("", encoding="utf-8")
            self._hb_mtime[rank] = hb.stat().st_mtime
            self._hb_content[rank] = ""
            return subprocess.Popen(self.worker_command(rank), env=self.worker_env(rank))

        return spawn

    # -- gang restart: fresh port + resume resolution + step fencing ----------
    def _on_gang_restart(self, generation: int) -> None:
        from sheeprl_tpu.fault.manager import _parse_step, find_latest_run_checkpoint

        self._port = _free_port(self.host)
        resolved = find_latest_run_checkpoint(self.ckpt_root)
        if resolved is None:
            # nothing committed yet: the gang restarts from scratch
            self._resume = self.user_resume
            step = 0
        else:
            self._resume = str(resolved)
            step = _parse_step(Path(resolved).name) or 0
        if self.fences and step < self.fences[-1]:
            raise StepFenceError(
                f"gang restart (generation {generation}) resolved resume checkpoint "
                f"'{resolved}' at step {step}, BEHIND the previous fence "
                f"{self.fences[-1]} — refusing to double-count steps"
            )
        self.fences.append(step)
        self._pending_restart = {
            "generation": generation,
            "resume": self._resume,
            "fence": step,
            "fault_t": self._fault_t,
            "respawn_t": time.monotonic(),
        }
        self._fault_t = None
        print(
            f"pod: gang restart (generation {generation}) on coordinator port {self._port}"
            + (f", resume_from={self._resume} (fence step {step})" if self._resume else ", fresh start")
        )

    # -- chaos handlers (kill-host / hang-host) -------------------------------
    def _live_victim(self):
        for h in self.sup.replicas():
            if h.state == "running" and h.is_alive():
                return h
        return None

    def _chaos_kill(self) -> None:
        h = self._live_victim()
        if h is not None:
            self._fault_t = time.monotonic()
            print(f"pod: chaos kill-host -> SIGKILL worker '{h.name}' (pid {h.pid()})")
            try:
                os.kill(h.pid(), signal.SIGKILL)
            except OSError:
                pass

    def _chaos_hang(self) -> None:
        h = self._live_victim()
        if h is not None:
            self._fault_t = time.monotonic()
            print(f"pod: chaos hang-host -> SIGSTOP worker '{h.name}' (pid {h.pid()})")
            try:
                os.kill(h.pid(), signal.SIGSTOP)
            except OSError:
                pass

    # -- heartbeat polling ----------------------------------------------------
    def _poll_heartbeats(self) -> None:
        for rank, path in self._hb_paths.items():
            try:
                st = path.stat()
            except OSError:
                continue
            if st.st_mtime > self._hb_mtime.get(rank, 0.0):
                self._hb_mtime[rank] = st.st_mtime
                self.sup.beat(f"worker-{rank}")
            try:
                content = path.read_text(encoding="utf-8", errors="replace")
            except OSError:
                continue
            if content and content != self._hb_content.get(rank, ""):
                if self._pending_restart is not None:
                    # first post-restart completed train iteration: close the
                    # MTTR window (fault injection -> first train step)
                    rec = self._pending_restart
                    self._pending_restart = None
                    now = time.monotonic()
                    rec["first_step_t"] = now
                    t0 = rec.get("fault_t") or rec["respawn_t"]
                    rec["mttr_s"] = now - t0
                    self.restart_log.append(rec)
                    print(
                        f"pod: first post-restart train step (generation {rec['generation']}) — "
                        f"MTTR {rec['mttr_s']:.3f}s"
                    )
                self._hb_content[rank] = content
                # progress-keyed chaos point: Nth observed step advance is the
                # same training moment no matter how fast the run executes
                inject.fault_point(STEP_POINT)

    # -- the run loop ---------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        inject.arm_from_cfg(self.cfg)
        inject.set_host_chaos(kill=self._chaos_kill, hang=self._chaos_hang)
        drain = threading.Event()
        prev_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, lambda *_: drain.set())
            except (ValueError, OSError):  # pragma: no cover - non-main thread
                pass
        print(
            f"pod: launching {self.workers} workers on coordinator {self.host}:{self._port}"
            + (f" ({self.devices_per_worker} virtual device(s)/worker)" if self.devices_per_worker else "")
        )
        self.fences.append(0)
        self.sup.spawn_gang({f"worker-{rank}": self._spawner(rank) for rank in range(self.workers)})
        error: Optional[BaseException] = None
        try:
            while not drain.is_set():
                drain.wait(self.tick_s)
                inject.fault_point(TICK_POINT)
                self._poll_heartbeats()
                self.sup.check()
                if self.sup.finished():
                    break
        except BaseException as e:  # typed supervision errors included
            error = e
        finally:
            for sig, handler in prev_handlers.items():
                try:
                    signal.signal(sig, handler)
                except (ValueError, OSError):  # pragma: no cover
                    pass
            drained = drain.is_set()
            if drained:
                # outermost-first: stop admission (supervision) here, then let
                # each worker checkpoint-and-exit inside the grace
                print("pod: drain requested — terminating workers (checkpoint-and-exit)")
            self.sup.terminate_all(grace_s=self.join_s)
            inject.set_host_chaos()
        summary = self.summary(drained=drained, error=error)
        print("POD_SUMMARY " + json.dumps(summary))
        if error is not None:
            raise error
        return summary

    def summary(self, drained: bool, error: Optional[BaseException]) -> Dict[str, Any]:
        snap = self.sup.snapshot()
        return {
            "workers": self.workers,
            "generation": self.sup.generation,
            "pod_restarts": self.sup.pod_restarts,
            "finished": self.sup.finished(),
            "drained": drained,
            "error": f"{type(error).__name__}: {error}" if error is not None else None,
            "fences": self.fences,
            "kills": sum(h["kills"] for h in snap.values()),
            "hangs": sum(h["hangs"] for h in snap.values()),
            "deaths": sum(h["deaths"] for h in snap.values()),
            "restarts": [
                {k: v for k, v in rec.items() if k in ("generation", "fence", "mttr_s")}
                for rec in self.restart_log
            ],
            "workers_detail": snap,
        }


def run_pod(cfg: Any, argv: List[str]) -> Dict[str, Any]:
    """CLI entrypoint body for ``sheeprl_tpu run --pod N`` — see
    :class:`PodLauncher`."""
    from sheeprl_tpu.utils.utils import pin_cpu_platform, refuse_children_on_tpu

    pin_cpu_platform((cfg.get("fabric") or {}).get("accelerator", "auto"))
    refuse_children_on_tpu("run --pod", "N worker processes on this host")
    return PodLauncher(cfg, argv).run()
