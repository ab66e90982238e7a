"""Flywheel SLO bench: does the production loop cost the serving tier?

Stands up the full graft-flywheel stack in its real process topology — a
SAC :class:`~sheeprl_tpu.serve.server.PolicyServer` serving a trained
checkpoint, the spool-backed :class:`~sheeprl_tpu.serve.flywheel.TrajectoryLog`
behind the resolve path, and the REAL learner subprocess (``run
--from-serve``) under its :class:`~sheeprl_tpu.serve.flywheel.LearnerSupervisor`
— and drives it with closed-loop feedback clients (every request grades the
previous action on its stream, so each turn completes a production
transition into the spool).

Two phases on identical traffic:

- ``learner-off`` — flywheel disabled entirely: the pure serving baseline;
- ``learner-on`` — flywheel spooling + live learner ingesting and
  publishing: the number an operator compares against the baseline.

Reported per phase: completed requests/s, p50/p99 request latency; for the
on-phase additionally rows-ingested/s (from the learner's status file),
learner grad steps, and the published step. Asserted IN-LANE: zero dropped
requests, zero request errors, zero shed rows, and a learner that actually
consumed production rows — a flywheel that silently sheds or a learner that
never ingests makes the lane FAIL, not emit a pretty number.

Knobs (env vars): ``BENCH_FLYWHEEL_DURATION`` (seconds per phase, default
6), ``BENCH_FLYWHEEL_CLIENTS`` (closed-loop client threads, default 4),
``BENCH_FLYWHEEL_CKPT`` (reuse an existing SAC checkpoint instead of
training a tiny one), ``BENCH_SERVE_BUCKETS`` (ladder, default ``1,4,8``).
Interpretation notes in ``howto/serving.md#the-flywheel``.
"""

from __future__ import annotations

import glob
import json
import os
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

SAC_TINY = [
    "exp=sac",
    "env=dummy",
    "env.id=continuous_dummy",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    "dry_run=True",
    "buffer.memmap=False",
    "fabric.devices=1",
    "metric.log_level=0",
    "checkpoint.save_last=True",
    "algo.run_test=False",
    "algo.per_rank_batch_size=8",
    "algo.mlp_keys.encoder=[state]",
    "algo.hidden_size=16",
]


def _checkpoint(workdir: str) -> str:
    given = os.environ.get("BENCH_FLYWHEEL_CKPT", "").strip()
    if given:
        return given
    from sheeprl_tpu.cli import run

    run(SAC_TINY + [f"log_root={workdir}/train"])
    ckpts = sorted(glob.glob(f"{workdir}/train/**/ckpt_*.ckpt", recursive=True), key=os.path.getmtime)
    if not ckpts:
        raise SystemExit("flywheel bench: tiny SAC train produced no checkpoint")
    return ckpts[-1]


def _build(ckpt: str):
    from sheeprl_tpu.cli import _merged_ckpt_cfg
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.factory import make_env
    from sheeprl_tpu.fault.manager import load_state
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.serve.server import resolve_builder_state
    from sheeprl_tpu.utils.registry import get_entrypoint, resolve_policy_builder

    serve_cfg = compose(
        [f"checkpoint_path={ckpt}", "fabric.accelerator=cpu"], config_name="serve_config"
    )
    cfg = _merged_ckpt_cfg(
        serve_cfg,
        "flywheel_bench",
        capture_video=False,
        # the learner subprocess reads its knobs from cfg.serve.flywheel
        extra={"serve": dict(serve_cfg.get("serve", {}) or {})},
    )
    fabric = Fabric(devices=1, accelerator="cpu")
    fabric.seed_everything(cfg.seed)
    state = load_state(ckpt)
    env = make_env(cfg, cfg.seed, 0, None, "flywheel_bench", vector_env_idx=0)()
    obs_space, act_space = env.observation_space, env.action_space
    env.close()
    builder = get_entrypoint(resolve_policy_builder(cfg.algo.name))
    agent_state, builder_kwargs = resolve_builder_state(builder, state, ckpt, str(cfg.algo.name))
    policy = builder(fabric, cfg, obs_space, act_space, agent_state, **builder_kwargs)
    return cfg, policy


def _drive_closed_loop(
    policy, scheduler, duration_s: float, n_clients: int
) -> Dict[str, Any]:
    """Closed-loop feedback clients: each thread is one production stream —
    request, wait for the action, grade it on the NEXT request. Latency is
    stamped at worker resolve time."""
    import numpy as np

    counters = {"submitted": 0, "errors": 0, "completed": 0}
    latencies: List[float] = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s

    def client(idx: int) -> None:
        rng = np.random.default_rng(idx)
        turn = 0
        while time.perf_counter() < stop_at:
            obs = policy.prepare({"state": rng.standard_normal(10).astype(np.float32)}, 1)
            kw: Dict[str, Any] = {"stream": f"bench-client-{idx}"}
            if turn > 0:
                kw["reward"] = 1.0
                kw["done"] = 1.0 if turn % 16 == 0 else 0.0
            try:
                req = scheduler.submit(obs, timeout=60.0, **kw)
                with lock:
                    counters["submitted"] += 1
            except Exception:
                with lock:
                    counters["errors"] += 1
                continue
            if not req.event.wait(timeout=120.0) or req.error is not None:
                with lock:
                    counters["errors"] += 1
                continue
            with lock:
                counters["completed"] += 1
                latencies.append(req.latency_s)
            turn += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 180.0)
    elapsed = time.perf_counter() - start
    lat = np.sort(np.asarray(latencies)) if latencies else np.zeros(1)
    return {
        "duration_s": round(elapsed, 2),
        "submitted": counters["submitted"],
        "completed": counters["completed"],
        "dropped": counters["submitted"] - counters["completed"],
        "errors": counters["errors"],
        "throughput_rps": round(counters["completed"] / elapsed, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }


def _run_phase(
    cfg, policy, duration: float, n_clients: int, flywheel_dir: Optional[str]
) -> Dict[str, Any]:
    """One phase = one fresh PolicyServer (+ learner when ``flywheel_dir``)."""
    from sheeprl_tpu.serve.flywheel import LearnerSupervisor, read_learner_status
    from sheeprl_tpu.serve.server import PolicyServer

    serve_cfg: Dict[str, Any] = {
        "buckets": [int(x) for x in os.environ.get("BENCH_SERVE_BUCKETS", "1,4,8").split(",") if x.strip()],
        "mode": "greedy",
        "max_wait_ms": 1.0,
        "queue_bound": 1024,
        "port": None,
    }
    learner_sup = None
    if flywheel_dir is not None:
        serve_cfg["flywheel"] = {
            "enabled": True,
            "dir": flywheel_dir,
            "replica": "bench-replica",
            "block_rows": 64,
            "flush_s": 0.1,
        }
        cfg["serve"]["flywheel"] = {
            **dict(cfg["serve"].get("flywheel") or {}),
            "enabled": True,
            "dir": flywheel_dir,
            "poll_s": 0.1,
            "ingest_rows": 16,
            "grad_max": 4,
            "replay_ratio": 0.5,
            "learning_starts_rows": 64,
            "buffer_size": 4096,
            "publish_rows": 256,
        }
    server = PolicyServer(policy, serve_cfg)
    server.start(with_socket=False)
    ticker_stop = threading.Event()
    ticker = None
    try:
        if flywheel_dir is not None:
            learner_sup = LearnerSupervisor(cfg, flywheel_dir)

            def _tick() -> None:
                while not ticker_stop.is_set():
                    learner_sup.tick()
                    ticker_stop.wait(0.2)

            ticker = threading.Thread(target=_tick, daemon=True)
            ticker.start()
            # the phase measures steady state, not learner cold-start: wait
            # for the first ingested rows before opening the traffic window
            warm = {"deadline": time.monotonic() + 240.0}
            warm_sched = server.scheduler
            import numpy as np

            rng = np.random.default_rng(7)
            turn = 0
            while time.monotonic() < warm["deadline"]:
                obs = policy.prepare({"state": rng.standard_normal(10).astype(np.float32)}, 1)
                kw: Dict[str, Any] = {"stream": "bench-warmup"}
                if turn > 0:
                    kw["reward"] = 0.0
                    kw["done"] = 0.0
                req = warm_sched.submit(obs, timeout=60.0, **kw)
                req.event.wait(timeout=120.0)
                turn += 1
                status = read_learner_status(flywheel_dir) or {}
                if status.get("consumed_rows", 0) > 0:
                    break
                time.sleep(0.05)
            else:
                raise SystemExit("flywheel bench: learner never ingested a row during warmup")

        consumed_before = 0
        if flywheel_dir is not None:
            consumed_before = int((read_learner_status(flywheel_dir) or {}).get("consumed_rows", 0))
        result = _drive_closed_loop(policy, server.scheduler, duration, n_clients)
        if flywheel_dir is not None:
            # let the tail of the spool drain before reading the meter
            deadline = time.monotonic() + 30.0
            fl = server.flywheel.snapshot()
            while time.monotonic() < deadline:
                status = read_learner_status(flywheel_dir) or {}
                fl = server.flywheel.snapshot()
                if int(status.get("consumed_rows", 0)) >= fl["rows_spooled"]:
                    break
                time.sleep(0.25)
            status = read_learner_status(flywheel_dir) or {}
            result["rows_logged"] = int(fl["rows_logged"])
            result["rows_shed"] = int(fl["rows_shed"])
            result["flywheel_errors"] = int(fl["errors"])
            result["rows_ingested"] = int(status.get("consumed_rows", 0)) - consumed_before
            result["rows_ingested_per_sec"] = round(result["rows_ingested"] / result["duration_s"], 1)
            result["learner_grad_steps"] = int(status.get("grad_steps", 0))
            result["learner_published_step"] = int(status.get("published_step", -1))
    finally:
        ticker_stop.set()
        if ticker is not None:
            ticker.join(timeout=10.0)
        server.stop()
        if learner_sup is not None:
            learner_sup.stop()
    return result


def main() -> None:
    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    duration = float(os.environ.get("BENCH_FLYWHEEL_DURATION", "6"))
    n_clients = int(os.environ.get("BENCH_FLYWHEEL_CLIENTS", "4"))

    with tempfile.TemporaryDirectory(prefix="flywheel_bench_") as workdir:
        ckpt = _checkpoint(workdir)
        cfg, policy = _build(ckpt)
        off = _run_phase(cfg, policy, duration, n_clients, flywheel_dir=None)
        flywheel_dir = str(Path(workdir) / "flywheel")
        on = _run_phase(cfg, policy, duration, n_clients, flywheel_dir=flywheel_dir)

    # the lane's contract, not a hint: the loop must close without loss
    for name, phase in (("learner-off", off), ("learner-on", on)):
        if phase["dropped"] != 0:
            raise SystemExit(f"flywheel bench: {phase['dropped']} dropped requests in {name} phase")
        if phase["errors"] != 0:
            raise SystemExit(f"flywheel bench: {phase['errors']} request errors in {name} phase")
    if on["rows_shed"] != 0:
        raise SystemExit(f"flywheel bench: {on['rows_shed']} production rows shed under bench load")
    if on["flywheel_errors"] != 0:
        raise SystemExit(f"flywheel bench: {on['flywheel_errors']} trajectory-log errors")
    if on["rows_ingested"] <= 0:
        raise SystemExit("flywheel bench: learner ingested zero rows during the measured window")

    print(
        json.dumps(
            {
                "metric": "serve_flywheel_rows_ingested_per_sec",
                # headline: sustained production-ingest rate with the live learner
                "value": on["rows_ingested_per_sec"],
                "unit": "rows/s",
                "clients": n_clients,
                "duration_s": duration,
                "learner_off": off,
                "learner_on": on,
                # the isolation claim as a ratio: on-phase p99 over baseline
                "p99_on_over_off": round(on["p99_ms"] / max(off["p99_ms"], 1e-9), 3),
            }
        )
    )


if __name__ == "__main__":
    main()
