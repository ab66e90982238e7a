"""Fleet SLO bench for graft-fleet replicated serving.

Pairs a **fleet of N replica processes behind the FleetRouter** against a
**single replica behind the same router** on identical offered load, and —
because the fleet's whole claim is robustness — SIGKILLs one replica halfway
through every fleet repetition: the lane asserts ``dropped == 0`` and
``errors == 0`` (every submitted request got an answer; failovers and the
supervised respawn are invisible to clients) while reporting completed
throughput and client-observed p50/p99 round-trip latency.

Each replica is a REAL process: this script re-invokes itself with
``--replica --port P`` to build the same PPO CartPole policy as the
``BENCH_METRIC=serve`` lane (random init — latency/throughput do not care
about returns) and serve it through a full :class:`PolicyServer`.

Knobs (env vars): ``BENCH_FLEET_REPLICAS`` (default 3),
``BENCH_FLEET_LOADS`` (comma-separated offered req/s, default ``200``),
``BENCH_FLEET_DURATION`` (seconds per load, default 6),
``BENCH_FLEET_CLIENTS`` (client connections, default 4),
``BENCH_FLEET_BUCKETS`` (ladder, default ``1,8,32``),
``BENCH_FLEET_MODES`` (default ``fleet,single``).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def _build_policy():
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.factory import make_env
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.utils.registry import get_entrypoint, resolve_policy_builder

    cfg = compose(
        [
            "exp=ppo_benchmarks",
            "env.capture_video=False",
            "buffer.memmap=False",
            "metric.log_level=0",
            "metric.disable_timer=True",
            "checkpoint.save_last=False",
        ]
    )
    fabric = Fabric(devices=1, accelerator="cpu")
    fabric.seed_everything(cfg.seed)
    env = make_env(cfg, cfg.seed, 0, None, "serve_fleet_bench", vector_env_idx=0)()
    obs_space, act_space = env.observation_space, env.action_space
    env.close()
    builder = get_entrypoint(resolve_policy_builder(cfg.algo.name))
    return builder(fabric, cfg, obs_space, act_space, None)


def replica_main(port: int, buckets: List[int]) -> None:
    """One replica process: the bench policy behind a full PolicyServer."""
    from sheeprl_tpu.utils.utils import pin_cpu_platform

    pin_cpu_platform("cpu")
    from sheeprl_tpu.serve.server import PolicyServer, install_drain_handlers

    policy = _build_policy()
    drain = threading.Event()
    restore = install_drain_handlers(drain)
    server = PolicyServer(
        policy,
        {"buckets": buckets, "host": "127.0.0.1", "port": port, "max_wait_ms": 2.0, "supervisor": {"backoff": 0.05}},
    ).start()
    print(f"REPLICA_READY 127.0.0.1:{server.address[1]}", flush=True)
    try:
        while not drain.is_set():
            drain.wait(0.2)
    finally:
        server.stop()
        restore()


def _drive_load(addr, offered_rps: float, duration_s: float, n_clients: int) -> Dict[str, Any]:
    """n_clients paced connections through the router; per-request
    round-trip stamped client-side. Counted: sent, answered (== not
    dropped), action responses, error responses by kind."""
    per_client_interval = n_clients / max(offered_rps, 1e-9)
    results: Dict[str, Any] = {"sent": 0, "answered": 0, "ok": 0, "errors": [], "latencies": []}
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s
    obs = {"state": [[0.1, -0.2, 0.05, 0.3]]}

    def client_loop(i: int) -> None:
        sock = socket.create_connection(addr, timeout=60.0)
        rfile = sock.makefile("rb")
        payload = (json.dumps({"obs": obs, "n": 1}) + "\n").encode()
        next_send = time.perf_counter() + (i / n_clients) * per_client_interval
        try:
            while True:
                now = time.perf_counter()
                if now >= stop_at:
                    return
                if now < next_send:
                    time.sleep(min(next_send - now, 0.005))
                    continue
                next_send += per_client_interval
                t0 = time.perf_counter()
                with lock:
                    results["sent"] += 1
                sock.sendall(payload)
                line = rfile.readline()
                if not line:
                    return  # connection lost: the sent request counts as dropped
                dt = time.perf_counter() - t0
                resp = json.loads(line.decode())
                with lock:
                    results["answered"] += 1
                    if "error" in resp:
                        results["errors"].append(resp["error"])
                    else:
                        results["ok"] += 1
                        results["latencies"].append(dt)
        finally:
            try:
                rfile.close()
                sock.close()
            except OSError:
                pass

    threads = [threading.Thread(target=client_loop, args=(i,)) for i in range(n_clients)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    lat = np.sort(np.asarray(results["latencies"])) if results["latencies"] else np.asarray([0.0])
    return {
        "offered_rps": offered_rps,
        "completed_rps": round(results["ok"] / elapsed, 2),
        "sent": results["sent"],
        "answered": results["answered"],
        "dropped": results["sent"] - results["answered"],
        "errors": len(results["errors"]),
        "error_samples": results["errors"][:3],
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "elapsed_s": round(elapsed, 2),
    }


def _stand_up(n_replicas: int, buckets: List[int]):
    from sheeprl_tpu.fault.procsup import ProcessSupervisor
    from sheeprl_tpu.serve.fleet import FleetRouter, ReplicaEndpoint, free_port

    sup = ProcessSupervisor(lease_s=10.0, grace_s=600.0, backoff=0.1, max_restarts=3, name="bench-fleet")
    endpoints = []
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for i in range(n_replicas):
        port = free_port()
        cmd = [
            sys.executable,
            os.path.abspath(__file__),
            "--replica",
            "--port",
            str(port),
            "--buckets",
            ",".join(str(b) for b in buckets),
        ]

        def spawn(cmd=cmd):
            return subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        sup.spawn(f"replica-{i}", spawn)
        endpoints.append(ReplicaEndpoint(f"replica-{i}", "127.0.0.1", port, request_timeout_s=30.0))
    router = FleetRouter(
        endpoints,
        fleet_cfg={"health_poll_s": 0.1, "retry_budget": 3, "request_timeout_s": 30.0},
        procsup=sup,
        owns_replicas=True,
        port=0,
    ).start()
    if not router.wait_ready(timeout_s=600):
        router.stop()
        raise SystemExit("serve_fleet bench: replicas never became ready")
    return router, sup


def main() -> None:
    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 3))
    loads = [float(x) for x in os.environ.get("BENCH_FLEET_LOADS", "200").split(",") if x.strip()]
    duration = float(os.environ.get("BENCH_FLEET_DURATION", 6))
    n_clients = int(os.environ.get("BENCH_FLEET_CLIENTS", 4))
    buckets = [int(b) for b in os.environ.get("BENCH_FLEET_BUCKETS", "1,8,32").split(",")]
    modes = [m.strip() for m in os.environ.get("BENCH_FLEET_MODES", "fleet,single").split(",") if m.strip()]

    for mode in modes:
        n = replicas if mode == "fleet" else 1
        router, sup = _stand_up(n, buckets)
        try:
            for offered in loads:
                killer = None
                if mode == "fleet":
                    # one replica kill per fleet rep, halfway through: the
                    # robustness claim measured, not assumed
                    def kill_one():
                        for h in sup.replicas():
                            if h.is_alive():
                                os.kill(h.pid(), signal.SIGKILL)
                                return

                    killer = threading.Timer(duration / 2.0, kill_one)
                    killer.start()
                rep = _drive_load(router.address, offered, duration, n_clients)
                if killer is not None:
                    killer.cancel()
                health = router.health()
                rep.update(
                    {
                        "metric": "serve_fleet_requests_per_sec",
                        "mode": mode,
                        "replicas": n,
                        "clients": n_clients,
                        "buckets": buckets,
                        "replica_kills": sum(h.kills for h in sup.replicas()) if mode == "fleet" else 0,
                        "replica_restarts": sum(h.restarts for h in sup.replicas()),
                        "router_retries": health["fleet"]["retries"],
                        "router_shed": health["fleet"]["shed"],
                        "sessions_rehomed": health["fleet"]["sessions_rehomed"],
                    }
                )
                print(json.dumps(rep), flush=True)
                # the lane's hard assertions: nothing dropped, nothing errored
                assert rep["dropped"] == 0, f"serve_fleet bench dropped {rep['dropped']} requests: {rep}"
                assert rep["errors"] == 0, f"serve_fleet bench errored requests: {rep['error_samples']}"
                if mode == "fleet":
                    assert rep["replica_kills"] >= 1, "fleet rep finished without its replica kill"
        finally:
            router.stop()


if __name__ == "__main__":
    if "--replica" in sys.argv:
        port = int(sys.argv[sys.argv.index("--port") + 1])
        raw = sys.argv[sys.argv.index("--buckets") + 1] if "--buckets" in sys.argv else "1,8,32"
        replica_main(port, [int(b) for b in raw.split(",")])
    else:
        main()
