"""Offered-load SLO bench for graft-sessions: K concurrent stateful clients.

Builds a ppo_recurrent stateful policy (the LSTM-hidden session family),
stands up the full serving stack — session engine + cache, micro-batching
scheduler, versioned weight store — and drives it with K CLOSED-LOOP session
clients: each client is one user streaming sequential steps (a session can
only send step t+1 after receiving step t — that is what session traffic IS),
so the lane reports aggregate session-steps/s and p50/p99 step latency, with
one hot weight swap published mid-run (sessions must ride it live:
``sessions_reset == 0`` is asserted).

``BENCH_SESSIONS_MODE`` pairs the two dispatch disciplines on identical
traffic:

- ``batched`` (default) — the bucket ladder: concurrent sessions' states are
  gathered into ONE padded ``serve.session[N].step`` dispatch per admitted
  batch (GA3C's predictor queue, stateful);
- ``naive``  — per-session dispatch: ``session.buckets=[1]`` +
  ``max_batch=1``, every session step is its own bucket-1 program call — the
  per-user-model-replica discipline a session server without cross-session
  batching degenerates to.

Knobs (env vars): ``BENCH_SESSIONS`` (concurrent sessions, default 32),
``BENCH_SESSIONS_DURATION`` (seconds, default 6),
``BENCH_SESSIONS_BUCKETS`` (batched-mode ladder, default ``1,8,32``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List


def _build_policy():
    import gymnasium as gym
    import numpy as np

    from sheeprl_tpu.config import compose
    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.utils.registry import get_entrypoint, resolve_policy_builder

    cfg = compose(
        [
            "exp=ppo_recurrent",
            "env=gym",
            "env.capture_video=False",
            "buffer.memmap=False",
            "fabric.devices=1",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "algo.mlp_keys.encoder=[state]",
        ]
    )
    fabric = Fabric(devices=1, accelerator="cpu")
    fabric.seed_everything(cfg.seed)
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, (4,), np.float32)})
    act_space = gym.spaces.Discrete(2)
    builder = get_entrypoint(resolve_policy_builder(cfg.algo.name))
    # fresh params: session-step latency/throughput does not care about returns
    return builder(fabric, cfg, obs_space, act_space, None)


def main() -> None:
    import numpy as np

    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    mode = os.environ.get("BENCH_SESSIONS_MODE", "batched").strip().lower()
    if mode not in ("batched", "naive"):
        raise SystemExit(f"Unknown BENCH_SESSIONS_MODE '{mode}' (expected 'batched' or 'naive')")
    n_sessions = int(os.environ.get("BENCH_SESSIONS", "32"))
    duration = float(os.environ.get("BENCH_SESSIONS_DURATION", "6"))
    buckets = [int(x) for x in os.environ.get("BENCH_SESSIONS_BUCKETS", "1,8,32").split(",") if x.strip()]

    from sheeprl_tpu.serve.server import PolicyServer

    policy = _build_policy()
    serve_cfg = {
        "mode": "greedy",
        "max_wait_ms": 2.0,
        "queue_bound": 1024,
        "port": None,
        "session": {"buckets": buckets, "max_sessions": max(64, 2 * n_sessions), "ttl_s": 600.0},
    }
    if mode == "naive":
        # per-session dispatch: no cross-session batching, one bucket-1
        # program call per step
        serve_cfg["session"]["buckets"] = [1]
        serve_cfg["max_batch"] = 1
        serve_cfg["max_wait_ms"] = 0.0
    server = PolicyServer(policy, serve_cfg)
    server.start(with_socket=False)

    stop_at = time.perf_counter() + duration
    latencies: List[float] = []
    lat_lock = threading.Lock()
    counters = {"steps": 0, "errors": 0}

    def client_loop(idx: int) -> None:
        rng = np.random.default_rng(idx)
        while time.perf_counter() < stop_at:
            obs = {"state": rng.standard_normal(4).astype(np.float32)}
            t0 = time.perf_counter()
            try:
                server.client.act(obs, session_id=f"user-{idx}", timeout=120.0)
            except Exception:
                with lat_lock:
                    counters["errors"] += 1
                continue
            with lat_lock:
                latencies.append(time.perf_counter() - t0)
                counters["steps"] += 1

    threads = [threading.Thread(target=client_loop, args=(i,), daemon=True) for i in range(n_sessions)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    # one hot weight swap mid-run: sessions must ride it live
    time.sleep(duration / 2)
    import jax

    _, current = server.weights.pull()
    swap_version = server.weights.publish_params(jax.tree.map(lambda x: x + 1e-3, current))
    for t in threads:
        t.join(timeout=duration + 180.0)
    elapsed = time.perf_counter() - start
    sessions_snap = server.engine.cache.snapshot()
    engine_stats = server.engine.stats()
    server.stop()

    lat = np.sort(np.asarray(latencies)) if latencies else np.zeros(1)
    assert sessions_snap["resets"] == 0, "a weight swap reset live sessions"
    print(
        json.dumps(
            {
                "metric": "ppo_recurrent_serve_session_steps_per_sec",
                "value": round(counters["steps"] / elapsed, 1),
                "unit": "session-steps/s",
                "mode": mode,
                "sessions": n_sessions,
                "buckets": serve_cfg["session"]["buckets"],
                "duration_s": round(elapsed, 2),
                "steps": counters["steps"],
                "errors": counters["errors"],
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "swap_version": swap_version,
                "sessions_live": sessions_snap["live"],
                "sessions_reset": sessions_snap["resets"],
                "batch_fill_ratio": engine_stats["batch_fill_ratio"],
                "dispatches": engine_stats["dispatches"],
                "steps_per_dispatch": round(counters["steps"] / max(1, engine_stats["dispatches"]), 2),
            }
        )
    )


if __name__ == "__main__":
    main()
