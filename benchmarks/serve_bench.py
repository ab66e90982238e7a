"""Offered-load SLO bench for the graft-serve inference tier.

Builds a PPO CartPole policy (the same model/conditions as the
``ppo_benchmarks`` lane), stands up the full serving stack — engine,
micro-batching scheduler, versioned weight store — and drives it with
open-loop client threads at fixed offered request rates. Per load it reports
completed throughput and p50/p99 request latency; halfway through each load
one hot weight swap is published, and the lane asserts zero
dropped/errored requests around it.

``BENCH_SERVE_MODE`` pairs the two engines on identical traffic:

- ``aot`` (default) — :class:`~sheeprl_tpu.serve.engine.BucketEngine`:
  continuous batching into AOT bucket-compiled programs;
- ``naive`` — :class:`~sheeprl_tpu.serve.engine.JitEngine` behind a
  ``max_batch=1`` scheduler: every request is its own ``jax.jit`` dispatch,
  the GA3C-without-a-predictor-queue baseline every per-actor policy call
  effectively is today.

Knobs (env vars): ``BENCH_SERVE_LOADS`` (comma-separated offered req/s,
default ``500,4000``), ``BENCH_SERVE_DURATION`` (seconds per load, default
6), ``BENCH_SERVE_CLIENTS`` (client threads, default 8),
``BENCH_SERVE_BUCKETS`` (ladder, default ``1,8,32,128``).

Open-loop arrivals with a bounded queue degrade gracefully: past capacity
the submit path backpressures and the measured throughput is the tier's
sustainable rate at that load — exactly the SLO number an operator needs.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List


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
    env = make_env(cfg, cfg.seed, 0, None, "serve_bench", vector_env_idx=0)()
    obs_space, act_space = env.observation_space, env.action_space
    env.close()
    builder = get_entrypoint(resolve_policy_builder(cfg.algo.name))
    # fresh params: serving latency/throughput does not care about returns
    return builder(fabric, cfg, obs_space, act_space, None), obs_space


def _drive_load(
    policy,
    scheduler,
    store,
    offered_rps: float,
    duration_s: float,
    n_generators: int,
) -> Dict[str, Any]:
    """Open-loop: generator threads pace ``scheduler.submit`` calls at the
    offered rate (prepared single-row obs; submission is cheap and does NOT
    wait for results, so generation capacity far exceeds engine capacity and
    saturation is the ENGINE's, not the client harness's); a collector
    thread drains the futures in submit order. Latency is stamped by the
    worker at resolve time, so collector lag can't inflate it. Past the
    queue bound the generators block (backpressure) — measured throughput is
    then the tier's sustainable rate at that load."""
    import collections

    import numpy as np

    counters = {"submitted": 0, "errors": 0}
    pending: "collections.deque" = collections.deque()
    pend_lock = threading.Lock()
    gen_done = threading.Event()
    stop_at = time.perf_counter() + duration_s
    period = n_generators / offered_rps  # per-thread inter-arrival

    def generator(idx: int) -> None:
        rng = np.random.default_rng(idx)
        next_t = time.perf_counter() + (idx / n_generators) * period  # phase-spread
        while True:
            now = time.perf_counter()
            if now >= stop_at:
                return
            if now < next_t:
                time.sleep(min(next_t - now, stop_at - now))
                continue
            next_t += period
            obs = policy.prepare({"state": rng.standard_normal(4).astype(np.float32)}, 1)
            try:
                req = scheduler.submit(obs, timeout=60.0)
                with pend_lock:
                    counters["submitted"] += 1
                    pending.append(req)
            except Exception:
                with pend_lock:
                    counters["errors"] += 1

    latencies: List[float] = []
    served: List[tuple] = []  # (t_resolve, version)
    collected = {"n": 0, "errors": 0}

    def collector() -> None:
        while True:
            with pend_lock:
                req = pending.popleft() if pending else None
            if req is None:
                if gen_done.is_set():
                    with pend_lock:
                        if not pending:
                            return
                    continue
                time.sleep(0.0005)
                continue
            if not req.event.wait(timeout=120.0) or req.error is not None:
                collected["errors"] += 1
                continue
            latencies.append(req.latency_s)
            served.append((req.t_resolve, req.version))
            collected["n"] += 1

    gens = [threading.Thread(target=generator, args=(i,), daemon=True) for i in range(n_generators)]
    col = threading.Thread(target=collector, daemon=True)
    start = time.perf_counter()
    for t in gens:
        t.start()
    col.start()
    # one hot weight swap mid-load: zero dropped/torn requests is the claim
    time.sleep(duration_s / 2)
    import jax

    _, current = store.pull()
    swap_version = store.publish_params(jax.tree.map(lambda x: x + 1e-3, current))
    for t in gens:
        t.join(timeout=duration_s + 120.0)
    gen_done.set()
    col.join(timeout=180.0)
    elapsed = time.perf_counter() - start
    lat = np.sort(np.asarray(latencies)) if latencies else np.zeros(1)
    # versions must be monotone in SERVE order (the generator-append order
    # races across threads and proves nothing)
    versions = [v for _, v in sorted(served)]
    monotone = all(a <= b for a, b in zip(versions, versions[1:]))
    return {
        "offered_rps": offered_rps,
        "duration_s": round(elapsed, 2),
        "submitted": counters["submitted"],
        "completed": collected["n"],
        "dropped": counters["submitted"] - collected["n"] - collected["errors"],
        "errors": counters["errors"] + collected["errors"],
        "throughput_rps": round(collected["n"] / elapsed, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "swap_version": swap_version,
        "max_version_served": max(versions) if versions else -1,
        "versions_monotone": monotone,
    }


def main() -> None:
    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    mode = os.environ.get("BENCH_SERVE_MODE", "aot").strip().lower()
    if mode not in ("aot", "naive"):
        raise SystemExit(f"Unknown BENCH_SERVE_MODE '{mode}' (expected 'aot' or 'naive')")
    loads = [float(x) for x in os.environ.get("BENCH_SERVE_LOADS", "2000,16000").split(",") if x.strip()]
    duration = float(os.environ.get("BENCH_SERVE_DURATION", "6"))
    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    buckets = [int(x) for x in os.environ.get("BENCH_SERVE_BUCKETS", "1,8,32,128").split(",") if x.strip()]

    from sheeprl_tpu.serve.server import PolicyServer

    policy, _ = _build_policy()
    serve_cfg = {
        "buckets": buckets,
        "mode": "greedy",
        "max_wait_ms": 2.0,
        "queue_bound": 1024,
        "port": None,
    }
    if mode == "naive":
        # the per-request baseline: no batching, one jit dispatch per request
        serve_cfg["max_batch"] = 1
        serve_cfg["max_wait_ms"] = 0.0
    server = PolicyServer(policy, serve_cfg, engine="aot" if mode == "aot" else "naive")
    server.start(with_socket=False)
    try:
        results = [
            _drive_load(policy, server.scheduler, server.weights, rps, duration, n_clients) for rps in loads
        ]
    finally:
        server.stop()
    snap = server.stats.snapshot()
    print(
        json.dumps(
            {
                "metric": "ppo_cartpole_serve_requests_per_sec",
                # headline: sustained throughput at the highest offered load
                "value": results[-1]["throughput_rps"],
                "unit": "requests/s",
                "mode": mode,
                "buckets": buckets if mode == "aot" else [],
                "max_wait_ms": serve_cfg["max_wait_ms"],
                "clients": n_clients,
                "loads": results,
                "swap_count": snap["Serve/swap_count"],
                "batch_fill_ratio": server.engine.stats()["batch_fill_ratio"],
                "dispatches": server.engine.stats()["dispatches"],
            }
        )
    )


if __name__ == "__main__":
    main()
