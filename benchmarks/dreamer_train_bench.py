#!/usr/bin/env python
"""Dreamer-V3 train-step throughput on the available accelerator.

Measures the steady-state wall time of ONE fully-jitted gradient step
(dynamic-learning scan + imagination scan + actor/critic updates) at the
Atari-100K training shape — ``batch 16 x seq 64`` replayed frames — for a
chosen size config (default S, the Atari-100K config; see BASELINE.md).

Reports replayed-frames/s and the implied env-steps/s at ``replay_ratio``
(Atari-100K trains one gradient step per policy step: replay_ratio=1 over
batch*seq frames). Timing uses ``block_until_ready`` on device outputs.

    python benchmarks/dreamer_train_bench.py            # S size, 5 steps
    python benchmarks/dreamer_train_bench.py M 10
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    # `python benchmarks/<script>.py` puts benchmarks/ (not the repo root) at
    # sys.path[0]; make the package importable without an editable install.
    sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    size = sys.argv[1] if len(sys.argv) > 1 else "S"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5

    import jax

    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    import gymnasium as gym
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.parallel.fabric import Fabric

    cfg = compose(
        [
            "exp=dreamer_v3",
            f"algo=dreamer_v3_{size}",
            "env=dummy",
            "algo.per_rank_batch_size=16",
            "algo.per_rank_sequence_length=64",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "env.screen_size=64",
        ]
    )
    fabric = Fabric(devices=1)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    n_act = 9  # MsPacman action set
    world_model, actor, critic, params, _ = build_agent(fabric, (n_act,), False, cfg, obs_space)
    txs = {
        "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
        "actor": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
    }
    opts = {
        "world": txs["world"].init(params["world_model"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
    }
    opts = fabric.put_replicated(opts)
    moments = fabric.put_replicated(init_moments())
    train_fn = make_train_step(world_model, actor, critic, cfg, fabric.mesh, (n_act,), False, txs)

    G, T, B = 1, 64, 16
    rng = np.random.default_rng(0)
    sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))
    data = {
        "rgb": rng.integers(0, 255, (G, T, B, 64, 64, 3)).astype(np.float32),
        "actions": np.eye(n_act, dtype=np.float32)[rng.integers(0, n_act, (G, T, B))],
        "rewards": rng.normal(size=(G, T, B, 1)).astype(np.float32),
        "terminated": np.zeros((G, T, B, 1), np.float32),
        "truncated": np.zeros((G, T, B, 1), np.float32),
        "is_first": np.zeros((G, T, B, 1), np.float32),
    }
    data = {k: jax.device_put(v, sharding) for k, v in data.items()}

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    params, opts, moments, _ = train_fn(params, opts, moments, data, key, jnp.int32(0))
    jax.block_until_ready(params)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(steps):
        params, opts, moments, _ = train_fn(params, opts, moments, data, key, jnp.int32(i + 1))
    jax.block_until_ready(params)
    per_step = (time.perf_counter() - t0) / steps

    frames = T * B
    print(
        json.dumps(
            {
                "benchmark": f"dreamer_v3_{size}_train_step",
                "device": str(jax.devices()[0]),
                "batch": B,
                "seq_len": T,
                "compile_s": round(compile_s, 2),
                "train_step_ms": round(per_step * 1e3, 2),
                "replayed_frames_per_sec": round(frames / per_step, 1),
            }
        )
    )


if __name__ == "__main__":
    main()
