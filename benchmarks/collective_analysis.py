#!/usr/bin/env python
"""Make the multi-chip scaling claim falsifiable from one chip.

Compiles the REAL PPO and Dreamer-V3 train steps over dp=8 and dp=64
virtual meshes, walks the optimized HLO for every collective op
(all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all), accounts the exact bytes each moves per step, and derives a
v5e ICI roofline bound on data-parallel scaling efficiency:

    t_coll(ring all-reduce of B bytes over n chips) = 2*B*(n-1)/n / ICI_BW
    efficiency_bound = t_compute / (t_compute + t_coll)

with ``t_compute`` taken from a single-chip step time (``MEASURED_STEP_S``
below) — so the claim is a checkable arithmetic consequence
of (a) the byte counts printed here, (b) the public v5e ICI bandwidth, and
(c) a measured single-chip step time, not an extrapolated wall-clock.

Run (CPU-only, no TPU needed):

    python benchmarks/collective_analysis.py          # both algos, dp=8,64
    python benchmarks/collective_analysis.py ppo 8    # one row

Each row prints one JSON line; the summary lines carry the roofline.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Public v5e specs (Google Cloud TPU docs / the scaling-book numbers):
# 197 bf16 TFLOP/s per chip; 1600 Gbps (= 200 GB/s) aggregate ICI per chip.
V5E_ICI_BYTES_PER_S = 200e9
# Single-chip step times, the one measured input of the bound. Both are the
# builders' round-3/4 figures (in git history before PR 21), taken on an
# earlier installation and NOT measured on the current code: dreamer_v3 is
# the batch-16 x seq-64 S step (35.23 ms, benchmarks/dreamer_train_bench.py)
# — the analysis meshes carry the same batch-16 PER DEVICE (weak scaling), so
# it stands for the per-device compute at every dp; ppo is a 512-batch step
# on the CPU backend, scaled. ROADMAP S7 replaces both with a four-chip run.
MEASURED_STEP_S = {"dreamer_v3": 35.23e-3, "ppo": 16.0e-3 / 20}


# ONE lowering/HLO-walk path shared with the graft-audit gate
# (sheeprl_tpu/analysis/hlo.py): the bench's byte accounting and the audit's
# collective budgets can never drift apart.
sys.path.insert(0, _REPO_ROOT)
from sheeprl_tpu.analysis.hlo import account_collectives  # noqa: E402


def _analyze_body(algo: str, n_devices: int, reduce_dtype: str = "float32") -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, _REPO_ROOT)
    import __graft_entry__ as ge

    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.parallel.comm import set_grad_reduce_dtype
    from sheeprl_tpu.parallel.fabric import Fabric

    set_grad_reduce_dtype(reduce_dtype)

    if algo == "ppo":
        from sheeprl_tpu.algos.ppo.ppo import make_train_step

        cfg, agent, params, obs = ge._ppo_setup()
        fabric = Fabric(devices=n_devices, mesh_axes=("dp",))
        tx = optax.inject_hyperparams(
            lambda learning_rate: build_optimizer(
                {**cfg.algo.optimizer, "lr": learning_rate}, max_grad_norm=cfg.algo.max_grad_norm
            )
        )(learning_rate=float(cfg.algo.optimizer.lr))
        opt_state = tx.init(params)
        B = 8 * n_devices
        train_fn = make_train_step(agent, tx, cfg, fabric.mesh, B // n_devices)
        rng = np.random.default_rng(0)
        data = {
            "state": jnp.asarray(rng.normal(size=(B, 4)), dtype=jnp.float32),
            "actions": jnp.asarray(rng.integers(0, 2, size=(B, 2)), dtype=jnp.float32),
            "logprobs": jnp.zeros((B, 1), jnp.float32),
            "values": jnp.zeros((B, 1), jnp.float32),
            "returns": jnp.zeros((B, 1), jnp.float32),
            "advantages": jnp.zeros((B, 1), jnp.float32),
            "rewards": jnp.zeros((B, 1), jnp.float32),
            "dones": jnp.zeros((B, 1), jnp.uint8),
        }
        data = fabric.shard_data(data)
        p = fabric.put_replicated(params)
        o = fabric.put_replicated(opt_state)
        lowered = train_fn.lower(p, o, data, jax.random.PRNGKey(0), jnp.float32(0.2), jnp.float32(0.0))
    else:
        # The REAL flagship shape: dreamer_v3_S at the measured batch-16 x
        # seq-64 per-device load (weak scaling: global batch = 16 * dp).
        # Data is passed as ShapeDtypeStructs — AOT lowering needs shapes +
        # shardings, not 3 GB of concrete pixels at dp=64.
        import gymnasium as gym

        from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
        from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
        from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
        from sheeprl_tpu.config import compose

        per_dev_batch = 16
        cfg = compose(
            [
                "exp=dreamer_v3",
                "algo=dreamer_v3_S",
                "env=dummy",
                f"algo.per_rank_batch_size={per_dev_batch * n_devices}",
                "algo.per_rank_sequence_length=64",
                "algo.cnn_keys.encoder=[rgb]",
                "algo.mlp_keys.encoder=[]",
                "algo.mlp_keys.decoder=[]",
                "env.screen_size=64",
            ]
        )
        fabric = Fabric(devices=n_devices, mesh_axes=("dp",))
        obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
        world_model, actor, critic, params, _ = build_agent(fabric, (18,), False, cfg, obs_space)
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
        train_fn = make_train_step(world_model, actor, critic, cfg, fabric.mesh, (18,), False, txs)
        G, T, B = 1, 64, per_dev_batch * n_devices
        sharding = NamedSharding(fabric.mesh, P(None, None, "dp"))
        shapes = {
            "rgb": (G, T, B, 64, 64, 3),
            "actions": (G, T, B, 18),
            "rewards": (G, T, B, 1),
            "terminated": (G, T, B, 1),
            "truncated": (G, T, B, 1),
            "is_first": (G, T, B, 1),
        }
        data = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=sharding) for k, v in shapes.items()}
        p = fabric.put_replicated(params)
        o = fabric.put_replicated(opts)
        m = fabric.put_replicated(init_moments())
        lowered = train_fn.lower(p, o, m, data, jax.random.PRNGKey(0), jnp.int32(0))

    compiled = lowered.compile()
    hlo = compiled.as_text()
    table = account_collectives(hlo)
    cost = (compiled.cost_analysis() or [{}])
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    flops = float(cost.get("flops", 0.0))
    total_bytes = sum(v["bytes"] for v in table.values())
    print(
        json.dumps(
            {
                "algo": algo,
                "dp": n_devices,
                "grad_reduce_dtype": reduce_dtype,
                "collectives": table,
                "collective_bytes_per_step": total_bytes,
                "hlo_flops_per_device": flops,
            }
        )
    )


def roofline(algo: str, rows: list) -> dict:
    """v5e ring-all-reduce roofline from the measured step time + byte count.

    DP collective volume is gradient-sized — independent of n up to the
    ring factor 2(n-1)/n — so the dp=8/dp=64 rows cross-check that the
    compiler didn't introduce extra resharding as the mesh widens."""
    t_comp = MEASURED_STEP_S[algo]
    out = {"algo": algo, "t_compute_s": t_comp, "assumed_ici_bytes_per_s": V5E_ICI_BYTES_PER_S}
    for row in rows:
        n = row["dp"]
        b = row["collective_bytes_per_step"]
        if row.get("grad_reduce_dtype") == "bfloat16":
            # Both collectives ride the wire dtype under bfloat16: gradients
            # via pmean_grads, the Moments percentile gather via
            # all_gather_wire. XLA:CPU promotes BOTH back to f32 during
            # lowering (no native host bf16 collectives — the feeding
            # converts are visible in HLO, tests/test_utils/test_comm.py), so
            # the CPU-accounted bytes are halved analytically; on TPU the
            # collectives keep bf16 on the wire.
            b = b // 2
            out["cpu_hlo_promotes_bf16_collectives"] = True
        t_coll = 2 * b * (n - 1) / n / V5E_ICI_BYTES_PER_S
        out[f"dp{n}"] = {
            "collective_bytes": b,
            "t_collective_s": round(t_coll, 6),
            "efficiency_bound": round(t_comp / (t_comp + t_coll), 4),
        }
    return out


def main() -> None:
    if len(sys.argv) >= 3:  # worker: one (algo, dp[, reduce_dtype]) row
        _analyze_body(sys.argv[1], int(sys.argv[2]), sys.argv[3] if len(sys.argv) > 3 else "float32")
        return
    results: dict = {}
    jobs = [("ppo", 8, "float32"), ("ppo", 64, "float32")] + [
        ("dreamer_v3", n, dt) for dt in ("float32", "bfloat16") for n in (8, 64)
    ]
    for algo, n, dtype in jobs:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n}").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), algo, str(n), dtype],
            env=env, capture_output=True, text=True, timeout=1800, cwd=_REPO_ROOT,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"{algo} dp={n} {dtype} failed:\n{proc.stderr[-3000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        results.setdefault((algo, dtype), []).append(row)
        print(json.dumps(row))
    for (algo, dtype), rows in results.items():
        print(json.dumps({"roofline": {**roofline(algo, rows), "grad_reduce_dtype": dtype}}))


if __name__ == "__main__":
    main()
