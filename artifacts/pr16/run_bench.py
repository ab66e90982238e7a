#!/usr/bin/env python
"""PR 16 paired bench driver: BENCH_METRIC=scenario_matrix, alternating
reps per mode (vmapped / sequential) at the IDENTICAL per-scenario recipe
(CartPole pole-length ladder, same seed, same step budget), warm XLA cache
(one unrecorded warmup run per mode first). Writes
artifacts/pr16/scenario_matrix_bench.json."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
STEPS = int(os.environ.get("BENCH_TOTAL_STEPS", 65536))
POP = int(os.environ.get("BENCH_SCENARIO_SIZE", 8))
REPS = int(os.environ.get("BENCH_REPS", 3))


def run_once(mode: str) -> dict:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_METRIC": "scenario_matrix",
        "BENCH_SCENARIO_MODE": mode,
        "BENCH_SCENARIO_SIZE": str(POP),
        "BENCH_TOTAL_STEPS": str(STEPS),
    }
    out = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=3600,
    )
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


results = {"vmapped": [], "sequential": []}
runs = []
for mode in ("vmapped", "sequential"):  # unrecorded warmups: fill the XLA cache
    rec = run_once(mode)
    print(f"warmup {mode}: {rec['value']} aggregate env-steps/s "
          f"(compiles {rec['block_compiles']})")
for rep in range(REPS):
    for mode in ("vmapped", "sequential"):  # alternating, same seed per rep
        rec = run_once(mode)
        rec["rep"] = rep
        results[mode].append(rec)
        runs.append(rec)
        print(f"rep {rep} {mode}: {rec['value']} aggregate env-steps/s "
              f"(elapsed {rec['elapsed_s']}s, compiles {rec['block_compiles']}, "
              f"fitness spread {rec['fitness_spread']})")

mean = {m: sum(r["value"] for r in v) / len(v) for m, v in results.items()}
ratios = [
    round(v["value"] / s["value"], 3)
    for v, s in zip(results["vmapped"], results["sequential"])
]
payload = {
    "metric": "ppo_cartpole_scenario_matrix_env_steps_per_sec",
    "conditions": {
        "exp": "ppo_anakin_population_benchmarks (both modes)",
        "env": "CartPole-v1 (pure-JAX twin)",
        "scenario_axis": "algo.population.env_params.length — pole half-lengths 0.25..1.0",
        "population_size": POP,
        "hparams": "none swept (identical per-scenario recipe, seed=42)",
        "total_steps_per_scenario": STEPS,
        "driver": "BENCH_METRIC=scenario_matrix BENCH_SCENARIO_MODE={vmapped,sequential} "
                  f"BENCH_SCENARIO_SIZE={POP} python bench.py",
        "sandbox": "CPU-only container, XLA compile cache warm (one unrecorded "
                   f"warmup run per mode), {REPS} alternating reps, nothing else running",
    },
    "runs": {m: results[m] for m in results},
    "summary": {
        "aggregate_env_steps_per_sec_mean": {m: round(v, 1) for m, v in mean.items()},
        "per_rep_ratio": ratios,
        "mean_ratio": round(mean["vmapped"] / mean["sequential"], 3),
        "block_compiles": {m: [r["block_compiles"] for r in v] for m, v in results.items()},
    },
}
with open(os.path.join(HERE, "scenario_matrix_bench.json"), "w") as fh:
    json.dump(payload, fh, indent=2)
print(json.dumps(payload["summary"]))
