#!/usr/bin/env python
"""PR 13 paired bench driver: BENCH_METRIC=dreamer_sebulba, 3 alternating
reps per mode (sebulba / coupled) at the IDENTICAL recipe (model, batch,
sequence length, replay ratio, env, seeds, step budget), warm XLA cache.
Writes artifacts/pr13/dreamer_sebulba_bench.json."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
STEPS = int(os.environ.get("BENCH_TOTAL_STEPS", 4096))
REPS = int(os.environ.get("BENCH_REPS", 3))

results = {"sebulba": [], "coupled": []}
runs = []
for rep in range(REPS):
    for mode in ("sebulba", "coupled"):  # alternating, same seeds per rep
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_METRIC": "dreamer_sebulba",
            "BENCH_DREAMER_MODE": mode,
            "BENCH_TOTAL_STEPS": str(STEPS),
        }
        out = subprocess.run(
            [sys.executable, "bench.py"], cwd=REPO, env=env, capture_output=True, text=True,
            timeout=3600,
        )
        line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
        rec = json.loads(line)
        rec["rep"] = rep
        results[mode].append(rec["value"])
        runs.append(rec)
        print(f"rep {rep} {mode}: {rec['value']} env-steps/s "
              f"(elapsed {rec['elapsed_s']}s, replay_path {rec['replay_path_s']}s, "
              f"train {rec['train_s']}s, env {rec['env_interaction_s']}s)")

mean = {m: sum(v) / len(v) for m, v in results.items()}
payload = {
    "metric": "dreamer_dummy_sebulba_env_steps_per_sec",
    "total_steps": STEPS,
    "reps": REPS,
    "runs": runs,
    "mean": {m: round(v, 2) for m, v in mean.items()},
    "ratio_sebulba_over_coupled": round(mean["sebulba"] / mean["coupled"], 3),
}
with open(os.path.join(HERE, "dreamer_sebulba_bench.json"), "w") as fh:
    json.dump(payload, fh, indent=2)
print(json.dumps(payload["mean"]), "ratio:", payload["ratio_sebulba_over_coupled"])
