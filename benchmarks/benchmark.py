#!/usr/bin/env python
"""Wall-clock benchmark harness (reference: ``benchmarks/benchmark.py``).

The reference toggles commented argument blocks; here the algorithm is the
first CLI argument and everything after is passed through as overrides::

    python benchmarks/benchmark.py ppo
    python benchmarks/benchmark.py sac fabric.devices=2 env.num_envs=8
    python benchmarks/benchmark.py dreamer_v3

Prints the elapsed wall-clock seconds and an env-steps/s JSON line. Uses the
repository's one persistent compilation cache (``enable_compile_cache``,
through the CLI) so repeated runs measure the framework, not the compiler.
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

KNOWN = ("ppo", "a2c", "sac", "dreamer_v1", "dreamer_v2", "dreamer_v3")


def main() -> None:
    if len(sys.argv) < 2 or sys.argv[1] not in KNOWN:
        raise SystemExit(f"usage: benchmark.py <{'|'.join(KNOWN)}> [overrides...]")
    algo = sys.argv[1]
    overrides = sys.argv[2:]

    from sheeprl_tpu.cli import check_configs, run_algorithm
    from sheeprl_tpu.config import compose

    cfg = compose([f"exp={algo}_benchmarks", *overrides])
    total_steps = int(cfg.algo.total_steps)

    # one process: run_algorithm opens the backend (and the compile cache)
    tic = time.perf_counter()
    check_configs(cfg)
    run_algorithm(cfg)
    elapsed = time.perf_counter() - tic
    result = {
        "benchmark": algo,
        "elapsed_s": round(elapsed, 2),
        "env_steps_per_sec": round(total_steps / elapsed, 2),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
