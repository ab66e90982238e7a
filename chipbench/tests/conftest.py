import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

# A size a CPU test run can hold: the XS preset cut further, 4 x 8 batches.
TINY = [
    "algo=dreamer_v3_XS", "algo.hybrid_player.enabled=true", "algo.learning_starts=64", "buffer.size=2000",
    "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=8", "algo.horizon=4",
    "algo.dense_units=32", "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.discrete_size=8", "algo.world_model.stochastic_size=8",
]


def rehearse(workload: str, *extra: str, seed: int = 3000000019, timeout: int = 900):
    """One `run.py --rehearsal 1` on the CPU at the tiny size; the last line."""
    cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", workload, "--seed", str(seed),
           "--seconds", "3", "--trace", "0", "--rehearsal", "1", *extra]
    for o in TINY:
        cmd += ["--override", o]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
