"""The control, at a size a test run can hold: the reference computed in
bfloat16 and put in the program's place has to read as not correct against
the float32 reference, by the limits of every configuration's file. So has the
reference with half of the batch left out. (On the chip the same two readings
were taken at each cell's own size, three seeds each: PERF.md, section 2.)"""

import json
import os

import numpy as np
import pytest

from conftest import ROOT
from run import load_module

TINY_AS_RUN = {
    "algo.dense_units": 32, "algo.mlp_layers": 1,
    "algo.world_model.encoder.cnn_channels_multiplier": 4,
    "algo.world_model.recurrent_model.recurrent_state_size": 32,
    "algo.world_model.recurrent_model.dense_units": 32,
    "algo.world_model.transition_model.hidden_size": 32,
    "algo.world_model.representation_model.hidden_size": 32,
    "algo.world_model.stochastic_size": 8, "algo.world_model.discrete_size": 8,
    "algo.world_model.reward_model.bins": 255, "algo.critic.bins": 255,
    "algo.horizon": 4, "algo.per_rank_batch_size": 8, "algo.per_rank_sequence_length": 8,
    "algo.unimix": 0.01, "env.screen_size": 64,
}


@pytest.fixture(scope="module")
def readings():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from sheeprl_tpu.config import compose

    conf = json.load(open(os.path.join(ROOT, "chipbench", "configs", "dreamer_v3_S.json")))
    ref = load_module("reference", "dreamer_v3_ref")
    correct = load_module("correct", "dreamer_v3")
    cfg = compose(["exp=dreamer_v3_100k_atari_dummy", "algo=dreamer_v3_XS"])
    h = ref.hyper(TINY_AS_RUN, conf["assumed"], cfg)
    out = {}
    for seed in (11, 12, 13):
        rng = np.random.default_rng(seed)
        rows = []
        for i in range(48):
            action = np.eye(18, dtype=np.float32)[rng.integers(0, 18, size=1)]
            rows.append(({"rgb": rng.integers(0, 256, (1, 64, 64, 3), dtype=np.uint8), "actions": action,
                          "rewards": rng.integers(0, 2, (1, 1)).astype(np.float32),
                          "terminated": np.zeros((1, 1), np.float32),
                          "is_first": np.full((1, 1), float(i % 20 == 0), np.float32)}, np.ones(1, np.int32)))
        flush = {"key": np.asarray([seed, 7], np.uint32), "grad_chunk": 4}
        params = ref.init_params(h, seed)
        want = ref.follow(h, params, rows, flush, 2000, 3)
        names = ref.leaf_names(params)
        out[seed] = {
            "control": correct.compare(ref.follow(h, params, rows, flush, 2000, 3, compute="bfloat16"), want, names),
            "half_batch": correct.compare(ref.follow(h, params, rows, flush, 2000, 3, fault="half_batch"), want, names),
            "again": correct.compare(ref.follow(h, params, rows, flush, 2000, 3), want, names),
        }
    return out


@pytest.mark.parametrize("config", ["dreamer_v3_S", "dreamer_v3_XL"])
def test_control_and_fault_fail_some_limit(readings, config):
    path = os.path.join(ROOT, "chipbench", "configs", config + ".json")
    if not os.path.isfile(path):
        pytest.skip("configuration not in this benchmark")
    limits = json.load(open(path))["correct_limits"]
    for seed, r in readings.items():
        assert all(v == 0 for v in r["again"].values())  # the reference repeats itself exactly
        for kind in ("control", "half_batch"):
            assert any(limits.get(name) is not None and r[kind][name] > limits[name] for name in r[kind]), (
                seed, kind, r[kind])
