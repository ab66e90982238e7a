"""Chipless rehearsal, run by hand: compiles the burst program of a cell for a
described v5e (no chip attached) at the cell's real sizes and prints the
compiler's memory analysis. A compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python chipbench/tests/aot_burst.py <workload>
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import run as harness  # chipbench/run.py

workload = sys.argv[1]
bench = harness.load_json(ROOT, "BENCHMARK.json")
cell, config, traffic = harness.find_cell(bench, workload)

from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.config import compose
from sheeprl_tpu.data.ring import effective_stage_buckets, make_blob_layouts
from sheeprl_tpu.ops.kernels import registry
from sheeprl_tpu.optim.builders import build_optimizer
from sheeprl_tpu.parallel.fabric import Fabric
from sheeprl_tpu.utils.burst import dreamer_ring_keys, dreamer_stage_sizes

registry._process_has_tpu = lambda: True  # take the kernel tier a TPU process takes
jax.config.update("jax_enable_compilation_cache", False)

cfg = compose(harness.compose_overrides(config, traffic, 5, []))
harness.check_config_as_run(cfg, config)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
rep = NamedSharding(mesh, P())

fabric = Fabric(devices=1, accelerator="cpu")
obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, tuple(config["assumed"]["image"]), np.uint8)})
actions_dim = (config["assumed"]["actions"],)
world_model, actor, critic, params, _ = build_agent(fabric, actions_dim, False, cfg, obs_space)
txs = {
    "world": build_optimizer(cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients),
    "actor": build_optimizer(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
    "critic": build_optimizer(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
}
opts = {"world": txs["world"].init(params["world_model"]), "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"])}
n_envs, capacity = int(cfg.env.num_envs), int(cfg.buffer.size) // int(cfg.env.num_envs)
train_every = int(cfg.algo.hybrid_player.train_every)
grad_chunk = max(1, int(round(cfg.algo.replay_ratio * n_envs * train_every)))
stage_max, stage_buckets = dreamer_stage_sizes(train_every, n_envs, capacity)
buckets = effective_stage_buckets(stage_buckets, stage_max)
ring_keys = dreamer_ring_keys(obs_space, ["rgb"], [], actions_dim, with_is_first=True)
ring = {"capacity": capacity, "n_envs": n_envs, "grad_chunk": grad_chunk,
        "seq_len": int(cfg.algo.per_rank_sequence_length), "batch_size": int(cfg.algo.per_rank_batch_size),
        "ring_keys": ring_keys, "stage_buckets": buckets, "stage_max": stage_max}
burst_fn = make_train_step(world_model, actor, critic, cfg, mesh, actions_dim, False, txs, ring=ring)
layouts = make_blob_layouts(ring_keys, n_envs, grad_chunk, buckets)


def aval(x):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype, sharding=rep)


carry = jax.tree.map(aval, (params, opts, init_moments(), jnp.int32(0)))
rb = {k: jax.ShapeDtypeStruct((capacity, n_envs) + shape, dtype, sharding=rep) for k, (shape, dtype) in ring_keys.items()}
size = min(buckets)
blob = jax.ShapeDtypeStruct((layouts[size].nbytes,), jnp.uint8, sharding=rep)
compiled = burst_fn.trace(carry, rb, blob).lower(lowering_platforms=("tpu",)).compile()
mem = compiled.memory_analysis()
state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(carry))
ring_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(rb))
out = {
    "workload": workload, "bucket_rows": size, "grad_chunk": grad_chunk,
    "state_bytes": state_bytes, "ring_bytes": ring_bytes,
    "argument_bytes": mem.argument_size_in_bytes, "output_bytes": mem.output_size_in_bytes,
    "alias_bytes": mem.alias_size_in_bytes, "temp_bytes": mem.temp_size_in_bytes,
    "peak_estimate_bytes": mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    + mem.temp_size_in_bytes,
    "flops_xla": (compiled.cost_analysis() or {}).get("flops"),
    "pallas_calls": compiled.as_text().count("tpu_custom_call"),
}
print(json.dumps(out))
