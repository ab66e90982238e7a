"""`exp=ppo_anakin_lm_kanana2` at toy widths through the CLI's entry point: the
second language-model policy (latent attention, a sigmoid router with a
selection bias, shared experts, a leading dense layer) on the same main, host
loop, block cache, recorder and checkpointing as the first; the bias left
bit-identical by the update; the first policy's block traced to the primitives
it had before the second came; and the audit program."""

import collections
import glob
import json
import os

import numpy as np
import pytest

from sheeprl_tpu.algos.ppo.ppo_anakin_lm import PROGRAM_NAME, TOY_OVERRIDES, TOY_OVERRIDES_LATENT
from sheeprl_tpu.cli import run
from sheeprl_tpu.utils import profiler

HERE = os.path.dirname(os.path.abspath(__file__))


def _args(tmp_path, iterations, save_last=False, envs=2):
    return [*TOY_OVERRIDES_LATENT, f"env.num_envs={envs}", "algo.per_rank_batch_size=1", "fabric.devices=1",
            "metric.log_level=0", f"checkpoint.save_last={save_last}", f"log_root={tmp_path}/logs",
            f"algo.total_steps={iterations * envs * 8}"]


def test_two_iterations_through_the_same_main_with_the_new_regions_and_counters(tmp_path):
    profiler.reset()
    run(_args(tmp_path, iterations=2))
    assert f"{PROGRAM_NAME}/1" in profiler.programs()
    spans = profiler.snapshot()["spans"]
    iters = [s["counters"] for s in spans if s["name"] == "iter"]
    assert [c["iter_num"] for c in iters] == [1, 2]
    for c in iters:
        # 2 routed layers x top 2 x (2 prompts of 24 + 2 gradient steps' 32 positions): every assignment could be moved
        assert c["moe_bias_movable"] == 2 * 2 * (2 * 24 + 2 * 32) and 0 <= c["moe_bias_moved"] <= c["moe_bias_movable"]
        # 3 layers x 2 sequences x 32 positions x (32 latent + 8 rotary) float32: the latent cache, no head axis
        assert c["rollout_cache_bytes"] == 3 * 2 * 32 * (32 + 8) * 4
        assert c["moe_compactable_calls"] == 0  # under a row tile of assignments: every row is moved anyway
    table = profiler.scope_table(f"{PROGRAM_NAME}/1")
    outers = {v["outer"] for v in table.values()}
    assert outers == (set(profiler.LM_BLOCK_REGIONS) - {"env.token", "lm.attn_global", "lm.attn_window"}) | {None}
    assert {"kernel.moe_grouped_ffn", "kernel.window_attention"} <= {v["scope"] for v in table.values()}
    backward = {v["outer"] for v in table.values() if v["backward"]}
    assert {"lm.embed", "lm.attn_mla", "lm.moe", "lm.ffn_shared", "lm.head_loss"} <= backward
    assert not backward & {"rollout.prefill", "rollout.decode", "ppo.optim"}


def test_the_first_policys_iter_span_carries_its_cache_size_and_no_bias_counter(tmp_path):
    profiler.reset()
    run([*TOY_OVERRIDES, "env.num_envs=2", "algo.per_rank_batch_size=1", "fabric.devices=1", "metric.log_level=0",
         "checkpoint.save_last=False", f"log_root={tmp_path}/logs", "dry_run=True"])
    (span,) = [s["counters"] for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    assert "moe_bias_moved" not in span and "moe_bias_movable" not in span
    # one global layer of 32 slots and three window rings of 8: keys and values of 2 heads x 16, 2 sequences, float32
    assert span["rollout_cache_bytes"] == 2 * (32 + 3 * 8) * 2 * (2 * 16) * 4


def test_checkpoint_and_resume_as_the_first_policy_does(tmp_path):
    """Four iterations checkpointing after the second, then a resume from that checkpoint: the old run's total
    governs, the counters go on from 32 and the run ends on the final step's checkpoint."""
    ckpts = lambda root: sorted(glob.glob(f"{root}/**/ckpt_*.ckpt", recursive=True), key=os.path.getmtime)  # noqa: E731
    run([*_args(tmp_path, iterations=4), "checkpoint.every=32", f"log_root={tmp_path}/first"])
    first = ckpts(f"{tmp_path}/first")
    assert first and "ckpt_32_" in first[0]
    run([*_args(tmp_path, iterations=4, save_last=True), f"checkpoint.resume_from={first[0]}", f"log_root={tmp_path}/resumed"])
    assert any("ckpt_64_" in c for c in ckpts(f"{tmp_path}/resumed"))


def _build_block(overrides, envs, **block_kwargs):
    """The block of one policy at toy widths on one device: ``(block, model, jenv, benv, tx)``."""
    import jax
    from jax.sharding import Mesh

    from sheeprl_tpu.algos.ppo import ppo_anakin_lm
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, make_jax_env
    from sheeprl_tpu.models import decoder_lm as lm
    from sheeprl_tpu.optim.builders import build_optimizer

    cfg = compose([*overrides, f"env.num_envs={envs}", "algo.per_rank_batch_size=1"])
    model = lm.DecoderConfig.from_config(cfg.algo.lm)
    jenv = make_jax_env(cfg.env.id, vocab_size=model.vocab_held, prompt_len=24, response_len=8)
    policy, benv = ppo_anakin_lm.LMPolicy(model, 24, 8), BatchedJaxEnv(jenv, envs)
    tx = build_optimizer(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm)
    block = ppo_anakin_lm.make_anakin_lm_block(policy, tx, cfg, Mesh(np.array(jax.devices()[:1]), ("dp",)), benv, envs, 1,
                                               guard=True, **block_kwargs)
    return block, model, jenv, benv, tx


def _toy_block():
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block([*TOY_OVERRIDES_LATENT, "algo.optimizer.lr=1e-2"], 3, ferry_episodes=False)

    def make_params():
        params = lm.init_params(model, jax.random.PRNGKey(0))
        for i, layer in enumerate(params["layers"]):  # a bias that moves selections, as a published model's would
            if "router_bias" in layer:
                layer["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(10 + i), layer["router_bias"].shape)
        return params

    def call(grad_steps):
        params = make_params()
        env_state, obs = benv.reset(jax.random.PRNGKey(1))
        return block(params, tx.init(params), env_state, jnp.copy(obs), jnp.zeros(3), jnp.zeros(3, jnp.int32),
                     jax.random.split(jax.random.PRNGKey(2), 1), jax.random.PRNGKey(3), jnp.float32(0.2), jnp.float32(0.01),
                     jenv.default_params(), jnp.int32(grad_steps))

    return call, make_params


def test_the_selection_bias_is_bit_identical_after_gradient_steps_while_every_trained_leaf_moved():
    import jax

    call, make_params = _toy_block()
    params, opt_state, *_, metrics = call(3)
    assert int(np.asarray(metrics["moe_bias_moved"]).sum()) > 0 and float(np.asarray(metrics["bad"]).sum()) == 0
    before = dict(jax.tree_util.tree_leaves_with_path(make_params()))
    moments = {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(opt_state) if "router_bias" in jax.tree_util.keystr(p)}
    assert moments and all(not np.asarray(x).any() for x in moments.values())  # Adam saw it under a zero gradient
    seen = 0
    for path, after in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            seen += 1
            assert np.asarray(after).tobytes() == np.asarray(before[path]).tobytes(), name
        else:
            assert float(np.abs(np.asarray(after) - np.asarray(before[path])).max()) > 0, name
    assert seen == 2


def _primitives(jaxpr, count):
    for eqn in jaxpr.eqns:
        count[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, count)
    return count


def test_the_first_policys_block_traces_to_the_primitives_it_had_before():
    """The block of the grouped-query policy at the audit's toy size, traced on this tree, against the count of
    each primitive the same trace gave on the tree before the second policy came (commit dd415a6; recorded with
    this function): new layer kinds, a static activation and a values' head size of its own changed nothing of it."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block(TOY_OVERRIDES, 2, ferry_episodes=True)
    params = jax.eval_shape(lambda: lm.init_params(model, jax.random.PRNGKey(0)))
    env_state, obs = jax.eval_shape(benv.reset, jax.random.PRNGKey(1))
    S = jax.ShapeDtypeStruct
    args = (params, jax.eval_shape(tx.init, params), env_state, obs, S((2,), jnp.float32), S((2,), jnp.int32),
            S((1, 2), jnp.uint32), S((2,), jnp.uint32), S((), jnp.float32), S((), jnp.float32), jenv.default_params(),
            S((), jnp.int32))
    got = _primitives(jax.make_jaxpr(block)(*args).jaxpr, collections.Counter())
    with open(os.path.join(HERE, "ppo_anakin_lm_block_primitives.json")) as f:
        before = json.load(f)
    assert dict(got) == before


def test_the_new_policys_block_is_registered_with_graft_audit_and_passes():
    from sheeprl_tpu.analysis.audit import run_audit
    from sheeprl_tpu.analysis.programs import AuditMesh, registered_names
    from sheeprl_tpu.parallel.comm import get_grad_reduce_dtype, set_grad_reduce_dtype

    assert {"ppo_anakin_lm.block", "ppo_anakin_lm.block_latent"} <= set(registered_names())
    mesh, before = AuditMesh(devices=2), get_grad_reduce_dtype()
    set_grad_reduce_dtype(mesh.wire_dtype, fresh_run=True)  # as the audit's CLI does: gradients cross dp in bfloat16
    try:
        findings, measurements = run_audit(mesh, select=["ppo_anakin_lm.block_latent"], manifest=None)
    finally:
        set_grad_reduce_dtype("float32" if before is None else "bfloat16", fresh_run=True)
    assert findings == [] and set(measurements) == {"ppo_anakin_lm.block_latent"}
    print(json.dumps(measurements))
