"""`exp=ppo_anakin_lm_lfm2` at toy widths through the CLI's entry point: the
fourth language-model policy (gated short-convolution layers beside a
grouped-query attention layer with q/k norms, a sigmoid router with a
selection bias after the mixer and no shared expert, a leading dense layer, a
head tied to the embedding) on the same main, host loop, block cache,
recorder and checkpointing as the other three; its convolution scopes and its
cache counter; the bias left bit-identical by the update while every trained
leaf, the tied matrix among them, moved; the third policy's block traced to
the primitives it had before the fourth came; and the audit program."""

import collections
import json
import os

import numpy as np

from sheeprl_tpu.algos.ppo.ppo_anakin_lm import PROGRAM_NAME, TOY_OVERRIDES_AFMOE, TOY_OVERRIDES_LFM2
from sheeprl_tpu.cli import run
from sheeprl_tpu.utils import profiler
from tests.test_algos.test_ppo_anakin_lm_latent import _build_block, _primitives

HERE = os.path.dirname(os.path.abspath(__file__))


def test_two_iterations_through_the_same_main_with_the_convolution_scopes_and_the_counters(tmp_path):
    profiler.reset()
    run([*TOY_OVERRIDES_LFM2, "env.num_envs=2", "algo.per_rank_batch_size=1", "fabric.devices=1", "metric.log_level=0",
         "checkpoint.save_last=True", f"log_root={tmp_path}/logs", "algo.total_steps=32"])
    assert f"{PROGRAM_NAME}/1" in profiler.programs()
    iters = [s["counters"] for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    assert [c["iter_num"] for c in iters] == [1, 2]
    for c in iters:
        # 4 routed layers x top 2 x (2 prompts of 24 + 2 gradient steps' 32 positions): every assignment could be moved
        assert c["moe_bias_movable"] == 4 * 2 * (2 * 24 + 2 * 32) and 0 <= c["moe_bias_moved"] <= c["moe_bias_movable"]
        # four convolution states of 3 gated inputs of 64 and one full buffer of 32 slots, keys and values of 2 heads
        # x 16, 2 sequences, float32
        assert c["rollout_cache_bytes"] == 2 * (4 * 3 * 64 + 32 * 2 * (2 * 16)) * 4
    table = profiler.scope_table(f"{PROGRAM_NAME}/1")
    outers = {v["outer"] for v in table.values()}
    # the gates and taps lie inside the convolution's region and count to it: never outermost; no window, no
    # latent attention, no output gate, no shared expert (the dense layer's feed-forward keeps `lm.ffn_shared`)
    others = {"env.token", "lm.attn_window", "lm.attn_mla", "lm.attn_gate", "lm.conv_mix"}
    assert outers == (set(profiler.LM_BLOCK_REGIONS) - others) | {None}
    mix = [v for v in table.values() if v["scope"] == "lm.conv_mix"]
    assert mix and {v["outer"] for v in mix} == {"lm.conv", "rollout.prefill", "rollout.decode"}
    assert any(v["backward"] for v in mix)
    backward = {v["outer"] for v in table.values() if v["backward"]}
    assert {"lm.embed", "lm.conv", "lm.attn_global", "lm.moe", "lm.ffn_shared", "lm.head_loss"} <= backward


def _toy_block():
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block([*TOY_OVERRIDES_LFM2, "algo.optimizer.lr=1e-2"], 3, ferry_episodes=False)

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
        else:  # the convolutions' projections and taps, the q/k norms and the tied embedding among them
            assert float(np.abs(np.asarray(after) - np.asarray(before[path])).max()) > 0, name
    assert seen == 4 and "['head']" not in {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)}


def test_the_third_policys_block_traces_to_the_primitives_it_had_before():
    """The gated grouped-query policy's block at the audit's toy size, traced on this tree, against the count of
    each primitive the same trace gave on the tree before the fourth policy came (commit c39c593; recorded with
    this function): the convolution kind, the tied head, the router's epsilon as a static and the shared experts
    made optional changed nothing of it. The first two policies' are `test_ppo_anakin_lm_latent.py`'s and
    `test_ppo_anakin_lm_afmoe.py`'s."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block(TOY_OVERRIDES_AFMOE, 2, ferry_episodes=True)
    params = jax.eval_shape(lambda: lm.init_params(model, jax.random.PRNGKey(0)))
    env_state, obs = jax.eval_shape(benv.reset, jax.random.PRNGKey(1))
    S = jax.ShapeDtypeStruct
    args = (params, jax.eval_shape(tx.init, params), env_state, obs, S((2,), jnp.float32), S((2,), jnp.int32),
            S((1, 2), jnp.uint32), S((2,), jnp.uint32), S((), jnp.float32), S((), jnp.float32), jenv.default_params(),
            S((), jnp.int32))
    got = _primitives(jax.make_jaxpr(block)(*args).jaxpr, collections.Counter())
    with open(os.path.join(HERE, "ppo_anakin_lm_afmoe_block_primitives.json")) as f:
        before = json.load(f)
    assert dict(got) == before


def test_the_fourth_policys_block_is_registered_with_graft_audit_and_passes():
    from sheeprl_tpu.analysis.audit import run_audit
    from sheeprl_tpu.analysis.programs import AuditMesh, registered_names
    from sheeprl_tpu.parallel.comm import get_grad_reduce_dtype, set_grad_reduce_dtype

    assert {"ppo_anakin_lm.block", "ppo_anakin_lm.block_latent", "ppo_anakin_lm.block_afmoe",
            "ppo_anakin_lm.block_lfm2"} <= set(registered_names())
    mesh, before = AuditMesh(devices=2), get_grad_reduce_dtype()
    set_grad_reduce_dtype(mesh.wire_dtype, fresh_run=True)  # as the audit's CLI does: gradients cross dp in bfloat16
    try:
        findings, measurements = run_audit(mesh, select=["ppo_anakin_lm.block_lfm2"], manifest=None)
    finally:
        set_grad_reduce_dtype("float32" if before is None else "bfloat16", fresh_run=True)
    assert findings == [] and set(measurements) == {"ppo_anakin_lm.block_lfm2"}
