"""`exp=ppo_anakin_lm_trinity` at toy widths through the CLI's entry point: the
third language-model policy (gated grouped-query attention with q/k norms over
window and full layers, sandwich norms, a sigmoid router with a selection bias
after attention, a shared expert, a leading dense layer) on the same main, host
loop, block cache, recorder and checkpointing as the other two; its bias and
cache counters on a grouped-query layer; the bias left bit-identical by the
update; the other two policies' blocks traced to the primitives they had before
the third came; and the audit program."""

import collections
import json
import os

import numpy as np

from sheeprl_tpu.algos.ppo.ppo_anakin_lm import PROGRAM_NAME, TOY_OVERRIDES_AFMOE, TOY_OVERRIDES_LATENT
from sheeprl_tpu.cli import run
from sheeprl_tpu.utils import profiler
from tests.test_algos.test_ppo_anakin_lm_latent import _build_block, _primitives

HERE = os.path.dirname(os.path.abspath(__file__))


def test_two_iterations_through_the_same_main_with_the_gate_scope_and_the_counters(tmp_path):
    profiler.reset()
    run([*TOY_OVERRIDES_AFMOE, "env.num_envs=2", "algo.per_rank_batch_size=1", "fabric.devices=1", "metric.log_level=0",
         "checkpoint.save_last=True", f"log_root={tmp_path}/logs", "algo.total_steps=32"])
    assert f"{PROGRAM_NAME}/1" in profiler.programs()
    iters = [s["counters"] for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    assert [c["iter_num"] for c in iters] == [1, 2]
    for c in iters:
        # 4 routed layers x top 2 x (2 prompts of 24 + 2 gradient steps' 32 positions): every assignment could be moved
        # (a run from scratch starts with a zero bias, which moves none)
        assert c["moe_bias_movable"] == 4 * 2 * (2 * 24 + 2 * 32) and 0 <= c["moe_bias_moved"] <= c["moe_bias_movable"]
        # four window rings of 8 slots and one full buffer of 32, keys and values of 2 heads x 16, 2 sequences, float32
        assert c["rollout_cache_bytes"] == 2 * (4 * 8 + 32) * 2 * (2 * 16) * 4
    table = profiler.scope_table(f"{PROGRAM_NAME}/1")
    outers = {v["outer"] for v in table.values()}
    # the gate lies inside the attention's regions and counts to them: never outermost
    assert outers == (set(profiler.LM_BLOCK_REGIONS) - {"env.token", "lm.attn_mla", "lm.attn_gate"}) | {None}
    gate = [v for v in table.values() if v["scope"] == "lm.attn_gate"]
    assert gate and {v["outer"] for v in gate} == {"lm.attn_window", "lm.attn_global", "rollout.prefill", "rollout.decode"}
    assert any(v["backward"] for v in gate)
    backward = {v["outer"] for v in table.values() if v["backward"]}
    assert {"lm.embed", "lm.attn_window", "lm.attn_global", "lm.moe", "lm.ffn_shared", "lm.head_loss"} <= backward


def _toy_block():
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block([*TOY_OVERRIDES_AFMOE, "algo.optimizer.lr=1e-2"], 3, ferry_episodes=False)

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
        else:  # the gate, the q/k norms and both output norms of every layer among them
            assert float(np.abs(np.asarray(after) - np.asarray(before[path])).max()) > 0, name
    assert seen == 4


def test_the_second_policys_block_traces_to_the_primitives_it_had_before():
    """The latent-attention policy's block at the audit's toy size, traced on this tree, against the count of each
    primitive the same trace gave on the tree before the third policy came (commit 7cee9e6; recorded with this
    function): layer kinds made independent of the attention kind changed nothing of it. The first policy's is
    `test_ppo_anakin_lm_latent.py`'s."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import decoder_lm as lm

    block, model, jenv, benv, tx = _build_block(TOY_OVERRIDES_LATENT, 2, ferry_episodes=True)
    params = jax.eval_shape(lambda: lm.init_params(model, jax.random.PRNGKey(0)))
    env_state, obs = jax.eval_shape(benv.reset, jax.random.PRNGKey(1))
    S = jax.ShapeDtypeStruct
    args = (params, jax.eval_shape(tx.init, params), env_state, obs, S((2,), jnp.float32), S((2,), jnp.int32),
            S((1, 2), jnp.uint32), S((2,), jnp.uint32), S((), jnp.float32), S((), jnp.float32), jenv.default_params(),
            S((), jnp.int32))
    got = _primitives(jax.make_jaxpr(block)(*args).jaxpr, collections.Counter())
    with open(os.path.join(HERE, "ppo_anakin_lm_latent_block_primitives.json")) as f:
        before = json.load(f)
    assert dict(got) == before


def test_the_third_policys_block_is_registered_with_graft_audit_and_passes():
    from sheeprl_tpu.analysis.audit import run_audit
    from sheeprl_tpu.analysis.programs import AuditMesh, registered_names
    from sheeprl_tpu.parallel.comm import get_grad_reduce_dtype, set_grad_reduce_dtype

    assert {"ppo_anakin_lm.block", "ppo_anakin_lm.block_latent", "ppo_anakin_lm.block_afmoe"} <= set(registered_names())
    mesh, before = AuditMesh(devices=2), get_grad_reduce_dtype()
    set_grad_reduce_dtype(mesh.wire_dtype, fresh_run=True)  # as the audit's CLI does: gradients cross dp in bfloat16
    try:
        findings, measurements = run_audit(mesh, select=["ppo_anakin_lm.block_afmoe"], manifest=None)
    finally:
        set_grad_reduce_dtype("float32" if before is None else "bfloat16", fresh_run=True)
    assert findings == [] and set(measurements) == {"ppo_anakin_lm.block_afmoe"}
