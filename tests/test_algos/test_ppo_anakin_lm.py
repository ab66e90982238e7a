"""`exp=ppo_anakin_lm` at toy widths through the CLI's entry point: the same
main, host loop and block cache as `exp=ppo_anakin`, a decoder policy on the
token MDP; and the block's registration with graft-audit."""

import numpy as np
import pytest

from sheeprl_tpu.algos.ppo.ppo_anakin_lm import PROGRAM_NAME, TOY_OVERRIDES
from sheeprl_tpu.cli import run
from sheeprl_tpu.utils import profiler


@pytest.fixture()
def trace_hygiene():
    """Strict tracecheck, the steady-state transfer guard and tracer-leak
    checking around one run (as tests/test_analysis/conftest.py arms them)."""
    import jax

    from sheeprl_tpu.analysis.tracecheck import tracecheck

    tracecheck.reset()
    tracecheck.configure(mode="strict", transfer_guard=True)
    try:
        with jax.check_tracer_leaks():
            yield tracecheck
    finally:
        tracecheck.configure(mode="warn", transfer_guard=False)
        tracecheck.reset()


def _args(tmp_path, devices, iterations, log_level=0, save_last=False):
    envs = 2 * devices
    return [*TOY_OVERRIDES, f"env.num_envs={envs}", "algo.per_rank_batch_size=1", f"fabric.devices={devices}",
            f"metric.log_level={log_level}", "metric.log_every=16", f"checkpoint.save_last={save_last}",
            f"log_root={tmp_path}/logs", f"algo.total_steps={iterations * envs * 8}"]


@pytest.mark.parametrize("devices", [1, 2])
def test_two_iterations_one_compile_finite_losses(tmp_path, trace_hygiene, devices):
    profiler.reset()
    seen = []
    from sheeprl_tpu.algos.ppo import ppo_anakin

    orig = ppo_anakin._RegisteredBlock.__call__

    def spy(block, *args):
        out = orig(block, *args)
        seen.append({k: np.asarray(v) for k, v in out[-1].items() if k != "rollout"})
        return out

    ppo_anakin._RegisteredBlock.__call__ = spy
    try:
        run(_args(tmp_path, devices, iterations=2))
    finally:
        ppo_anakin._RegisteredBlock.__call__ = orig
    assert trace_hygiene.post_warmup_retraces() == {}
    report = trace_hygiene.report()["ppo_anakin_lm.block"]
    assert report["calls"] == 2 and report["compiles"] == 1  # the block compiled once, the second call fed by the first
    assert len(seen) == 2
    for metrics in seen:
        assert all(np.isfinite(metrics[k]).all() for k in ("pg", "v", "ent", "pg_steps", "grad_norm_steps"))
        assert metrics["pg_steps"].shape == (1, 2) and float(metrics["bad"].sum()) == 0
        assert int(metrics["moe_dropped"].sum()) == 0
        # 2 envs a device x 32 positions x top 2 assignments a layer, about half of them on the 4 of 8 experts held
        per_layer = metrics["moe_local_assignments"][0]
        assert per_layer.shape == (4,) and (per_layer > 0).all() and (per_layer < devices * 2 * 32 * 2).all()
    # the host spans and the registered program the benchmark's readers look for
    assert f"{PROGRAM_NAME}/1" in profiler.programs()
    spans = profiler.snapshot()["spans"]
    iters = [s for s in spans if s["name"] == "iter"]
    assert [s["counters"]["iter_num"] for s in iters] == [1, 2] and iters[0]["counters"]["grad_steps"] == 2
    assert all(s["counters"]["program"] == f"{PROGRAM_NAME}/1" for s in spans if s["name"] == "burst.dispatch")
    table = profiler.scope_table(f"{PROGRAM_NAME}/1")
    outers = {v["outer"] for v in table.values()}
    # every region of the block but env.token, which lies inside rollout.decode and counts to it, and the
    # other policies' scopes (latent attention, shared experts: tests/test_algos/test_ppo_anakin_lm_latent.py; the
    # attention's output gate, never outermost: tests/test_algos/test_ppo_anakin_lm_afmoe.py; the convolution:
    # tests/test_algos/test_ppo_anakin_lm_lfm2.py)
    others = {"env.token", "lm.attn_mla", "lm.ffn_shared", "lm.attn_gate", "lm.conv", "lm.conv_mix"}
    assert outers == (set(profiler.LM_BLOCK_REGIONS) - others) | {None}
    scopes = {v["scope"] for v in table.values()}
    assert {"kernel.moe_grouped_ffn", "kernel.window_attention"} <= scopes
    backward = {v["outer"] for v in table.values() if v["backward"]}
    assert {"lm.embed", "lm.attn_global", "lm.attn_window", "lm.moe", "lm.head_loss"} <= backward
    assert not backward & {"rollout.prefill", "rollout.decode", "ppo.optim"}


def test_the_iter_span_carries_how_often_the_routed_layer_compacted(tmp_path, monkeypatch):
    """A dry run whose sequences are long enough for the share to leave rows out (2 of 8 experts held, 1016 + 8
    positions: 1024 of a call's ~2048 sorted rows moved): the block counts the calls that could compact and those
    that did, and the host loop puts both on the block's `iter` span; the attention's kernel tier on padded blocks
    counts the same."""
    profiler.reset()
    run([*_args(tmp_path, 1, iterations=1), "dry_run=True", "algo.lm.experts_held=2", "env.prompt_len=1016"])
    (span,) = [s for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    # 4 layers x (2 prompts prefilled + 2 gradient steps' forwards); a fresh router sends a quarter to 2 of 8 experts
    assert span["counters"]["moe_compactable_calls"] == 16 and span["counters"]["moe_compact_calls"] == 16
    # at the toy sizes of the other tests every row is moved anyway: nothing could compact, and the span says so
    profiler.reset()
    run([*_args(tmp_path, 1, iterations=1), "dry_run=True"])
    (span,) = [s for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    assert span["counters"]["moe_compactable_calls"] == 0 and span["counters"]["moe_compact_calls"] == 0
    # the kernel tier (interpret mode) on blocks of 128: prefill's 184 positions run at 256, the update's 192 at 256
    from sheeprl_tpu.ops import kernels as K
    from sheeprl_tpu.ops.kernels import attn

    monkeypatch.setattr(attn, "SPLASH_BLOCK", 128)
    profiler.reset()
    with K.use_backend():  # the run's override of the registry ends with the run
        run([*_args(tmp_path, 1, iterations=1), "dry_run=True", "env.prompt_len=184", "ops.kernels.window_attention=pallas"])
    (span,) = [s for s in profiler.snapshot()["spans"] if s["name"] == "iter"]
    assert span["counters"]["moe_compactable_calls"] == 0 and span["counters"]["moe_compact_calls"] == 0


def _toy_block(extra=()):
    """The block at toy widths on one device, with a fresh state for every call (the block donates it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from sheeprl_tpu.algos.ppo import ppo_anakin_lm
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax_envs import BatchedJaxEnv, make_jax_env
    from sheeprl_tpu.models import decoder_lm as lm
    from sheeprl_tpu.optim.builders import build_optimizer

    cfg = compose([*TOY_OVERRIDES, "env.num_envs=3", "algo.per_rank_batch_size=1", *extra])
    model = lm.DecoderConfig.from_config(cfg.algo.lm)
    jenv = make_jax_env(cfg.env.id, vocab_size=model.vocab_held, prompt_len=24, response_len=8)
    policy, benv = ppo_anakin_lm.LMPolicy(model, 24, 8), BatchedJaxEnv(jenv, 3)
    tx = build_optimizer(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm)
    block = ppo_anakin_lm.make_anakin_lm_block(policy, tx, cfg, Mesh(np.array(jax.devices()[:1]), ("dp",)), benv, 3, 1,
                                               ferry_episodes=False, guard=True)

    def call(grad_steps):
        params = lm.init_params(model, jax.random.PRNGKey(0))
        env_state, obs = benv.reset(jax.random.PRNGKey(1))
        return block(params, tx.init(params), env_state, jnp.copy(obs), jnp.zeros(3), jnp.zeros(3, jnp.int32),
                     jax.random.split(jax.random.PRNGKey(2), 1), jax.random.PRNGKey(3), jnp.float32(0.2), jnp.float32(0.01),
                     jenv.default_params(), jnp.int32(grad_steps))

    return call, lambda: lm.init_params(model, jax.random.PRNGKey(0))


def test_the_update_runs_the_gradient_steps_it_is_granted_and_no_more():
    import jax

    call, make_params = _toy_block(["algo.ferry_rollout=True"])
    outs = {g: call(g) for g in (0, 1, 2, 3, 7)}
    steps = {g: np.asarray(out[-1]["pg_steps"])[0] for g, out in outs.items()}
    norms = {g: np.asarray(out[-1]["grad_norm_steps"])[0] for g, out in outs.items()}
    for g in (0, 1, 2, 3):
        assert (norms[g][:g] > 0).all() and not norms[g][g:].any() and not steps[g][g:].any()  # a row a step that ran
        np.testing.assert_array_equal(norms[g][:g], norms[3][:g])  # the same steps, whatever is granted after them
        np.testing.assert_array_equal(outs[g][-1]["rollout"]["tokens"], outs[3][-1]["rollout"]["tokens"])
    np.testing.assert_array_equal(norms[7], norms[3])  # more than the iteration has is all of it
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(make_params())):
        np.testing.assert_array_equal(a, b)  # nothing granted: the parameters are as they were
    moved = [float(np.abs(np.asarray(a) - np.asarray(b)).max()) for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[2][0]))]
    assert max(moved) > 0  # and a second step moves them on
    # mean losses are over the steps that ran
    assert float(outs[1][-1]["pg"][0]) == pytest.approx(float(steps[1][0]), rel=1e-6)
    assert int(np.asarray(outs[3][-1]["moe_dropped"]).sum()) == 0


def test_the_rollout_record_is_returned_only_when_asked_for():
    call, _ = _toy_block()
    assert "rollout" not in call(1)[-1]


def test_checkpoint_and_logging_share_the_classic_layout(tmp_path):
    import glob

    from sheeprl_tpu.utils.utils import compile_stats

    run(_args(tmp_path, 1, iterations=2, log_level=1, save_last=True))
    ckpts = glob.glob(f"{tmp_path}/logs/**/ckpt_32_0.ckpt", recursive=True)
    assert len(ckpts) == 1
    # the registered block's text is its call's executable: asking for it compiles nothing
    compiled_before = compile_stats.snapshot()[0]
    assert len(profiler.program(f"{PROGRAM_NAME}/1").as_text()) > 10_000
    assert compile_stats.snapshot()[0] == compiled_before


def test_the_block_is_registered_with_graft_audit_and_passes():
    from sheeprl_tpu.analysis.audit import run_audit
    from sheeprl_tpu.analysis.programs import AuditMesh, registered_names

    from sheeprl_tpu.parallel.comm import get_grad_reduce_dtype, set_grad_reduce_dtype

    assert "ppo_anakin_lm.block" in registered_names()
    mesh, before = AuditMesh(devices=2), get_grad_reduce_dtype()
    set_grad_reduce_dtype(mesh.wire_dtype, fresh_run=True)  # as the audit's CLI does: gradients cross dp in bfloat16
    try:
        findings, measurements = run_audit(mesh, select=["ppo_anakin_lm.block"], manifest=None)
    finally:
        set_grad_reduce_dtype("float32" if before is None else "bfloat16", fresh_run=True)
    assert findings == [] and set(measurements) == {"ppo_anakin_lm.block"}  # donation, pinned placements, dtypes, constants
