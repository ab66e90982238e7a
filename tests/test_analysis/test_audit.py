"""graft-audit: planted-hazard fixtures (each must FAIL with the right rule
id), the PR 8 sharding-canonicalization regression, budget-manifest
semantics, and the repo-tree-clean gate over the real program registry."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from sheeprl_tpu.analysis.audit import (
    AUDIT_RULES,
    audit_program,
    sharding_cache_fingerprint,
    sharding_fingerprint,
)
from sheeprl_tpu.analysis.budgets import check_budgets, manifest_from_measurements
from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def mesh():
    return AuditMesh(devices=2).build()


def rules_of(findings):
    return sorted({f.rule for f in findings})


# --------------------------------------------------------------------------- #
# planted hazards — one per rule, each failing with ITS id
# --------------------------------------------------------------------------- #


def test_planted_unaliased_donation_fails_aud001(mesh):
    # y is donated but no output matches its shape/dtype -> XLA cannot alias
    def f(x, y):
        return x * 2.0, jnp.float32(y.sum())

    prog = AuditProgram(
        name="planted.donation",
        fn=jax.jit(f, donate_argnums=(0, 1)),
        args=(jnp.zeros((8, 4), jnp.float32), jnp.ones((3,), jnp.float32)),
        donate_argnums=(0, 1),
        donation_slack_bytes=0,
        check_input_shardings=False,
    )
    findings, _ = audit_program(prog)
    assert "AUD001" in rules_of(findings)


def test_planted_resharded_feedback_output_fails_aud002(mesh):
    # env-carried output declared P("dp") but the program RESHARDS it to
    # replicated (pinned, so the pin check passes — the drift check fires)
    def body(x):
        return jax.lax.all_gather(x, "dp", tiled=True)

    sm = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=False)
    fn = jax.jit(sm, out_shardings=NamedSharding(mesh, P()))
    prog = AuditProgram(
        name="planted.resharded",
        fn=fn,
        args=(jax.ShapeDtypeStruct((8, 4), jnp.float32, sharding=NamedSharding(mesh, P("dp"))),),
        out_decl={0: P("dp")},  # the REGISTERED declaration the program violates
        mesh=mesh,
    )
    findings, _ = audit_program(prog)
    assert "AUD002" in rules_of(findings)
    assert any("drift" in f.message for f in findings)


def test_planted_f64_leak_fails_aud003(mesh):
    with jax.enable_x64(True):
        fn = jax.jit(lambda x: jnp.asarray(x, jnp.float64) * np.float64(2.0))
        prog = AuditProgram(
            name="planted.f64",
            fn=fn,
            args=(jax.ShapeDtypeStruct((16,), jnp.float64),),
            check_input_shardings=False,
        )
        findings, _ = audit_program(prog)
    assert "AUD003" in rules_of(findings)
    assert any("f64" in f.message for f in findings)


def test_planted_f32_collective_under_bf16_policy_fails_aud003(mesh):
    # a gradient-sized f32 all-reduce under a declared bfloat16 wire policy
    def body(g):
        return jax.lax.pmean(g, "dp")

    sm = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    prog = AuditProgram(
        name="planted.f32wire",
        fn=jax.jit(sm),
        args=(jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=NamedSharding(mesh, P())),),
        mesh=mesh,
        wire_dtype="bfloat16",
        check_input_shardings=False,
    )
    findings, _ = audit_program(prog)
    assert "AUD003" in rules_of(findings)
    assert any("bfloat16 wire policy" in f.message for f in findings)


def test_planted_oversized_baked_constant_fails_aud004(mesh):
    # weights closed over (not passed as args) fold into the executable —
    # exactly what breaks graft-serve hot swap
    baked = jnp.asarray(np.random.default_rng(0).normal(size=(128, 128)), jnp.float32)

    prog = AuditProgram(
        name="planted.constant",
        fn=jax.jit(lambda x: x @ baked),
        args=(jax.ShapeDtypeStruct((4, 128), jnp.float32),),
        constant_budget=16 * 1024,  # 64 KiB constant vs 16 KiB budget
        check_input_shardings=False,
    )
    findings, _ = audit_program(prog)
    assert "AUD004" in rules_of(findings)
    assert any("baked into the executable" in f.message for f in findings)


def test_broken_program_reports_aud000_not_crash(mesh):
    prog = AuditProgram(
        name="planted.broken",
        fn=jax.jit(lambda x: x.undefined_attr),
        args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
    )
    findings, meas = audit_program(prog)
    assert rules_of(findings) == ["AUD000"]
    assert meas == {}


# --------------------------------------------------------------------------- #
# the PR 8 regression: equivalent-but-differently-keyed canonicalization
# --------------------------------------------------------------------------- #


def _anakin_shaped_program(mesh, pinned: bool):
    """The bug shape PR 8 found in the fused Anakin block: a donated,
    env-carried P(None, 'dp') output fed back into the next dispatch, with
    the placement left to jit inference (pinned=False) or pinned to the
    driver's staging sharding (the fix, pinned=True)."""

    def body(env, params):
        env = env + jax.lax.pmean(params.sum(), "dp")
        return env, params.sum()

    sm = shard_map(
        body, mesh=mesh, in_specs=(P(None, "dp"), P()), out_specs=(P(None, "dp"), P()),
        check_vma=False,
    )
    env_out = NamedSharding(mesh, P(None, "dp"))
    if pinned:
        fn = jax.jit(sm, donate_argnums=(0,), out_shardings=(env_out, NamedSharding(mesh, P())))
    else:
        fn = jax.jit(sm, donate_argnums=(0,))
    return AuditProgram(
        name="pr8.block",
        fn=fn,
        args=(
            jax.ShapeDtypeStruct((4, 8), jnp.float32, sharding=env_out),
            jax.ShapeDtypeStruct((16,), jnp.float32, sharding=NamedSharding(mesh, P())),
        ),
        donate_argnums=(0,),
        feedback_outputs=(0,),
        out_decl={0: P(None, "dp")},
        mesh=mesh,
    )


def test_pr8_unpinned_canonicalization_class_caught_at_audit_time(mesh):
    """The regression test the acceptance criteria names: the PR 8 bug —
    jit canonicalizing a shard_map's P(None, 'dp') outputs to an EQUIVALENT
    placement with a different C++ jit-cache key, silently recompiling the
    whole program on call 2 — would now be caught at audit time, before any
    steady-state test runs."""
    findings, _ = audit_program(_anakin_shaped_program(mesh, pinned=False))
    assert "AUD002" in rules_of(findings)
    assert any("PR 8" in f.message and "fed back" in f.message for f in findings)


def test_pr8_pinned_fix_shape_passes(mesh):
    findings, _ = audit_program(_anakin_shaped_program(mesh, pinned=True))
    assert findings == []


def test_sharding_fingerprint_normalizes_equivalent_placements(mesh):
    """Two avals-equal programs with distinct cache keys: the NORMALIZED
    fingerprint maps two spellings of one placement (a one-device
    NamedSharding and the SingleDeviceSharding of that device) to the same
    identity (so drift checks compare placement, not spelling), while the
    CACHE-KEY fingerprint keeps them distinct (the PR 8 gap)."""
    device = mesh.devices.flat[0]
    named = NamedSharding(jax.sharding.Mesh(np.array([device]), ("dp",)), P())
    single = jax.sharding.SingleDeviceSharding(device)
    assert named.is_equivalent_to(single, 2)
    assert sharding_fingerprint(named, 2) == sharding_fingerprint(single, 2)
    assert sharding_cache_fingerprint(named, 2) != sharding_cache_fingerprint(single, 2)


# --------------------------------------------------------------------------- #
# budget manifest semantics (AUD005)
# --------------------------------------------------------------------------- #


def _meas(hbm=1000, coll=500, exe=2000):
    return {
        "peak_hbm_bytes": hbm,
        "collective_bytes": {"dp": coll},
        "executable_bytes": exe,
    }


def test_budget_within_tolerance_passes():
    manifest = manifest_from_measurements({"p": _meas()}, "dp=2", tolerance=0.25)
    assert check_budgets({"p": _meas(hbm=1200)}, manifest) == []


def test_budget_breach_fails_each_metric():
    manifest = manifest_from_measurements({"p": _meas()}, "dp=2", tolerance=0.25)
    for bad in (_meas(hbm=2000), _meas(coll=1000), _meas(exe=4000)):
        violations = check_budgets({"p": bad}, manifest)
        assert len(violations) == 1 and violations[0][0] == "p"


def test_new_program_without_entry_fails():
    manifest = manifest_from_measurements({"p": _meas()}, "dp=2")
    violations = check_budgets({"p": _meas(), "new_hot_path": _meas()}, manifest)
    assert any(name == "new_hot_path" and "no budget-manifest entry" in msg for name, msg in violations)


def test_stale_manifest_entry_fails():
    manifest = manifest_from_measurements({"p": _meas(), "removed": _meas()}, "dp=2")
    violations = check_budgets({"p": _meas()}, manifest, audited=["p"], all_registered=["p"])
    assert any(name == "removed" and "stale" in msg for name, msg in violations)


def test_new_collective_axis_without_budget_fails():
    manifest = manifest_from_measurements({"p": _meas()}, "dp=2")
    m = _meas()
    m["collective_bytes"]["fsdp"] = 4096
    violations = check_budgets({"p": m}, manifest)
    assert any("mesh axis 'fsdp'" in msg for _, msg in violations)


# --------------------------------------------------------------------------- #
# the repo-tree-clean gate: every registered hot path lowers green and the
# checked-in manifest covers all of it (mirrors graft-lint's clean gate)
# --------------------------------------------------------------------------- #


def _cli(args, timeout=560):
    env = {**os.environ, "PYTHONPATH": REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    return subprocess.run(
        [sys.executable, "-m", "sheeprl_tpu.analysis", "audit", *args],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=timeout,
    )


def test_audit_cli_repo_tree_clean_gate():
    """`python -m sheeprl_tpu.analysis audit` runs green over ALL registered
    hot paths on the CPU sandbox (abstract lowering, no execution), with the
    committed budget manifest covering every program."""
    r = _cli(["--format=json"])
    assert r.returncode == 0, f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-2000:]}"
    payload = json.loads(r.stdout)
    assert payload["findings"] == []
    assert payload["budgets_checked"] is True
    measured = set(payload["measurements"])
    # the committed manifest and the live registry must agree exactly
    with open(os.path.join(REPO_ROOT, ".graft-audit-budgets.json")) as fh:
        manifest = json.load(fh)
    assert set(manifest["programs"]) == measured
    # the tracecheck hot-path inventory the ISSUE names is all present
    for expected in (
        "ppo.train_step", "ppo.gae", "ppo.rollout_step", "ppo_anakin.block",
        "ppo_anakin_pop.block", "ppo_anakin_pop.block[pbt]",
        "sac.train_step", "sac.resident_step", "sac.rollout_step",
        "ppo_sebulba.train_step", "ppo_sebulba.gae", "ppo_sebulba.act", "ppo_sebulba.traj",
        "sac_sebulba.train_step", "sac_sebulba.act", "sac_sebulba.append",
        "dreamer_v3.burst_step",
        "dreamer_sebulba.train_step", "dreamer_sebulba.act", "dreamer_sebulba.append",
        "serve.bucket[1].greedy", "serve.bucket[8].greedy", "serve.bucket[8].sample",
    ):
        assert expected in measured, f"registered hot path {expected} missing from the audit"


def test_audit_cli_select_and_list_programs():
    r = _cli(["--list-programs"], timeout=120)
    assert r.returncode == 0
    assert "ppo.train_step" in r.stdout
    # a selected slice runs only the matching programs and skips the
    # stale-entry check (it cannot see the whole inventory)
    r2 = _cli(["--select", "ppo.gae", "--format=json"], timeout=300)
    assert r2.returncode == 0, r2.stderr[-2000:]
    payload = json.loads(r2.stdout)
    assert list(payload["measurements"]) == ["ppo.gae"]


def test_audit_cli_select_serve_bucket_literal_and_no_match():
    # `[8]` must match LITERALLY (star-only wildcards — a fnmatch char class
    # would silently select nothing for exactly the serve programs)
    r = _cli(["--select", "serve.bucket[8].greedy", "--format=json"], timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert list(json.loads(r.stdout)["measurements"]) == ["serve.bucket[8].greedy"]
    # a selection matching nothing is a USAGE error, never a green gate
    r2 = _cli(["--select", "ppo.gea"], timeout=120)
    assert r2.returncode == 2
    assert "matched no registered program" in r2.stderr


def test_audit_cli_selected_rebaseline_merges_manifest(tmp_path):
    # a --select re-baseline must keep every unselected program's row
    budgets = tmp_path / "budgets.json"
    seed = {
        "version": 1,
        "mesh": "dp=2",
        "tolerance": 0.25,
        "programs": {"untouched.program": {"peak_hbm_bytes": 1, "collective_bytes": {}, "executable_bytes": 1}},
    }
    budgets.write_text(json.dumps(seed))
    r = _cli(
        ["--select", "ppo.gae", "--write-budgets", "--budgets", str(budgets)], timeout=300
    )
    assert r.returncode == 0, r.stderr[-2000:]
    merged = json.loads(budgets.read_text())
    assert "ppo.gae" in merged["programs"]
    assert "untouched.program" in merged["programs"]


def test_audit_rules_catalog_documented():
    assert set(AUDIT_RULES) == {"AUD000", "AUD001", "AUD002", "AUD003", "AUD004", "AUD005"}
