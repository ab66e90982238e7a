"""graft-jit tier tests: planted-hazard fire/quiet pairs for every GJ rule
(incl. scan-carry key threading, vmap'd key axes staying quiet, np.* on
host-only values staying quiet), interprocedural tracedness propagation,
suppression + stale-suppression semantics, CLI-contract checks, and the
repo-tree-clean gates (the shipped baseline is EMPTY by policy — real
findings get fixed, suppressions carry inline justifications)."""

import json
import textwrap
from pathlib import Path

import pytest

from sheeprl_tpu.analysis.__main__ import main as analysis_main
from sheeprl_tpu.analysis.jit import (
    JIT_RULES,
    analyze_jit_sources,
    analyze_source_jit,
)

REPO_ROOT = Path(__file__).parents[2]


def rules_of(findings):
    return [f.rule for f in findings]


def src(code: str) -> str:
    return textwrap.dedent(code)


# --------------------------------------------------------------------------- #
# GJ001 — PRNG key dataflow
# --------------------------------------------------------------------------- #


def test_gj001_key_reuse_fires():
    code = src(
        """
        import jax

        @jax.jit
        def step(key, x):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ001"]
    assert "already spent" in findings[0].message


def test_gj001_aliased_reuse_fires():
    # value numbering: an alias shares the key id, so spending the alias
    # after the original is the same reuse graft-lint's name-based GL001
    # cannot see
    code = src(
        """
        import jax

        @jax.jit
        def step(key):
            k2 = key
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(k2, (4,))
            return a + b
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ001"]


def test_gj001_split_then_consume_quiet():
    code = src(
        """
        import jax

        @jax.jit
        def step(key):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (4,))
            key, sub2 = jax.random.split(key)
            b = jax.random.uniform(sub2, (4,))
            return a + b
        """
    )
    assert analyze_source_jit(code) == []


def test_gj001_fold_in_derivation_quiet():
    # fold_in DERIVES a child stream, it does not spend the parent
    code = src(
        """
        import jax

        @jax.jit
        def step(key, n):
            a = jax.random.normal(jax.random.fold_in(key, 0), (4,))
            sub = jax.random.fold_in(key, 1)
            b = jax.random.uniform(sub, (4,))
            return a + b
        """
    )
    assert analyze_source_jit(code) == []


def test_gj001_discarded_split_fires():
    code = src(
        """
        import jax

        @jax.jit
        def step(key):
            jax.random.split(key)
            return key
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ001"]
    assert "discarded" in findings[0].message


def test_gj001_burn_key_idiom_quiet():
    # `rng, _ = split(rng)` deliberately advances the stream — the split
    # result IS bound; only a wholly-discarded split fires
    code = src(
        """
        import jax

        @jax.jit
        def step(rng):
            rng, _ = jax.random.split(rng)
            return jax.random.normal(rng, (4,))
        """
    )
    assert analyze_source_jit(code) == []


def test_gj001_scan_carry_stale_fires():
    code = src(
        """
        import jax
        from jax import lax

        def body(carry, x):
            key, acc = carry[0], carry[1]
            n = jax.random.normal(key, (2,))
            return (key, acc + n), n

        def run(key, xs):
            out, _ = lax.scan(body, (key, 0.0), xs)
            return out
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ001"]
    assert "carry" in findings[0].message and findings[0].function == "body"


def test_gj001_scan_carry_threaded_quiet():
    code = src(
        """
        import jax
        from jax import lax

        def body(carry, x):
            key, acc = carry
            key, sub = jax.random.split(key)
            n = jax.random.normal(sub, (2,))
            return (key, acc + n), n

        def run(key, xs):
            out, _ = lax.scan(body, (key, 0.0), xs)
            return out
        """
    )
    assert analyze_source_jit(code) == []


def test_gj001_fori_loop_carry_stale_fires():
    # fori_loop's body is (i, carry) — the carry is parameter 1
    code = src(
        """
        import jax
        from jax import lax

        def body(i, key):
            x = jax.random.normal(key, (2,))
            return key

        def run(key):
            return lax.fori_loop(0, 4, body, key)
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ001"]


def test_gj001_const_key_in_traced_fires_host_quiet():
    code = src(
        """
        import jax

        @jax.jit
        def traced(x):
            k = jax.random.PRNGKey(0)
            return jax.random.normal(k, x.shape)

        def host_seeding(cfg):
            return jax.random.PRNGKey(42)
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ001"]
    assert findings[0].function == "traced"


def test_gj001_vmapped_key_axis_quiet():
    # a per-env key function under vmap with proper splitting stays quiet
    code = src(
        """
        import jax

        def per_env(key, obs):
            key, sub = jax.random.split(key)
            a = jax.random.categorical(sub, obs)
            return key, a

        batched = jax.vmap(per_env)
        """
    )
    assert analyze_source_jit(code) == []


# --------------------------------------------------------------------------- #
# GJ002 — host sync inside traced code
# --------------------------------------------------------------------------- #


def test_gj002_item_and_casts_fire():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            a = x.item()
            b = float(x)
            c = int(x)
            return a + b + c
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ002", "GJ002", "GJ002"]


def test_gj002_numpy_on_tracer_fires():
    code = src(
        """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return np.mean(x)
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ002"]
    assert "np.mean" in findings[0].message


def test_gj002_numpy_on_host_values_quiet():
    # np.* on concrete host values — module scope, host functions, and
    # trace-time constants inside a traced fn — is legal
    code = src(
        """
        import jax
        import numpy as np

        TABLE = np.arange(10)

        def host_stats(path):
            return np.mean(np.arange(100))

        @jax.jit
        def step(x):
            scale = np.float32(2.0)
            return x * scale
        """
    )
    assert analyze_source_jit(code) == []


def test_gj002_device_get_and_print_fire():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            y = jax.device_get(x)
            print(x)
            return y
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ002", "GJ002"]


def test_gj002_print_of_static_quiet():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            print("tracing step")
            return x + 1
        """
    )
    assert analyze_source_jit(code) == []


# --------------------------------------------------------------------------- #
# interprocedural tracedness (the corpus model)
# --------------------------------------------------------------------------- #


def test_cross_module_taint_propagates():
    # a helper in another module called WITH a traced argument is analyzed
    # as traced — the finding lands in the helper's file
    mod_a = src(
        """
        import jax
        from pkg import helpers

        @jax.jit
        def step(x):
            return helpers.loss(x)
        """
    )
    mod_b = src(
        """
        import numpy as np

        def loss(x):
            return np.mean(x)
        """
    )
    findings = analyze_jit_sources([(mod_a, "pkg/a.py"), (mod_b, "pkg/helpers.py")])
    assert rules_of(findings) == ["GJ002"]
    assert findings[0].path == "pkg/helpers.py"


def test_static_only_call_does_not_propagate():
    # a helper called only with STATIC arguments runs on concrete host
    # values at trace time — np.* there is legal and must stay quiet
    mod_a = src(
        """
        import jax
        from pkg import helpers

        @jax.jit
        def step(x, cfg):
            scale = helpers.make_scale(cfg)
            return x * scale
        """
    )
    mod_b = src(
        """
        import numpy as np

        def make_scale(cfg):
            return np.float32(np.mean([1.0, 2.0]))
        """
    )
    assert analyze_jit_sources([(mod_a, "pkg/a.py"), (mod_b, "pkg/helpers.py")]) == []


def test_self_method_propagation():
    code = src(
        """
        import jax
        import numpy as np

        class Agent:
            def act(self, obs):
                return self._postprocess(obs)

            def _postprocess(self, obs):
                return np.clip(obs, 0, 1)

        def make(agent):
            return jax.jit(agent.act)

        step = jax.vmap(Agent().act)
        """
    )
    # `Agent().act` / `agent.act` are attribute refs the corpus can't root
    # conservatively — but `self._postprocess` from a traced method would
    # propagate. Make `act` a root through a resolvable path instead:
    code2 = src(
        """
        import jax
        import numpy as np
        from jax import lax

        class Agent:
            def body(self, carry, x):
                y = self.helper(carry)
                return y, y

            def helper(self, v):
                return np.tanh(v)

        def run(agent, xs, v0):
            return lax.scan(agent.body, v0, xs)
        """
    )
    # agent.body is an attribute ref -> unresolvable -> conservative quiet
    assert analyze_source_jit(code2) == []
    code3 = src(
        """
        import jax
        import numpy as np

        class Agent:
            @jax.jit
            def act(self, obs):
                return self.helper(obs)

            def helper(self, obs):
                return np.tanh(obs)
        """
    )
    findings = analyze_source_jit(code3)
    assert rules_of(findings) == ["GJ002"]
    assert findings[0].function == "Agent.helper"


def test_unresolvable_reference_never_guesses():
    code = src(
        """
        import jax

        @jax.jit
        def step(x, fn):
            return fn(x)
        """
    )
    assert analyze_source_jit(code) == []


# --------------------------------------------------------------------------- #
# GJ003 — Python control flow on tracers
# --------------------------------------------------------------------------- #


def test_gj003_if_while_assert_fire():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                x = x + 1
            while x < 10:
                x = x * 2
            assert x > 0
            return x
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ003", "GJ003", "GJ003"]


def test_gj003_static_tests_quiet():
    code = src(
        """
        import jax

        @jax.jit
        def step(x, mask=None):
            if mask is None:
                return x
            if isinstance(mask, tuple):
                return x
            y = x + 1
            if len(y.shape) == 2:
                y = y[None]
            return y
        """
    )
    assert analyze_source_jit(code) == []


def test_gj003_host_code_quiet():
    code = src(
        """
        def host_loop(xs):
            out = 0
            for x in xs:
                if x > 0:
                    out += x
            return out
        """
    )
    assert analyze_source_jit(code) == []


# --------------------------------------------------------------------------- #
# GJ004 — constant baking
# --------------------------------------------------------------------------- #


def test_gj004_big_module_constant_fires_small_quiet():
    code = src(
        """
        import jax
        import numpy as np

        TABLE = np.zeros((1024, 1024))
        SMALL = np.zeros((8,))

        @jax.jit
        def step(x):
            return x + TABLE + SMALL
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ004"]
    assert "'TABLE'" in findings[0].message and "MiB" in findings[0].message


def test_gj004_factory_closure_constant_fires():
    # the binding lives in the enclosing factory frame; the nested traced
    # function closes over it
    code = src(
        """
        import jax
        import jax.numpy as jnp

        def make_step():
            table = jnp.ones((512, 512))

            @jax.jit
            def step(x):
                return x + table

            return step
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ004"]
    assert findings[0].function == "make_step.step"


def test_gj004_unknown_size_conservative_quiet():
    # np.zeros(shape) with a dynamic shape: size not statically computable,
    # so no guessed finding
    code = src(
        """
        import jax
        import numpy as np

        def make(shape):
            table = np.zeros(shape)

            @jax.jit
            def step(x):
                return x + table

            return step
        """
    )
    assert analyze_source_jit(code) == []


def test_gj004_jit_in_loop_fires_outside_quiet():
    code = src(
        """
        import jax

        def retrace(xs):
            for i in range(4):
                f = jax.jit(lambda x: x + i)
                xs = f(xs)
            return xs

        def fine(xs):
            f = jax.jit(lambda x: x + 1)
            for i in range(4):
                xs = f(xs)
            return xs
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ004"]
    assert findings[0].function == "retrace"


# --------------------------------------------------------------------------- #
# GJ005 — retrace hazards at static arguments
# --------------------------------------------------------------------------- #


def test_gj005_unhashable_static_literal_fires():
    code = src(
        """
        import jax

        g = jax.jit(lambda x, sizes: x, static_argnums=(1,))

        def call(x):
            return g(x, [1, 2, 3])
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ005"]
    assert "unhashable" in findings[0].message


def test_gj005_loop_varying_static_fires_constant_quiet():
    code = src(
        """
        import jax

        g = jax.jit(lambda x, n: x, static_argnums=(1,))

        def varying(x):
            for n in range(4):
                x = g(x, n)
            return x

        def constant(x):
            for _ in range(4):
                x = g(x, 7)
            return x
        """
    )
    findings = analyze_source_jit(code)
    assert rules_of(findings) == ["GJ005"]
    assert "'n'" in findings[0].message


def test_gj005_static_argnames_keyword_fires():
    code = src(
        """
        import jax

        g = jax.jit(lambda x, mode=0: x, static_argnames=("mode",))

        def call(x, modes):
            for m in modes:
                x = g(x, mode=m)
            return x
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ005"]


def test_gj005_decorated_static_argnums():
    code = src(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def g(x, sizes):
            return x

        def call(x):
            return g(x, {1: 2})
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ005"]


# --------------------------------------------------------------------------- #
# suppressions + staleness
# --------------------------------------------------------------------------- #


def test_inline_suppression_absorbs():
    code = src(
        """
        import jax

        @jax.jit
        def step(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))  # graft-jit: disable=GJ001 — test fixture
            return a + b
        """
    )
    assert analyze_source_jit(code) == []


def test_disable_next_line_skips_continuation_comments():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            # graft-jit: disable-next-line=GJ002 — justification line one
            # wrapping onto a second comment line
            return float(x)
        """
    )
    assert analyze_source_jit(code) == []


def test_rule_scoped_suppression_does_not_absorb_others():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            return float(x)  # graft-jit: disable=GJ001
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ002"]


def test_graft_lint_suppression_does_not_absorb_jit():
    # the tiers are parallel: a graft-lint directive says nothing about GJ
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            return float(x)  # graft-lint: disable=GL002
        """
    )
    assert rules_of(analyze_source_jit(code)) == ["GJ002"]


def test_stale_suppression_collected():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            return x + 1  # graft-jit: disable=GJ002 — nothing fires here anymore
        """
    )
    stale = []
    assert analyze_source_jit(code, stale_out=stale) == []
    assert rules_of(stale) == ["SUP001"]
    assert "GJ002 does not fire" in stale[0].message


def test_used_suppression_not_stale():
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            return float(x)  # graft-jit: disable=GJ002 — intentional
        """
    )
    stale = []
    assert analyze_source_jit(code, stale_out=stale) == []
    assert stale == []


def test_unknown_rule_in_directive_always_stale():
    code = src(
        """
        def f():
            return 1  # graft-jit: disable=GX123
        """
    )
    stale = []
    analyze_source_jit(code, stale_out=stale)
    assert rules_of(stale) == ["SUP001"]
    assert "can never fire" in stale[0].message


def test_filtered_out_rule_not_judged_stale():
    # --select excludes GJ002: a GJ002 directive can't be judged this run
    code = src(
        """
        import jax

        @jax.jit
        def step(x):
            return x + 1  # graft-jit: disable=GJ002
        """
    )
    stale = []
    analyze_source_jit(code, select={"GJ001"}, stale_out=stale)
    assert stale == []


def test_stale_detection_in_lint_and_sync_tiers():
    # the machinery is SHARED: the same staleness semantics in every tier
    from sheeprl_tpu.analysis.lint import analyze_source
    from sheeprl_tpu.analysis.sync import analyze_source_sync

    lint_code = src(
        """
        def f():
            return 1  # graft-lint: disable=GL007 — dead justification
        """
    )
    stale = []
    assert analyze_source(lint_code, "f.py", stale_out=stale) == []
    assert rules_of(stale) == ["SUP001"]

    sync_code = src(
        """
        def f():
            return 1  # graft-sync: disable=GS004 — dead justification
        """
    )
    stale = []
    assert analyze_source_sync(sync_code, "f.py", stale_out=stale) == []
    assert rules_of(stale) == ["SUP001"]


# --------------------------------------------------------------------------- #
# CLI contract
# --------------------------------------------------------------------------- #


def test_cli_list_rules(capsys):
    assert analysis_main(["jit", "--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in JIT_RULES:
        assert rule in out


def test_cli_exit_codes_and_formats(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        src(
            """
            import jax

            @jax.jit
            def step(key):
                a = jax.random.normal(key, (4,))
                b = jax.random.uniform(key, (4,))
                return a + b
            """
        )
    )
    assert analysis_main(["jit", str(bad)]) == 1
    capsys.readouterr()
    assert analysis_main(["jit", str(bad), "--format=json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["tool"] == "graft-jit"
    assert payload["rules"] == JIT_RULES
    assert payload["findings"][0]["rule"] == "GJ001"
    assert analysis_main(["jit", str(bad), "--format=github"]) == 1
    gh = capsys.readouterr().out
    assert "::error file=" in gh and "graft-jit GJ001" in gh
    assert analysis_main(["jit", str(bad), "--select", "GJ002"]) == 0
    assert analysis_main(["jit", str(bad), "--select", "GJ999"]) == 2


def test_cli_syntax_error_reported_not_crash(tmp_path, capsys):
    bad = tmp_path / "broken.py"
    bad.write_text("def broken(:\n")
    assert analysis_main(["jit", str(bad)]) == 1
    assert "GJ000" in capsys.readouterr().out


def test_cli_stale_suppression_warns_by_default(tmp_path, capsys):
    f = tmp_path / "stale.py"
    f.write_text("def f():\n    return 1  # graft-jit: disable=GJ002\n")
    assert analysis_main(["jit", str(f)]) == 0
    err = capsys.readouterr().err
    assert "SUP001" in err and "warning" in err


def test_cli_strict_suppressions_promotes_to_findings(tmp_path, capsys):
    f = tmp_path / "stale.py"
    f.write_text("def f():\n    return 1  # graft-jit: disable=GJ002\n")
    assert analysis_main(["jit", str(f), "--strict-suppressions"]) == 1
    out = capsys.readouterr().out
    assert "SUP001" in out


def test_cli_strict_suppressions_lint_and_sync(tmp_path, capsys):
    f = tmp_path / "stale.py"
    f.write_text("def f():\n    return 1  # graft-lint: disable=GL007\n")
    assert analysis_main(["lint", str(f), "--strict-suppressions", "--no-baseline"]) == 1
    capsys.readouterr()
    g = tmp_path / "stale2.py"
    g.write_text("def f():\n    return 1  # graft-sync: disable=GS004\n")
    assert analysis_main(["sync", str(g), "--strict-suppressions"]) == 1


# --------------------------------------------------------------------------- #
# `analysis all` — merged catalog, selection, skip semantics
# --------------------------------------------------------------------------- #


def test_all_list_rules_enumerates_every_tier(capsys):
    assert analysis_main(["all", "--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("GL001", "GJ001", "GS001", "AUD001", "SUP001"):
        assert rule in out
    for tool in ("graft-lint", "graft-jit", "graft-sync", "graft-audit"):
        assert f"{tool}:" in out


def test_all_unknown_select_is_named_exit_2(tmp_path, capsys):
    assert analysis_main(["all", str(tmp_path), "--select", "BOGUS"]) == 2
    err = capsys.readouterr().err
    assert "unknown rule(s): BOGUS" in err and "GJ001" in err


def test_all_select_partitions_tiers(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        src(
            """
            import jax

            @jax.jit
            def step(key):
                a = jax.random.normal(key, (4,))
                b = jax.random.uniform(key, (4,))
                return a + b
            """
        )
    )
    rc = analysis_main(["all", str(bad), "--select", "GJ001"])
    err = capsys.readouterr().err
    assert rc == 1
    assert "lint=skipped" in err and "jit=1" in err
    assert "sync=skipped" in err and "audit=skipped" in err


def test_all_includes_jit_tier(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    rc = analysis_main(["all", str(clean), "--skip-audit"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "lint=0" in err and "jit=0" in err and "sync=0" in err


def test_all_propagates_jit_findings(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        src(
            """
            import jax

            @jax.jit
            def step(x):
                return float(x)
            """
        )
    )
    rc = analysis_main(["all", str(bad), "--skip-audit"])
    capsys.readouterr()
    assert rc == 1


# --------------------------------------------------------------------------- #
# repo-tree gates
# --------------------------------------------------------------------------- #


def test_repo_tree_is_clean():
    """THE shipped-baseline gate: the full CLI run over sheeprl_tpu/ is green
    — every real finding fixed, every suppression inline-justified."""
    rc = analysis_main(["jit", str(REPO_ROOT / "sheeprl_tpu")])
    assert rc == 0


def test_repo_tree_has_no_stale_suppressions():
    """Every `# graft-lint/sync/jit: disable` directive in the shipped tree
    still absorbs a finding — fixed code cannot carry dead justifications."""
    tree = str(REPO_ROOT / "sheeprl_tpu")
    assert analysis_main(["jit", tree, "--strict-suppressions"]) == 0
    assert analysis_main(["sync", tree, "--strict-suppressions"]) == 0
    assert analysis_main(["lint", tree, "--strict-suppressions"]) == 0


def test_repo_tree_corpus_is_nontrivial():
    """Guard against the analyzer rotting into a no-op: the shipped tree must
    keep producing a substantial traced set (roots via decorators, call-args,
    collectives, audit registry; closure via taint propagation)."""
    import os

    from sheeprl_tpu.analysis.jitgraph import Corpus
    from sheeprl_tpu.analysis.lint import iter_python_files

    corpus = Corpus()
    for path in iter_python_files([str(REPO_ROOT / "sheeprl_tpu")]):
        with open(path, "r", encoding="utf-8") as fh:
            corpus.add_source(fh.read(), os.path.relpath(path, REPO_ROOT))
    corpus.finalize()
    traced = corpus.traced_functions()
    assert len(traced) > 100
    propagated = [f for f in traced if f.trace_reason.startswith("called from")]
    assert len(propagated) > 20


def test_injected_bug_is_caught_in_real_tree():
    """End-to-end: a key reuse planted inside a real nested traced function
    (dreamer_v3's gradient step, nested in ``make_train_step``) is found — the
    corpus reaches it through the factory nesting, not just top-level
    decorated functions."""
    import os

    from sheeprl_tpu.analysis.lint import iter_python_files

    sources = []
    for path in iter_python_files([str(REPO_ROOT / "sheeprl_tpu")]):
        with open(path, "r", encoding="utf-8") as fh:
            sources.append((fh.read(), os.path.relpath(path, REPO_ROOT)))
    idx = next(i for i, (_, p) in enumerate(sources) if p.endswith("dreamer_v3/dreamer_v3.py"))
    text, p = sources[idx]
    target = "k_dyn, k_img = jax.random.split(key)"
    assert target in text
    sources[idx] = (
        text.replace(
            target,
            target + "\n        _a = jax.random.normal(k_dyn, (2,)); _b = jax.random.normal(k_dyn, (2,))",
            1,
        ),
        p,
    )
    findings = analyze_jit_sources(sources)
    assert [f.rule for f in findings] == ["GJ001"]
    assert findings[0].path.endswith("dreamer_v3/dreamer_v3.py")
