"""Per-rule fixtures: every GL rule must FIRE on its hazard and stay QUIET on
the idiomatic counterpart (the precision bar that keeps the baseline empty)."""

import textwrap

from sheeprl_tpu.analysis.lint import analyze_source


def lint(src):
    return analyze_source(textwrap.dedent(src), path="snippet.py")


def rules_of(findings):
    return sorted(f.rule for f in findings)


# --------------------------------------------------------------------------- #
# GL001 — RNG key reuse
# --------------------------------------------------------------------------- #


def test_gl001_fires_on_double_sample():
    fs = lint(
        """
        import jax

        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """
    )
    assert rules_of(fs) == ["GL001"]


def test_gl001_fires_on_use_after_split():
    fs = lint(
        """
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(key, (3,))
        """
    )
    assert rules_of(fs) == ["GL001"]


def test_gl001_fires_on_reuse_across_loop_iterations():
    fs = lint(
        """
        import jax

        def f(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (3,)))
            return out
        """
    )
    assert "GL001" in rules_of(fs)


def test_gl001_quiet_on_split_and_carry():
    fs = lint(
        """
        import jax

        def f(key, n):
            out = []
            for i in range(n):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (3,)))
            return out
        """
    )
    assert fs == []


def test_gl001_quiet_on_fold_in_derive():
    # fold_in is the sanctioned multi-derive: same base key, distinct data
    fs = lint(
        """
        import jax

        def f(key, n):
            return [jax.random.normal(jax.random.fold_in(key, i), (3,)) for i in range(n)]
        """
    )
    assert fs == []


def test_gl001_quiet_on_exclusive_branches():
    # the `if prioritized:` pattern in sac.make_resident_train_step: one key,
    # two exclusive consumers
    fs = lint(
        """
        import jax

        def f(key, flag):
            if flag:
                x = jax.random.uniform(key, (3,))
            else:
                x = jax.random.normal(key, (3,))
            return x
        """
    )
    assert fs == []


def test_gl001_quiet_when_branch_returns():
    # dreamer_v2.add_exploration_noise: the consuming branch returns, so the
    # later consumption never sees the spent key
    fs = lint(
        """
        import jax

        def f(key, cont):
            if cont:
                return jax.random.normal(key, (3,))
            keys = jax.random.split(key, 4)
            return keys
        """
    )
    assert fs == []


def test_gl001_keyword_key_argument():
    fs = lint(
        """
        import jax

        def f(key):
            a = jax.random.normal(key=key, shape=(3,))
            b = jax.random.normal(key=key, shape=(3,))
            return a + b
        """
    )
    assert rules_of(fs) == ["GL001"]


# --------------------------------------------------------------------------- #
# GL002 — host syncs in jit-reachable code
# --------------------------------------------------------------------------- #


def test_gl002_fires_on_item_inside_jit():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_gl002_fires_on_float_cast_of_traced():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x):
            return float(x.mean())
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_gl002_fires_on_np_asarray_in_scan_body():
    fs = lint(
        """
        import jax
        import numpy as np

        def outer(xs):
            def body(carry, x):
                return carry, np.asarray(x)
            return jax.lax.scan(body, 0, xs)
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_gl002_quiet_on_host_code():
    # .item()/float() outside jit-reachable code is normal host logging
    fs = lint(
        """
        def log_loss(loss):
            return float(loss.mean().item())
        """
    )
    assert fs == []


def test_gl002_quiet_on_static_config_float():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x, cfg_value=None):
            scale = float(3.5)
            return x * scale
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# GL003 — np. on traced values where jnp is required
# --------------------------------------------------------------------------- #


def test_gl003_fires_on_np_op_in_jit():
    fs = lint(
        """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
        """
    )
    assert rules_of(fs) == ["GL003"]


def test_gl003_quiet_on_np_over_static_shape():
    # np on STATIC metadata (tracer .shape is a python tuple) is idiomatic
    fs = lint(
        """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            n = int(np.prod(x.shape))
            return x.reshape(n)
        """
    )
    assert fs == []


def test_gl003_quiet_on_jnp():
    fs = lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.sum(x)
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# GL004 — Python control flow on traced values
# --------------------------------------------------------------------------- #


def test_gl004_fires_on_if_traced():
    fs = lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            s = jnp.sum(x)
            if s > 0:
                return x
            return -x
        """
    )
    assert rules_of(fs) == ["GL004"]


def test_gl004_fires_on_while_traced():
    fs = lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            n = jnp.sum(x)
            while n > 0:
                n = n - 1
            return n
        """
    )
    assert rules_of(fs) == ["GL004"]


def test_gl004_fires_on_for_over_traced_subscript():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(batch):
            total = 0
            for row in batch["obs"]:
                total = total + row
            return total
        """
    )
    assert rules_of(fs) == ["GL004"]


def test_gl004_quiet_on_static_flag_param():
    # `if greedy:` where greedy is an unmodified (static) parameter
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x, greedy):
            if greedy:
                return x
            return -x
        """
    )
    assert fs == []


def test_gl004_quiet_on_static_argnums():
    fs = lint(
        """
        import jax

        def _step(x, greedy, expl):
            if not greedy and expl > 0.0:
                return x * expl
            return x

        step_fn = jax.jit(_step, static_argnums=(1, 2))
        """
    )
    assert fs == []


def test_gl004_quiet_on_config_attribute():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x, actor):
            if actor.is_continuous:
                return x
            return -x
        """
    )
    assert fs == []


def test_gl004_quiet_on_none_and_isinstance_guards():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(x, mask, amount):
            if mask is not None and not isinstance(amount, float):
                return x
            if isinstance(amount, (int, float)) and amount <= 0.0:
                return -x
            return x
        """
    )
    assert fs == []


def test_gl004_quiet_on_zip_unroll():
    # static unrolling over python lists of arrays is idiomatic jax
    fs = lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(dists, keys):
            return [d + k for d, k in zip(dists, keys)]
        """
    )
    assert fs == []


def test_gl004_quiet_on_dict_iteration():
    fs = lint(
        """
        import jax

        @jax.jit
        def f(storage, idx):
            return {k: storage[k][idx] for k in storage}
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# GL005 — read-after-donate
# --------------------------------------------------------------------------- #


def test_gl005_fires_on_read_after_donating_call():
    fs = lint(
        """
        import jax

        def train(step, params, opt, data):
            step_fn = jax.jit(step, donate_argnums=(0, 1))
            new_params, new_opt = step_fn(params, opt, data)
            return params["w"]  # donated buffer!
        """
    )
    assert rules_of(fs) == ["GL005"]


def test_gl005_fires_on_donate_argnames():
    fs = lint(
        """
        import jax

        def train(step, params, opt, data):
            step_fn = jax.jit(step, donate_argnames=("params",))
            new_params = step_fn(data, params=params)
            return params["w"]  # donated by name!
        """
    )
    assert rules_of(fs) == ["GL005"]


def test_gl005_quiet_on_rebind():
    fs = lint(
        """
        import jax

        def train(step, params, opt, data):
            step_fn = jax.jit(step, donate_argnums=(0, 1))
            params, opt = step_fn(params, opt, data)
            return params["w"]  # rebound to the NEW buffers: fine
        """
    )
    assert fs == []


def test_gl005_quiet_without_donation():
    fs = lint(
        """
        import jax

        def train(step, params, opt, data):
            step_fn = jax.jit(step)
            new_params, new_opt = step_fn(params, opt, data)
            return params["w"]
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# GL006 — dict-ordering-sensitive pytrees
# --------------------------------------------------------------------------- #


def test_gl006_fires_on_dictcomp_over_set():
    fs = lint(
        """
        def build(keys_a, keys_b):
            return {k: 0.0 for k in set(keys_a) & set(keys_b)}
        """
    )
    assert rules_of(fs) == ["GL006"]


def test_gl006_fires_on_cross_object_zip():
    fs = lint(
        """
        def build(a, b):
            return dict(zip(a.keys(), b.values()))
        """
    )
    assert rules_of(fs) == ["GL006"]


def test_gl006_quiet_on_sorted_and_same_object():
    fs = lint(
        """
        def build(keys_a, keys_b, a):
            x = {k: 0.0 for k in sorted(set(keys_a) & set(keys_b))}
            y = dict(zip(a.keys(), a.values()))
            return x, y
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# GL007 — PRNGKey in a loop
# --------------------------------------------------------------------------- #


def test_gl007_fires_on_key_in_loop():
    fs = lint(
        """
        import jax

        def f(seed, n):
            out = []
            for i in range(n):
                k = jax.random.PRNGKey(seed + i)
                out.append(jax.random.normal(k, (3,)))
            return out
        """
    )
    assert "GL007" in rules_of(fs)


def test_gl007_quiet_outside_loop():
    fs = lint(
        """
        import jax

        def f(seed):
            key = jax.random.PRNGKey(seed)
            return jax.random.normal(key, (3,))
        """
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# jit-reachability edges
# --------------------------------------------------------------------------- #


def test_reachability_via_decorator_partial():
    fs = lint(
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            return x.sum().item()
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_reachability_via_shard_map_edge():
    # the repo idiom: local fn -> shard_map(...) -> jax.jit
    fs = lint(
        """
        import jax
        from jax import shard_map

        def make(mesh, spec):
            def local_train(x):
                return x.sum().item()

            return jax.jit(shard_map(local_train, mesh=mesh, in_specs=spec, out_specs=spec))
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_reachability_via_call_graph():
    # helper called FROM a jitted function is jit-reachable transitively
    fs = lint(
        """
        import jax

        def helper(x):
            return x.sum().item()

        @jax.jit
        def f(x):
            return helper(x)
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_reachability_via_collective_body():
    # lax.pmean is only legal under a mapped trace: body is trace context
    fs = lint(
        """
        import jax

        def local_train(grads):
            g = jax.lax.pmean(grads, "dp")
            return g.sum().item()
        """
    )
    assert rules_of(fs) == ["GL002"]


def test_unreachable_host_function_stays_quiet():
    fs = lint(
        """
        import numpy as np

        def stage(batch):
            return {k: np.asarray(v) for k, v in batch.items()}
        """
    )
    assert fs == []


def test_scan_body_reachable_without_jit():
    # lax.scan traces its body even outside jit
    fs = lint(
        """
        import jax
        import numpy as np

        def run(xs):
            def body(c, x):
                return c, np.sum(x)
            return jax.lax.scan(body, 0, xs)
        """
    )
    assert rules_of(fs) == ["GL003"]


# --------------------------------------------------------------------------- #
# GL008 — donating jit over sharded shard_map outputs without pinned
# out_shardings (the PR 8 silent-recompile shape)
# --------------------------------------------------------------------------- #


def test_gl008_fires_on_direct_sharded_donating_jit():
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh):
            def body(x, p):
                return x * 2, p

            st = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=(P("dp"), P()))
            return jax.jit(st, donate_argnums=(0,))
        """
    )
    assert rules_of(fs) == ["GL008"]


def test_gl008_fires_through_wrapper_and_conditional_spec():
    # the resident-ring idiom: spec = P(None, "dp") if cond else P(); a
    # wrapper unpacks the shard_map tuple, rebuilds a dict, and returns it
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh, shard_envs):
            spec = P(None, "dp") if shard_envs else P()

            def body(s, b):
                return s, b.sum()

            st = shard_map(body, mesh=mesh, in_specs=(spec, P()), out_specs=(spec, P()))

            def packed(state, blob):
                storage, tot = st(state["storage"], blob)
                new_state = {"storage": storage}
                return new_state, tot

            return jax.jit(packed, donate_argnums=(0,))
        """
    )
    assert rules_of(fs) == ["GL008"]


def test_gl008_fires_on_conditional_donation():
    # `donate_argnums=(0,) if donate else ()` must be treated as donating
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh, donate):
            def body(x):
                return x * 2

            st = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
            return jax.jit(st, donate_argnums=(0,) if donate else ())
        """
    )
    assert rules_of(fs) == ["GL008"]


def test_gl008_quiet_on_replicated_out_specs():
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh):
            def body(x, p):
                return x.sum(), p

            st = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=(P(), P()))
            return jax.jit(st, donate_argnums=(0,))
        """
    )
    assert rules_of(fs) == []


def test_gl008_quiet_when_pinned():
    fs = lint(
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh):
            def body(x, p):
                return x * 2, p

            st = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=(P("dp"), P()))
            out = NamedSharding(mesh, P("dp"))
            return jax.jit(st, donate_argnums=(0,), out_shardings=(out, None))
        """
    )
    assert rules_of(fs) == []


def test_gl008_quiet_without_donation():
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh):
            def body(x, p):
                return x * 2, p

            st = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=(P("dp"), P()))
            return jax.jit(st)
        """
    )
    assert rules_of(fs) == []


def test_gl008_sharded_factory_does_not_indict_replicated_neighbor():
    # name maps are frame-scoped: `st` sharded in one factory must not make
    # the other factory's replicated `st` fire
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make_sharded(mesh):
            def body(x):
                return x * 2

            st = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
            out = __import__("jax").sharding.NamedSharding(mesh, P("dp"))
            return jax.jit(st, donate_argnums=(0,), out_shardings=out)

        def make_replicated(mesh):
            def body(x):
                return x.sum()

            st = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P())
            return jax.jit(st, donate_argnums=(0,))
        """
    )
    assert rules_of(fs) == []


def test_gl008_suppressible():
    fs = lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        def make(mesh):
            def body(x):
                return x * 2

            st = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
            return jax.jit(st, donate_argnums=(0,))  # graft-lint: disable=GL008
        """
    )
    assert rules_of(fs) == []
