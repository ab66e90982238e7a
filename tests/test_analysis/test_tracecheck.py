"""Runtime sentinel semantics: retrace budgets, warmup, transfer guard,
report merging, and the trace-event ledger the comm wire-dtype guard rides."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.analysis.tracecheck import RetraceError, TraceCheck


@pytest.fixture()
def tc():
    t = TraceCheck()
    t.configure(mode="strict", transfer_guard=False)
    return t


def test_single_signature_never_trips(tc):
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f")
    for _ in range(5):
        f(jnp.ones((4,)))
    rep = tc.report()["f"]
    assert rep["calls"] == 5
    assert rep["compiles"] == 1
    assert rep["post_warmup_compiles"] == 0
    assert tc.post_warmup_retraces() == {}


def test_budget_trip_on_post_warmup_shape_drift(tc):
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f", warmup=1, budget=0)
    f(jnp.ones((4,)))  # warmup compile: free
    with pytest.raises(RetraceError, match="retraced after warmup"):
        f(jnp.ones((5,)))  # shape drift -> second compile -> trip


def test_budget_tolerates_declared_variants(tc):
    # budget=1: one legitimate post-warmup variant (e.g. a remainder batch)
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f", warmup=1, budget=1)
    f(jnp.ones((4,)))
    f(jnp.ones((5,)))  # within budget
    with pytest.raises(RetraceError):
        f(jnp.ones((6,)))  # exceeds it


def test_warmup_covers_deliberate_variants(tc):
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f", warmup=3, budget=0)
    f(jnp.ones((4,)))
    f(jnp.ones((5,)))
    f(jnp.ones((6,)))  # all inside warmup
    f(jnp.ones((4,)))  # cached
    assert tc.report()["f"]["post_warmup_compiles"] == 0


def test_weak_type_drift_is_a_retrace(tc):
    # the classic: a python float arg traces weakly-typed, a jnp scalar does
    # not — flipping between them recompiles
    f = tc.instrument(jax.jit(lambda x, s: x * s), name="f", warmup=1, budget=0)
    f(jnp.ones((4,)), jnp.float32(0.5))
    with pytest.raises(RetraceError):
        f(jnp.ones((4,)), 0.5)


def test_warn_mode_warns_instead_of_raising(tc):
    tc.configure(mode="warn")
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f", warmup=1, budget=0)
    f(jnp.ones((4,)))
    with pytest.warns(RuntimeWarning, match="retraced after warmup"):
        f(jnp.ones((5,)))


def test_off_mode_is_passthrough(tc):
    tc.configure(mode="off")
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f")
    f(jnp.ones((4,)))
    f(jnp.ones((5,)))
    assert tc.report()["f"]["calls"] == 0  # nothing recorded


def test_transfer_guard_blocks_post_warmup_numpy(tc):
    tc.configure(transfer_guard=True)
    f = tc.instrument(jax.jit(lambda x: x + 1), name="f", warmup=1)
    f(np.ones((4,), np.float32))  # warmup: implicit transfer tolerated
    with pytest.raises(Exception, match="[Dd]isallowed host-to-device"):
        f(np.ones((4,), np.float32))  # steady state: an error


def test_transfer_guard_allows_device_args(tc):
    tc.configure(transfer_guard=True)
    f = tc.instrument(jax.jit(lambda x: x + 1), name="f", warmup=1)
    x = jax.device_put(np.ones((4,), np.float32))
    f(x)
    f(x)  # post-warmup, on-device: fine
    assert tc.post_warmup_retraces() == {}


def test_transfer_guard_per_entry_opt_out(tc):
    tc.configure(transfer_guard=True)
    f = tc.instrument(jax.jit(lambda x: x + 1), name="rollout", warmup=1, transfer_guard=False)
    # host inputs by contract: never guarded
    f(np.ones((4,), np.float32))
    f(np.ones((4,), np.float32))
    assert tc.report()["rollout"]["calls"] == 2


def test_report_merges_same_name_across_runs(tc):
    # two "runs" instrument the same logical entry point
    f1 = tc.instrument(jax.jit(lambda x: x * 2), name="train_step")
    f1(jnp.ones((4,)))
    f2 = tc.instrument(jax.jit(lambda x: x * 3), name="train_step")
    f2(jnp.ones((4,)))
    rep = tc.report()["train_step"]
    assert rep["calls"] == 2
    assert rep["compiles"] == 2
    assert rep["post_warmup_compiles"] == 0  # each run's first call is its warmup


def test_instrument_transparent_to_donation(tc):
    f = tc.instrument(jax.jit(lambda x: x + 1, donate_argnums=(0,)), name="f")
    x = jax.device_put(jnp.ones((4,)))
    y = f(x)
    assert x.is_deleted()  # donation still happened through the wrapper
    np.testing.assert_allclose(np.asarray(y), 2.0)


def test_non_jit_callable_falls_back_to_signature_tracking(tc):
    # no _cache_size on a plain python fn: distinct abstract signatures count
    calls = []

    def f(x):
        calls.append(x.shape)
        return x

    g = tc.instrument(f, name="g", warmup=1, budget=0)
    g(jnp.ones((4,)))
    with pytest.raises(RetraceError):
        g(jnp.ones((5,)))


def test_thread_safety_under_concurrent_callers(tc):
    tc.configure(mode="strict")
    f = tc.instrument(jax.jit(lambda x: x * 2), name="f", warmup=8)
    errs = []

    def worker():
        try:
            for _ in range(20):
                f(jnp.ones((4,)))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    rep = tc.report()["f"]
    assert rep["calls"] == 80
    assert rep["post_warmup_compiles"] == 0


def test_event_ledger(tc):
    tc.record_event("tag", "a")
    tc.record_event("tag", "b")
    assert tc.events("tag") == ["a", "b"]
    assert tc.events("other") == []
    tc.clear_events("tag")
    assert tc.events("tag") == []


def test_reset_clears_entries_and_events(tc):
    f = tc.instrument(jax.jit(lambda x: x), name="f")
    f(jnp.ones((2,)))
    tc.record_event("tag", 1)
    tc.reset()
    assert tc.report() == {}
    assert tc.events("tag") == []


def test_configure_rejects_bad_mode(tc):
    with pytest.raises(ValueError):
        tc.configure(mode="loud")


def test_comm_wire_guard_rides_the_ledger():
    """The PR-3 grad_reduce_dtype retrace guard is now tracecheck-backed:
    tracing pmean_grads records an event, and a mid-run dtype flip warns."""
    from sheeprl_tpu.analysis.tracecheck import tracecheck as global_tc
    from sheeprl_tpu.parallel.comm import _WIRE_TAG, pmean_grads, set_grad_reduce_dtype

    set_grad_reduce_dtype("bfloat16", fresh_run=True)
    assert global_tc.events(_WIRE_TAG) == []

    def reduce_under_shmap():
        mesh = jax.sharding.Mesh(np.array(jax.devices("cpu")[:2]), ("dp",))
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        f = shard_map(
            lambda g: pmean_grads(g, "dp"), mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        return jax.jit(f)(jnp.ones((2, 4)))

    reduce_under_shmap()
    assert len(global_tc.events(_WIRE_TAG)) >= 1  # trace recorded its dtype
    with pytest.warns(UserWarning, match="grad_reduce_dtype changed"):
        set_grad_reduce_dtype("float32")  # mid-run flip
    set_grad_reduce_dtype("float32", fresh_run=True)  # leave clean state


# --------------------------------------------------------------------------- #
# JSON dump artifact (SHEEPRL_TPU_TRACECHECK_DUMP / bench lanes / the
# `python -m sheeprl_tpu.analysis tracecheck <path>` validator)
# --------------------------------------------------------------------------- #


def test_dump_payload_and_file_round_trip(tc, tmp_path):
    import json

    f = tc.instrument(jax.jit(lambda x: x * 2), name="hot", warmup=1, budget=0)
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))
    tc.record_event("wire_dtype", "bfloat16")
    path = tmp_path / "ledger.json"
    payload = tc.dump(str(path))
    assert payload["entries"]["hot"]["compiles"] == 1
    assert payload["post_warmup_retraces"] == {}
    assert payload["events"]["wire_dtype"] == ["'bfloat16'"]
    on_disk = json.loads(path.read_text())
    assert on_disk == payload


def test_dump_cli_validator_exit_contract(tc, tmp_path):
    import subprocess
    import sys

    clean = tc.instrument(jax.jit(lambda x: x * 2), name="clean", warmup=2, budget=0)
    clean(jnp.ones((4,)))
    path = tmp_path / "ok.json"
    tc.dump(str(path))
    r = subprocess.run(
        [sys.executable, "-m", "sheeprl_tpu.analysis", "tracecheck", str(path)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr

    # a hot path over its post-warmup budget must fail the validator
    tc.configure(mode="warn")
    bad = tc.instrument(jax.jit(lambda x: x * 3), name="bad", warmup=1, budget=0)
    with pytest.warns(RuntimeWarning):
        bad(jnp.ones((4,)))
        bad(jnp.ones((5,)))  # post-warmup retrace
    path2 = tmp_path / "bad.json"
    tc.dump(str(path2))
    r2 = subprocess.run(
        [sys.executable, "-m", "sheeprl_tpu.analysis", "tracecheck", str(path2)],
        capture_output=True, text=True,
    )
    assert r2.returncode == 1
    assert "RETRACE bad" in r2.stdout


def test_dump_env_var_registers_atexit_export(tmp_path):
    import json
    import os
    import subprocess
    import sys
    import textwrap

    path = tmp_path / "exit.json"
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp
        # the process-wide singleton reads the env at construction and
        # registers the atexit export (a fresh TraceCheck would register a
        # SECOND atexit dump to the same path and race it)
        from sheeprl_tpu.analysis.tracecheck import tracecheck
        f = tracecheck.instrument(jax.jit(lambda x: x + 1), name="exit_hot")
        f(jnp.ones((2,)))
        """
    )
    env = {**os.environ, "SHEEPRL_TPU_TRACECHECK_DUMP": str(path), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    payload = json.loads(path.read_text())
    assert payload["entries"]["exit_hot"]["calls"] == 1
