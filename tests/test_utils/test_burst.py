"""Unit tests for the shared hybrid-player burst machinery
(``utils/burst.py``): packed host snapshots, ring init/mirror, and the
BurstRunner staging/dispatch semantics — with a fake burst_fn so the queue
and thread lifecycle are exercised without compiling a train step.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sheeprl_tpu.ops.kernels as K
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.ring import (
    build_burst_train_step,
    build_seq_append_step,
    build_seq_train_step,
    env_view,
    make_blob_layouts,
    pack_burst_blob,
    ring_append_rows,
    ring_cell,
    ring_sample_windows,
    ring_view,
)
from sheeprl_tpu.utils.burst import BurstRunner, HostSnapshot, dreamer_ring_keys, init_device_ring


class _FakeFabric:
    replicated = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def put_replicated(self, tree):
        return jax.tree.map(jnp.asarray, tree)


class TestHostSnapshot:
    def test_pull_round_trips_subset(self):
        params = {"world_model": {"encoder": jnp.arange(8.0), "decoder": jnp.ones(4)}, "actor": jnp.ones(3) * 2}
        subset = lambda p: {"enc": p["world_model"]["encoder"], "actor": p["actor"]}
        snap = HostSnapshot(subset, params)
        host = snap.pull(params)
        np.testing.assert_allclose(np.asarray(host["enc"]), np.arange(8.0), rtol=1e-2)
        np.testing.assert_allclose(np.asarray(host["actor"]), 2.0, rtol=1e-2)

    def test_refresh_then_poll_returns_once(self):
        params = {"w": jnp.ones(4)}
        snap = HostSnapshot(lambda p: p, params)
        assert snap.poll() is None
        snap.refresh({"w": jnp.full((4,), 3.0)})
        polled = snap.poll()
        np.testing.assert_allclose(np.asarray(polled["w"]), 3.0, rtol=1e-2)
        assert snap.poll() is None  # consumed


class TestInitDeviceRing:
    KEYS = {"obs": ((2,), jnp.float32), "rewards": ((1,), jnp.float32), "rgb": ((8, 16, 3), jnp.uint8)}

    def test_fresh_ring_is_zeroed(self):
        rb_dev, pos, valid = init_device_ring(_FakeFabric(), self.KEYS, capacity=5, n_envs=3)
        # stored view: (capacity, n_envs) + ring_cell(shape) -- (1, feat), or (feat // 128, 128) for pixels
        assert rb_dev["obs"].shape == (5, 3, 1, 2) and rb_dev["rewards"].shape == (5, 3, 1, 1)
        assert rb_dev["rgb"].shape == (5, 3, 3, 128) and rb_dev["rgb"].dtype == jnp.uint8
        assert float(rb_dev["obs"].sum()) == 0.0
        assert pos.tolist() == [0, 0, 0] and valid.tolist() == [0, 0, 0]

    def test_mirror_restores_contents_and_heads(self):
        rb = EnvIndependentReplayBuffer(4, n_envs=2, obs_keys=("obs", "rgb"), buffer_cls=SequentialReplayBuffer)
        data = {
            "obs": np.arange(12, dtype=np.float32).reshape(3, 2, 2),
            "rewards": np.ones((3, 2, 1), np.float32),
            "rgb": np.random.default_rng(0).integers(0, 255, (3, 2, 8, 16, 3)).astype(np.uint8),
        }
        rb.add(data)
        rb_dev, pos, valid = init_device_ring(_FakeFabric(), self.KEYS, capacity=4, n_envs=2, rb=rb)
        for k, (shape, _dtype) in self.KEYS.items():  # reshaped on the host, bit for bit
            assert rb_dev[k].shape == (4, 2) + ring_cell(shape)
            np.testing.assert_array_equal(env_view(np.asarray(rb_dev[k]), shape)[:3], data[k])
        assert pos.tolist() == [3, 3]
        assert valid.tolist() == [3, 3]


# -- the ring's stored view (data/ring.py:ring_cell) against the env-shaped scatter and gather --

PIXEL = {"rgb": ((8, 16, 3), jnp.uint8), "actions": ((5,), jnp.float32), "is_first": ((1,), jnp.float32)}
VECTOR = {"state": ((7,), jnp.float32), "rewards": ((1,), jnp.float32)}
GRAD_CHUNK, SEQ, BATCH = 2, 3, 4

# (ring keys, capacity, per-env heads before the append, per-env valid counts, (S, E) write masks)
STORED_VIEW_CASES = {
    "pixel-one-env": (PIXEL, 24, [5], [5], [[1], [1], [1]]),
    "pixel-wrap-around": (PIXEL, 24, [23], [24], [[1], [1], [1]]),
    "vector-dropped-slots": (VECTOR, 24, [4, 6, 3], [4, 6, 3], [[1, 0, 1], [0, 0, 1], [1, 0, 0]]),
    "pixel-four-envs-ragged-wrap": (PIXEL, 24, [22, 3, 23, 9], [24, 3, 24, 9], [[1, 1, 1, 1], [0, 1, 1, 0], [1, 0, 1, 0]]),
}


def _ring_spec(keys, capacity, n_envs, rows=None):
    spec = {"capacity": capacity, "n_envs": n_envs, "grad_chunk": GRAD_CHUNK, "seq_len": SEQ, "batch_size": BATCH,
            "ring_keys": keys}
    return spec if rows is None else {**spec, "stage_buckets": (rows,), "stage_max": rows}


def _fill(rng, keys, lead):
    return {
        k: (rng.integers(0, 255, lead + shape) if dtype == jnp.uint8 else rng.normal(size=lead + shape)).astype(
            np.dtype(dtype)
        )
        for k, (shape, dtype) in keys.items()
    }


def _collect_step(keys):
    """A ``gradient_step`` that keeps every granted step's batch in the carry."""
    carry = (jnp.int32(0), {k: jnp.zeros((GRAD_CHUNK, SEQ, BATCH) + shape, dtype) for k, (shape, dtype) in keys.items()})

    def gradient_step(c, xs):
        i, seen = c
        batch, _key = xs
        return (i + 1, {k: seen[k].at[i].set(batch[k]) for k in seen}), (jnp.zeros(()),)

    return carry, gradient_step


def _reference_windows(key, env_ring, pos, valid, capacity, n_envs):
    """What the granted steps must see: the keys the burst derives, the env-shaped gather."""
    out = []
    for k in jax.random.split(jax.random.fold_in(key, 0), GRAD_CHUNK):
        k_env, k_start, _k_grad = jax.random.split(k, 3)
        env_idx = jax.random.randint(k_env, (BATCH,), 0, n_envs)
        t_idx = ring_sample_windows(k_start, env_idx, pos, valid, capacity, SEQ)
        out.append({name: np.asarray(arr)[np.asarray(t_idx), np.asarray(env_idx)[None, :]] for name, arr in env_ring.items()})
    return out


@pytest.mark.parametrize("backend", ["lax", "pallas"])  # pallas here is the interpreter
@pytest.mark.parametrize("case", list(STORED_VIEW_CASES))
def test_append_then_sample_through_stored_view_equals_env_shaped_reference(case, backend):
    """One burst (append, then two granted steps) on the ring as stored,
    against the literal env-shaped ``.at[row, col].set`` and fancy-indexed
    gather, bit for bit: pixel and vector keys, a head that wraps, dropped
    slots, several envs."""
    from sheeprl_tpu.parallel.fabric import Fabric

    keys, capacity, pos, valid, mask = STORED_VIEW_CASES[case]
    pos, valid, mask = np.asarray(pos, np.int32), np.asarray(valid, np.int32), np.asarray(mask, np.int32)
    n_envs, rows = mask.shape[1], mask.shape[0]
    rng = np.random.default_rng(3)
    env_ring, staged = _fill(rng, keys, (capacity, n_envs)), _fill(rng, keys, (rows, n_envs))
    ring = _ring_spec(keys, capacity, n_envs, rows)
    carry, gradient_step = _collect_step(keys)
    key = jax.random.PRNGKey(7)
    layout = make_blob_layouts(keys, n_envs, GRAD_CHUNK, (rows,))[rows]
    blob = pack_burst_blob(
        layout,
        {**staged, "__mask__": mask, "__pos__": pos, "__valid_n__": valid, "__key__": np.asarray(key, np.uint32),
         "__validmask__": np.ones(GRAD_CHUNK, np.float32)},
    )
    rb = {k: jnp.asarray(ring_view(env_ring[k], shape)) for k, (shape, _d) in keys.items()}
    with K.use_backend(backend):
        burst_fn = build_burst_train_step(gradient_step, Fabric(devices=1, accelerator="cpu").mesh, ring)
        (steps, seen), rb_out, _metrics = burst_fn(carry, rb, jnp.asarray(blob))
    assert int(steps) == GRAD_CHUNK

    row, new_pos, new_valid = ring_append_rows(jnp.asarray(pos), jnp.asarray(valid), jnp.asarray(mask), capacity)
    cols = jnp.broadcast_to(jnp.arange(n_envs)[None, :], row.shape)
    want_ring = {k: np.asarray(jnp.asarray(env_ring[k]).at[row, cols].set(jnp.asarray(staged[k]), mode="drop")) for k in keys}
    for k, (shape, _d) in keys.items():
        assert rb_out[k].shape == (capacity, n_envs) + ring_cell(shape)
        np.testing.assert_array_equal(env_view(np.asarray(rb_out[k]), shape), want_ring[k], err_msg=k)
    want = _reference_windows(key, want_ring, new_pos, new_valid, capacity, n_envs)
    for g in range(GRAD_CHUNK):
        for k, (shape, _d) in keys.items():
            assert seen[k][g].shape == (SEQ, BATCH) + shape  # gradient_step sees the env's shapes
            np.testing.assert_array_equal(np.asarray(seen[k][g]), want[g][k], err_msg=f"{k} step {g}")


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_sebulba_append_at_a_column_offset_then_sample_equals_reference(backend):
    """The decoupled pair (``build_seq_append_step`` + ``build_seq_train_step``)
    on the stored view: one actor's blob lands in env columns 2..3 of 4."""
    from sheeprl_tpu.parallel.fabric import Fabric

    keys, capacity, n_envs, local, rows, offset = PIXEL, 24, 4, 2, 3, 2
    mesh = Fabric(devices=1, accelerator="cpu").mesh
    rng = np.random.default_rng(5)
    env_ring, staged = _fill(rng, keys, (capacity, n_envs)), _fill(rng, keys, (rows, local))
    pos, valid = np.asarray([9, 9, 22, 7], np.int32), np.asarray([9, 9, 24, 7], np.int32)
    mask = np.asarray([[1, 1], [1, 0], [1, 1]], np.int32)
    key = np.asarray(jax.random.PRNGKey(11))  # the append donates the state, the key with it
    state = {
        "storage": {k: jnp.asarray(ring_view(env_ring[k], shape)) for k, (shape, _d) in keys.items()},
        "pos": jnp.asarray(pos), "valid": jnp.asarray(valid), "key": jnp.asarray(key),
    }
    carry, gradient_step = _collect_step(keys)
    ring = _ring_spec(keys, capacity, n_envs)
    with K.use_backend(backend):
        append_fn, layout = build_seq_append_step(mesh, keys, capacity, n_envs, local, rows)
        train_fn, ctl_layout = build_seq_train_step(gradient_step, mesh, ring)
        blob = pack_burst_blob(layout, {**staged, "__mask__": mask, "__offset__": np.asarray(offset, np.int32)})
        state = append_fn(state, jnp.asarray(blob))
        ctl = pack_burst_blob(ctl_layout, {"__validmask__": np.ones(GRAD_CHUNK, np.float32)})
        (steps, seen), _new_key, _metrics = train_fn(carry, state, jnp.asarray(ctl))
    assert int(steps) == GRAD_CHUNK

    row, new_pos_l, new_valid_l = ring_append_rows(
        jnp.asarray(pos[offset:]), jnp.asarray(valid[offset:]), jnp.asarray(mask), capacity
    )
    cols = offset + jnp.broadcast_to(jnp.arange(local)[None, :], row.shape)
    want_ring = {k: np.asarray(jnp.asarray(env_ring[k]).at[row, cols].set(jnp.asarray(staged[k]), mode="drop")) for k in keys}
    new_pos = jnp.asarray(pos).at[offset:].set(new_pos_l)
    new_valid = jnp.asarray(valid).at[offset:].set(new_valid_l)
    np.testing.assert_array_equal(np.asarray(state["pos"]), np.asarray(new_pos))
    for k, (shape, _d) in keys.items():
        np.testing.assert_array_equal(env_view(np.asarray(state["storage"][k]), shape), want_ring[k], err_msg=k)
    # the train program splits its dispatch key off the ring's key stream first
    _next_key, k_dispatch = jax.random.split(jnp.asarray(key))
    want = _reference_windows(k_dispatch, want_ring, new_pos, new_valid, capacity, n_envs)
    for g in range(GRAD_CHUNK):
        for k in keys:
            np.testing.assert_array_equal(np.asarray(seen[k][g]), want[g][k], err_msg=f"{k} step {g}")


def _equations(jaxpr):
    from jax.extend import core as jex

    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jex.ClosedJaxpr):
                    yield from _equations(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    yield from _equations(sub)


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_traced_burst_program_never_reshapes_or_transposes_a_ring_key(backend):
    """Structural: no ``reshape`` or ``transpose`` equation of the traced
    burst program takes an operand whose leading dimension is the ring's
    capacity. Under the TPU's tiled layouts such a reshape is no bitcast: XLA
    answers it with copies of the whole ring, every burst (PERF.md, PR 28)."""
    from sheeprl_tpu.parallel.fabric import Fabric

    keys, capacity, n_envs, rows = PIXEL, 29, 2, 3  # no blob segment is 29 bytes long
    ring = _ring_spec(keys, capacity, n_envs, rows)
    carry, gradient_step = _collect_step(keys)
    rb = {k: jax.ShapeDtypeStruct((capacity, n_envs) + ring_cell(shape), dtype) for k, (shape, dtype) in keys.items()}
    blob = jax.ShapeDtypeStruct((make_blob_layouts(keys, n_envs, GRAD_CHUNK, (rows,))[rows].nbytes,), jnp.uint8)
    with K.use_backend(backend):
        burst_fn = build_burst_train_step(gradient_step, Fabric(devices=1, accelerator="cpu").mesh, ring)
        jaxpr = jax.make_jaxpr(burst_fn)(carry, rb, blob).jaxpr
    eqns = list(_equations(jaxpr))
    names = {e.primitive.name for e in eqns}
    assert {"shard_map", "scan", "cond", "gather"} <= names  # the walk reaches the loop body's gather
    assert ("pallas_call" in names) == (backend == "pallas")
    whole_ring = [
        str(e) for e in eqns
        if e.primitive.name in ("reshape", "transpose") and getattr(e.invars[0].aval, "shape", ())[:1] == (capacity,)
    ]
    assert whole_ring == []


def test_dreamer_ring_keys_layout():
    import gymnasium as gym

    space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
            "state": gym.spaces.Box(-1, 1, (7,), np.float32),
        }
    )
    keys = dreamer_ring_keys(space, ["rgb"], ["state"], (2, 3), with_is_first=False)
    assert keys["rgb"] == ((64, 64, 3), jnp.uint8)
    assert keys["state"] == ((7,), jnp.float32)
    assert keys["actions"] == ((5,), jnp.float32)
    assert "is_first" not in keys
    assert "is_first" in dreamer_ring_keys(space, ["rgb"], [], (2,), with_is_first=True)


class _RecordingBurstFn:
    """Fake burst_fn: counts granted steps, appends rows into a numpy mirror."""

    def __init__(self):
        self.calls = []
        self.fail = False

    def __call__(self, carry, rb, staged, mask, pos, valid_n, key, validmask):
        if self.fail:
            raise RuntimeError("burst boom")
        granted = float(np.asarray(validmask).sum())
        self.calls.append(
            {
                "granted": granted,
                "rows": int(np.asarray(mask).sum()),
                "upload_rows": int(np.asarray(mask).shape[0]),
                "staged_shape": {k: staged[k].shape for k in staged},
            }
        )
        return carry + granted, rb, (jnp.float32(granted),)


def _runner(burst_fn, n_envs=2, capacity=8, grad_chunk=2, stage_max=6, seq_len=2):
    keys = {"obs": ((1,), jnp.float32)}
    rb_dev = {"obs": jnp.zeros((capacity, n_envs, 1), jnp.float32)}
    return BurstRunner(
        burst_fn, jnp.float32(0.0), rb_dev, keys,
        n_envs=n_envs, capacity=capacity, grad_chunk=grad_chunk,
        stage_max=stage_max, seq_len=seq_len, params_of=lambda c: c,
    )


def _wait(pred, timeout=5.0):
    t0 = time.time()
    while not pred():
        if time.time() - t0 > timeout:
            raise AssertionError("timed out waiting for burst worker")
        time.sleep(0.01)


class TestBurstRunner:
    def test_flush_holds_grants_until_windows_exist(self):
        fn = _RecordingBurstFn()
        r = _runner(fn, seq_len=4)
        r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        # 1 row < seq_len 4 -> append-only burst, no grants consumed
        assert r.flush(jax.random.PRNGKey(0), grant_backlog=5) == 0
        _wait(lambda: len(fn.calls) == 1)
        assert fn.calls[0]["granted"] == 0.0
        for _ in range(4):
            r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        assert r.flush(jax.random.PRNGKey(1), grant_backlog=5) == 2  # capped at grad_chunk
        _wait(lambda: len(fn.calls) == 2)
        assert fn.calls[1]["granted"] == 2.0
        assert r.close() is not None

    def test_ring_heads_advance_with_ragged_resets(self):
        fn = _RecordingBurstFn()
        r = _runner(fn)
        r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        r.stage_reset({"obs": np.ones((1, 1, 1), np.float32)}, [1])  # env 1 only
        r.flush(jax.random.PRNGKey(0), grant_backlog=0)
        assert r.dev_pos.tolist() == [1, 2]
        assert r.dev_valid.tolist() == [1, 2]
        assert r.staged_count == 0
        r.close()

    def test_patch_last_edits_most_recent_row(self):
        fn = _RecordingBurstFn()
        r = _runner(fn)
        r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        r.patch_last(0, {"obs": 9.0})
        row, _mask = r._staged[-1]
        assert row["obs"][0, 0] == 9.0 and row["obs"][1, 0] == 1.0
        r.close()

    def test_worker_crash_escalates_through_the_ladder(self):
        """A persistently-failing burst step exhausts the restart budget and
        surfaces as a TYPED supervision error on a later flush — the
        supervised replacement of the old park-and-resurface semantics."""
        import warnings

        from sheeprl_tpu.fault.supervisor import AllWorkersDeadError

        fn = _RecordingBurstFn()
        fn.fail = True
        keys = {"obs": ((1,), jnp.float32)}
        rb_dev = {"obs": jnp.zeros((8, 2, 1), jnp.float32)}
        r = BurstRunner(
            fn, jnp.float32(0.0), rb_dev, keys,
            n_envs=2, capacity=8, grad_chunk=2, stage_max=6, seq_len=2,
            params_of=lambda c: c,
            supervisor_cfg={"backoff": 0.0, "max_restarts": 1, "escalation": "degrade"},
        )
        r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", UserWarning)  # restart/degrade announcements
            with pytest.raises(AllWorkersDeadError):
                for _ in range(100):  # crash -> restart -> crash -> degraded -> typed error
                    r.flush(jax.random.PRNGKey(0), grant_backlog=0)
                    time.sleep(0.05)

    def test_kill_thread_chaos_is_restarted_not_silent(self):
        """The satellite regression: ``ThreadKilled`` (a BaseException the old
        raw daemon worker died SILENTLY on — submits then blocked forever)
        now restarts through the supervisor, the in-flight burst is
        re-dispatched, and every staged burst still lands."""
        from sheeprl_tpu.fault import inject

        fn = _RecordingBurstFn()
        keys = {"obs": ((1,), jnp.float32)}
        rb_dev = {"obs": jnp.zeros((8, 2, 1), jnp.float32)}
        r = BurstRunner(
            fn, jnp.float32(0.0), rb_dev, keys,
            n_envs=2, capacity=8, grad_chunk=2, stage_max=6, seq_len=1,
            params_of=lambda c: c, supervisor_cfg={"backoff": 0.0},
        )
        inject.arm("burst.trainer.step", action="kill-thread", at=2)
        try:
            with pytest.warns(UserWarning, match="burst-trainer.*restarting"):
                for i in range(3):
                    r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
                    r.flush(jax.random.PRNGKey(i), grant_backlog=1)
                # hit 2 kills the worker BEFORE dispatching burst 2; the
                # restarted generation re-dispatches it from shared state.
                # Detection runs at the CALLER's cadence (supervisor design),
                # so drive check() while waiting — the env loop's flush/submit
                # calls play this role in the real wiring.
                t0 = time.time()
                while len(fn.calls) < 3:
                    assert time.time() - t0 < 10.0, f"bursts never recovered: {len(fn.calls)}/3"
                    r._thread.check()
                    time.sleep(0.02)
        finally:
            inject.reset()
        assert r._thread.supervisor.worker("burst-trainer").restarts == 1
        assert [c["rows"] for c in fn.calls] == [2, 2, 2]  # nothing lost
        r.close()

    def test_supervised_snapshot_refresh_recovers_from_kill(self):
        """A killed device→host pull no longer freezes the host policy at its
        last version: the supervised refresh worker restarts and re-runs the
        retained pending pull."""
        from sheeprl_tpu.fault import inject
        from sheeprl_tpu.fault.supervisor import Supervisor

        params = {"w": jnp.ones(4)}
        snap = HostSnapshot(lambda p: p, params)
        sup = Supervisor(backoff=0.0, name="snap-test")
        snap.attach_supervisor(sup)
        inject.arm("burst.snapshot.refresh", action="kill-thread", at=1)
        try:
            assert snap.refresh_async({"w": jnp.full((4,), 5.0)})
            polled = None
            with pytest.warns(UserWarning, match="snapshot-refresh.*restarting"):
                t0 = time.time()
                while polled is None:
                    assert time.time() - t0 < 10.0, "refresh never recovered"
                    sup.check()
                    time.sleep(0.02)
                    polled = snap.poll()
        finally:
            inject.reset()
            sup.join()
        np.testing.assert_allclose(np.asarray(polled["w"]), 5.0, rtol=1e-2)

    def test_stage_buckets_size_each_upload(self):
        fn = _RecordingBurstFn()
        keys = {"obs": ((1,), jnp.float32)}
        rb_dev = {"obs": jnp.zeros((16, 2, 1), jnp.float32)}
        r = BurstRunner(
            fn, jnp.float32(0.0), rb_dev, keys,
            n_envs=2, capacity=16, grad_chunk=2, stage_max=12, seq_len=1,
            params_of=lambda c: c, stage_buckets=(3, 6),
        )
        # 2 staged rows -> smallest bucket (3); 5 rows -> next bucket (6);
        # 8 rows -> the implicit stage_max fallback bucket (12). Data beyond
        # the staged rows must be zero padding, never stale rows.
        for i, n_rows in enumerate((2, 5, 8)):
            for _ in range(n_rows):
                r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
            r.flush(jax.random.PRNGKey(n_rows), grant_backlog=0)
            _wait(lambda: len(fn.calls) == i + 1)
        sizes = [(c["rows"] // 2, c["upload_rows"]) for c in fn.calls]
        assert sizes == [(2, 3), (5, 6), (8, 12)]
        assert all(c["staged_shape"]["obs"] == (c["upload_rows"], 2, 1) for c in fn.calls)
        r.close()

    def test_bucket_normalization_caps_and_sorts(self):
        fn = _RecordingBurstFn()
        keys = {"obs": ((1,), jnp.float32)}
        rb_dev = {"obs": jnp.zeros((16, 1, 1), jnp.float32)}
        r = BurstRunner(
            fn, jnp.float32(0.0), rb_dev, keys,
            n_envs=1, capacity=16, grad_chunk=1, stage_max=5, seq_len=1,
            params_of=lambda c: c, stage_buckets=(9, 3, 0, 3),  # >cap, dup, junk
        )
        assert r._stage_buckets == [3, 5]
        r.close()

    def test_carry_readable_while_running(self):
        fn = _RecordingBurstFn()
        r = _runner(fn, seq_len=1)
        r.stage_step({"obs": np.ones((1, 2, 1), np.float32)})
        r.flush(jax.random.PRNGKey(0), grant_backlog=2)
        _wait(lambda: len(fn.calls) == 1)
        assert float(np.asarray(r.carry)) == 2.0  # fake carry counts granted steps
        r.close()
