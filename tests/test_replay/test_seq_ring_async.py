"""AsyncSequenceRing unit tests: the ragged per-env-head append program
(concurrent-actor blobs with env-column offsets) against a numpy oracle —
partial masks, wraparound, interleaved actors — plus the append-free train
sampler's head-validity plumbing, pack_rows purity, checkpoint round trip,
and the sequence-shape spillover accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data.ring import build_seq_train_step, env_view, pack_burst_blob, make_seq_ctl_layout
from sheeprl_tpu.parallel.fabric import Fabric
from sheeprl_tpu.replay import AsyncSequenceRing, estimate_ring_bytes, resolve_device_resident

CAP, LOCAL, ACTORS = 8, 2, 2
RING_ENVS = LOCAL * ACTORS
KEYS = {"obs": ((3,), jnp.float32), "rewards": ((1,), jnp.float32)}


def _ring(fabric=None, capacity=CAP, stage_rows=4, seq_len=2):
    fabric = fabric or Fabric(devices=1, accelerator="cpu")
    return AsyncSequenceRing(
        fabric, KEYS, capacity=capacity, n_envs=RING_ENVS, local_envs=LOCAL,
        seq_len=seq_len, stage_rows=stage_rows, seed=3,
    )


def _row(val, envs=LOCAL):
    return {
        "obs": np.full((envs, 3), val, np.float32),
        "rewards": np.full((envs, 1), val, np.float32),
    }


class _Oracle:
    """Per-env-head numpy ring twin."""

    def __init__(self, capacity=CAP, n_envs=RING_ENVS):
        self.storage = {
            k: np.zeros((capacity, n_envs) + shape, np.dtype(jnp.dtype(d))) for k, (shape, d) in KEYS.items()
        }
        self.pos = np.zeros(n_envs, np.int64)
        self.valid = np.zeros(n_envs, np.int64)
        self.capacity = capacity

    def append(self, rows, offset):
        for row, mask in rows:
            for e_local in range(len(mask)):
                if not mask[e_local]:
                    continue
                e = offset + e_local
                for k in self.storage:
                    self.storage[k][self.pos[e], e] = row[k][e_local]
                self.pos[e] = (self.pos[e] + 1) % self.capacity
                self.valid[e] = min(self.valid[e] + 1, self.capacity)


def _commit(ring, rows, offset):
    blob = ring.pack_rows(rows, offset)
    ring.append(jnp.asarray(blob))
    ring.note_append(
        np.concatenate([np.zeros(offset, np.int64), sum(m for _r, m in rows), np.zeros(RING_ENVS - offset - LOCAL, np.int64)]),
        blob.nbytes,
    )


def _assert_matches(ring, oracle):
    state = jax.device_get(ring.state)
    np.testing.assert_array_equal(np.asarray(state["pos"]), oracle.pos)
    np.testing.assert_array_equal(np.asarray(state["valid"]), oracle.valid)
    for k, (shape, _d) in KEYS.items():  # the ring as stored, read back in the env's shapes
        np.testing.assert_allclose(env_view(np.asarray(state["storage"][k]), shape), oracle.storage[k])
    np.testing.assert_array_equal(ring.host_pos, oracle.pos)
    np.testing.assert_array_equal(ring.host_valid, oracle.valid)


def test_ragged_append_matches_oracle_interleaved_actors():
    """Two actors' blobs — regular rows + ragged reset rows — commit
    interleaved; every env column's head advances exactly per its masks."""
    ring = _ring()
    oracle = _Oracle()
    ones = np.ones(LOCAL, np.int32)
    ragged = np.array([1, 0], np.int32)

    a0 = [(_row(1.0), ones), (_row(2.0), ragged)]  # env 0 gets an extra reset row
    a1 = [(_row(10.0), ones)]
    _commit(ring, a0, 0)
    oracle.append(a0, 0)
    _commit(ring, a1, LOCAL)
    oracle.append(a1, LOCAL)
    _assert_matches(ring, oracle)

    # heads advanced raggedly: actor-0's env 0 is one ahead of env 1
    assert ring.host_pos.tolist() == [2, 1, 1, 1]


def test_ragged_append_wraparound():
    """Rings wrap per env head; valid saturates at capacity."""
    ring = _ring(capacity=4, stage_rows=3)
    oracle = _Oracle(capacity=4)
    ones = np.ones(LOCAL, np.int32)
    for i in range(4):  # 4 blobs x 3 rows = 12 rows > capacity 4
        rows = [(_row(float(3 * i + j)), ones) for j in range(3)]
        _commit(ring, rows, 0)
        oracle.append(rows, 0)
        rows1 = [(_row(float(100 + 3 * i + j)), ones) for j in range(3)]
        _commit(ring, rows1, LOCAL)
        oracle.append(rows1, LOCAL)
    _assert_matches(ring, oracle)
    assert ring.host_valid.tolist() == [4, 4, 4, 4]


def test_pack_rows_is_pure():
    """pack_rows touches nothing on the ring (concurrent-writer safety)."""
    ring = _ring()
    before = jax.device_get(ring.state)
    blob1 = ring.pack_rows([(_row(5.0), np.ones(LOCAL, np.int32))], 0)
    blob2 = ring.pack_rows([(_row(5.0), np.ones(LOCAL, np.int32))], 0)
    np.testing.assert_array_equal(blob1, blob2)
    after = jax.device_get(ring.state)
    for k in KEYS:
        np.testing.assert_array_equal(before["storage"][k], after["storage"][k])
    assert ring.host_pos.sum() == 0 and ring._metrics["flushes"] == 0


def test_pack_rows_overflow_raises():
    ring = _ring(stage_rows=2)
    rows = [(_row(1.0), np.ones(LOCAL, np.int32))] * 3
    with pytest.raises(ValueError, match="exceed the append blob capacity"):
        ring.pack_rows(rows, 0)


def test_train_step_key_advances_and_heads_pass_through():
    """The append-free train program advances ONLY the in-ring key; storage
    and heads pass through, and granted steps sample with per-env validity."""
    fabric = Fabric(devices=1, accelerator="cpu")
    ring = _ring(fabric)
    ones = np.ones(LOCAL, np.int32)
    for off in (0, LOCAL):
        _commit(ring, [(_row(1.0), ones), (_row(2.0), ones)], off)

    calls = []

    def gradient_step(carry, xs):
        batch, key = xs
        calls.append(jax.tree.map(lambda x: x.shape, batch))
        return carry + 1, (jnp.mean(batch["obs"]),)

    train_fn, ctl_layout = build_seq_train_step(
        gradient_step, fabric.mesh,
        {"capacity": CAP, "n_envs": RING_ENVS, "grad_chunk": 2, "seq_len": 2, "batch_size": 4, "ring_keys": KEYS},
    )
    validmask = np.zeros(2, np.float32)
    validmask[:1] = 1.0
    ctl = fabric.put_replicated(pack_burst_blob(ctl_layout, {"__validmask__": validmask}))
    key_before = np.asarray(jax.device_get(ring.state["key"]))
    carry, new_key, metrics = train_fn(jnp.int32(0), ring.state, ctl)
    assert int(carry) == 1  # one granted step ran, one padding step skipped
    # the advanced train-key is the ONLY ring state the program returns —
    # storage/heads are read-only inputs (returning them would force a full
    # ring copy per dispatch); the caller splices the key back
    assert not np.array_equal(np.asarray(jax.device_get(new_key)), key_before)
    ring.set_key(new_key)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ring.state["key"])), np.asarray(jax.device_get(new_key))
    )
    # the sampled batch is (T, B) over the whole ring env axis
    assert calls[0]["obs"] == (2, 4, 3)


def test_train_step_holds_until_every_env_has_a_window():
    """The in-graph belt: granted steps are zeroed while ANY env is shorter
    than a sample window (mirrors the host-side ready() gate)."""
    fabric = Fabric(devices=1, accelerator="cpu")
    ring = _ring(fabric)
    # only actor 0's columns have data; actor 1's are empty
    _commit(ring, [(_row(1.0), np.ones(LOCAL, np.int32))] * 2, 0)
    assert not ring.ready()

    def gradient_step(carry, xs):
        return carry + 1, (jnp.zeros(()),)

    train_fn, ctl_layout = build_seq_train_step(
        gradient_step, fabric.mesh,
        {"capacity": CAP, "n_envs": RING_ENVS, "grad_chunk": 2, "seq_len": 2, "batch_size": 4, "ring_keys": KEYS},
    )
    ctl = fabric.put_replicated(
        pack_burst_blob(ctl_layout, {"__validmask__": np.ones(2, np.float32)})
    )
    carry, _new_key, _m = train_fn(jnp.int32(0), ring.state, ctl)
    assert int(carry) == 0  # every step masked off in-graph


def test_checkpoint_roundtrip_restores_heads_and_key():
    ring = _ring()
    ones = np.ones(LOCAL, np.int32)
    _commit(ring, [(_row(7.0), ones), (_row(8.0), np.array([0, 1], np.int32))], 0)
    _commit(ring, [(_row(9.0), ones)], LOCAL)
    snap = ring.state_dict()
    assert snap.kind == "sequence"

    ring2 = _ring()
    ring2.load_state_dict(snap)
    s1, s2 = jax.device_get(ring.state), jax.device_get(ring2.state)
    for k in KEYS:
        np.testing.assert_array_equal(s1["storage"][k], s2["storage"][k])
    np.testing.assert_array_equal(s1["pos"], s2["pos"])
    np.testing.assert_array_equal(s1["valid"], s2["valid"])
    np.testing.assert_array_equal(s1["key"], s2["key"])
    np.testing.assert_array_equal(ring.host_pos, ring2.host_pos)

    with pytest.raises(ValueError, match="shape mismatch"):
        _ring(capacity=16).load_state_dict(snap)


def test_sequence_spillover_accounting():
    """The sequence shape (heads + validity working set + the gathered f32
    sample window) must RAISE the estimate over flat rows, and the
    resolve gate must reflect it — an over-budget sequence ring is refused
    even when its flat rows alone would fit."""
    flat = estimate_ring_bytes(KEYS, 1024, RING_ENVS)
    seq = estimate_ring_bytes(KEYS, 1024, RING_ENVS, sequence={"seq_len": 64, "batch_size": 16})
    assert seq > flat
    # window-validity working set alone is capacity * n_envs * 4
    assert seq - flat >= 1024 * RING_ENVS * 4

    # budget chosen between the two estimates: flat fits, sequence does not
    budget_gb = (flat + (seq - flat) / 2) / (1 << 30)
    ok_flat, _, _ = resolve_device_resident("auto", KEYS, 1024, RING_ENVS, 1, budget_gb)
    assert ok_flat
    ok_seq, _, reason = resolve_device_resident(
        "auto", KEYS, 1024, RING_ENVS, 1, budget_gb, sequence={"seq_len": 64, "batch_size": 16}
    )
    assert not ok_seq and "GiB/device" in reason
