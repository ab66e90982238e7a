"""Device sequence-ring index math for Dreamer-V3 burst mode
(`ring_append_rows` / `ring_sample_windows`): per-env ragged appends and the
SequentialReplayBuffer window-validity rule on device.
"""

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import ring_append_rows, ring_sample_windows

CAP = 10


def test_ragged_append_advances_only_masked_envs():
    pos = jnp.asarray([0, 5], jnp.int32)
    valid = jnp.asarray([0, 5], jnp.int32)
    # 3 slots: all-envs row, env-1-only reset row, all-envs row.
    mask = jnp.asarray([[1, 1], [0, 1], [1, 1]], jnp.int32)
    row, new_pos, new_valid = ring_append_rows(pos, valid, mask, CAP)
    # env 0 writes rows 0,2 at positions 0,1; slot 1 dropped (capacity).
    assert row[:, 0].tolist() == [0, CAP, 1]
    # env 1 writes 3 consecutive rows from its own head at 5.
    assert row[:, 1].tolist() == [5, 6, 7]
    assert new_pos.tolist() == [2, 8]
    assert new_valid.tolist() == [2, 8]


def test_append_wraps_and_caps_valid():
    pos = jnp.asarray([8], jnp.int32)
    valid = jnp.asarray([9], jnp.int32)
    mask = jnp.ones((4, 1), jnp.int32)
    row, new_pos, new_valid = ring_append_rows(pos, valid, mask, CAP)
    assert row[:, 0].tolist() == [8, 9, 0, 1]
    assert new_pos.tolist() == [2]
    assert new_valid.tolist() == [CAP]


def test_padding_slots_are_dropped():
    pos = jnp.asarray([3], jnp.int32)
    valid = jnp.asarray([3], jnp.int32)
    mask = jnp.asarray([[1], [0], [0]], jnp.int32)
    row, new_pos, _ = ring_append_rows(pos, valid, mask, CAP)
    assert row[:, 0].tolist() == [3, CAP, CAP]
    assert new_pos.tolist() == [4]


def test_windows_never_cross_write_head_when_full():
    seq = 4
    pos = jnp.asarray([6], jnp.int32)  # full ring: oldest data starts at 6
    valid = jnp.asarray([CAP], jnp.int32)
    env_idx = jnp.zeros((512,), jnp.int32)
    for s in range(20):
        t_idx = np.asarray(ring_sample_windows(jax.random.PRNGKey(s), env_idx, pos, valid, CAP, seq))
        # Unroll each window from its start: the write head (position 6 as a
        # window INTERIOR crossing) must never be straddled — i.e. no window
        # contains the transition 5 -> 6 (newest -> oldest).
        starts = t_idx[0]
        for st in np.unique(starts):
            window = [(st + i) % CAP for i in range(seq)]
            # 6 may only appear as the FIRST element (oldest row).
            if 6 in window:
                assert window[0] == 6 or 6 not in window[1:] or window.index(6) == 0
            # stronger: the pair (5, 6) must never be adjacent inside a window
            for a, b in zip(window[:-1], window[1:]):
                assert not (a == (pos[0] - 1) % CAP and b == pos[0])


def test_windows_stay_in_valid_prefix_when_not_full():
    seq = 3
    pos = jnp.asarray([7], jnp.int32)
    valid = jnp.asarray([7], jnp.int32)  # rows 0..6 valid
    env_idx = jnp.zeros((256,), jnp.int32)
    t_idx = np.asarray(ring_sample_windows(jax.random.PRNGKey(0), env_idx, pos, valid, CAP, seq))
    assert t_idx.min() >= 0
    assert t_idx.max() <= 6  # last valid start = 7 - 3 = 4 -> max index 6


# -- episode-rule sampling (Dreamer-V2 buffer.type=episode on the ring) -------


def _episode_ring(first_rows, cap, n_envs=1):
    """An is_first channel as the ring stores it, (cap, n_envs, 1, 1), with
    1s at the given rows."""
    f = np.zeros((cap, n_envs, 1, 1), np.float32)
    for r in first_rows:
        f[r, :] = 1.0
    return jnp.asarray(f)


def test_episode_windows_never_contain_interior_boundary():
    from sheeprl_tpu.data.ring import ring_sample_windows_episode

    cap, seq = 16, 4
    # episodes start at rows 0, 5, 9 in a 12-row valid prefix
    is_first = _episode_ring([0, 5, 9], cap)
    pos = jnp.asarray([12], jnp.int32)
    valid = jnp.asarray([12], jnp.int32)
    env_idx = jnp.zeros((512,), jnp.int32)
    firsts = {0, 5, 9}
    for s in range(10):
        t_idx = np.asarray(
            ring_sample_windows_episode(jax.random.PRNGKey(s), env_idx, pos, valid, is_first, cap, seq)
        )
        for b in range(t_idx.shape[1]):
            window = t_idx[:, b].tolist()
            # boundary rows may appear only as the window's FIRST element
            for w in window[1:]:
                assert w not in firsts, (window, s)
            # and the sequential prefix rule still holds (valid rows 0..11,
            # max start 12-4=8 -> max index 11)
            assert max(window) <= 11


def test_episode_windows_cover_all_valid_starts():
    from sheeprl_tpu.data.ring import ring_sample_windows_episode

    cap, seq = 16, 3
    is_first = _episode_ring([0, 6], cap)
    pos = jnp.asarray([12], jnp.int32)
    valid = jnp.asarray([12], jnp.int32)
    env_idx = jnp.zeros((2048,), jnp.int32)
    t_idx = np.asarray(
        ring_sample_windows_episode(jax.random.PRNGKey(1), env_idx, pos, valid, is_first, cap, seq)
    )
    starts = set(t_idx[0].tolist())
    # valid starts: episode A rows 0..3 (windows end before 6), episode B rows
    # 6..9 (end before head 12); rows 4,5 would straddle the boundary at 6
    assert starts == {0, 1, 2, 3, 6, 7, 8, 9}, starts


def test_episode_sampling_falls_back_when_no_boundary_free_window():
    from sheeprl_tpu.data.ring import ring_sample_windows_episode

    cap, seq = 16, 4
    # every episode is 2 rows long -> no boundary-free window of length 4
    is_first = _episode_ring([0, 2, 4, 6, 8, 10, 12, 14], cap)
    pos = jnp.asarray([16], jnp.int32)
    valid = jnp.asarray([16], jnp.int32)
    env_idx = jnp.zeros((128,), jnp.int32)
    t_idx = np.asarray(
        ring_sample_windows_episode(jax.random.PRNGKey(2), env_idx, pos, valid, is_first, cap, seq)
    )
    # falls back to the sequential rule rather than emitting NaN/garbage
    assert t_idx.min() >= 0 and t_idx[0].max() <= 16 - seq


def test_episode_windows_respect_wrapped_ring():
    from sheeprl_tpu.data.ring import ring_sample_windows_episode

    cap, seq = 10, 3
    # full ring, head at 6; episode boundary at row 9 (inside the wrapped
    # valid range 6,7,...,9,0,...,5)
    is_first = _episode_ring([9], cap)
    pos = jnp.asarray([6], jnp.int32)
    valid = jnp.asarray([cap], jnp.int32)
    env_idx = jnp.zeros((1024,), jnp.int32)
    t_idx = np.asarray(
        ring_sample_windows_episode(jax.random.PRNGKey(3), env_idx, pos, valid, is_first, cap, seq)
    )
    for b in range(t_idx.shape[1]):
        window = t_idx[:, b].tolist()
        for w in window[1:]:
            assert w != 9  # never interior
        for a, bb in zip(window[:-1], window[1:]):
            assert not (a == 5 and bb == 6)  # never straddles the head
