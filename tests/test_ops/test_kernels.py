"""Pallas kernel tier vs the lax references (howto/kernels.md).

Every registered kernel: forward allclose + gradients via ``custom_vjp``
against ``jax.grad`` of the reference (f32 and bf16, interpret mode on the
CPU test mesh), registry dispatch semantics (auto/pallas/lax, per-kernel
override, named errors), and one-entry jit caches under both backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops import kernels as K
from sheeprl_tpu.replay import sumtree as st

EXPECTED_KERNELS = (
    "gae",
    "gru_gates",
    "moe_grouped_ffn",
    "ragged_ring_scatter",
    "sumtree_sample",
    "two_hot_symexp_decode",
    "two_hot_symlog_loss",
    "window_attention",
)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _norm_logits(rng, shape, dtype=np.float32):
    logits = jnp.asarray(rng.normal(size=shape).astype(dtype))
    return logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)


def _symlog_preimage(b):
    """A float32 whose symlog is EXACTLY ``b`` (searched around symexp(b)),
    or None where symlog steps over ``b`` there."""
    from sheeprl_tpu.ops.core import symexp, symlog

    near = np.float32(symexp(jnp.float32(b)))
    candidates = [near]
    for toward in (np.float32(np.inf), np.float32(-np.inf)):
        v = near
        for _ in range(64):
            v = np.nextafter(v, toward)
            candidates.append(v)
    candidates = np.asarray(candidates, np.float32)
    hits = candidates[np.asarray(symlog(jnp.asarray(candidates))) == np.float32(b)]
    return float(hits[0]) if hits.size else None


def _two_hot_values(rng, lead, num_buckets, kind, low=-20.0, high=20.0):
    """Targets ``lead + (1,)`` for the two-hot loss. ``random``: between bins.
    ``edges``: besides, values that symlog puts exactly ON a bin of the
    reference's support (``low`` and ``high`` among them), zero, and values
    beyond the support on both sides."""
    n = int(np.prod(lead))
    values = rng.normal(size=(n,)).astype(np.float32) * 4
    if kind == "edges":
        bins = np.asarray(jnp.linspace(low, high, num_buckets, dtype=jnp.float32))
        picks = sorted({0, 1, num_buckets // 3, num_buckets // 2, 2 * num_buckets // 3, num_buckets - 2, num_buckets - 1})
        on_a_bin = [v for v in (_symlog_preimage(bins[k]) for k in picks) if v is not None]
        assert len(on_a_bin) >= 5 and on_a_bin[0] < -4e8 and on_a_bin[-1] > 4e8  # low and high themselves
        edges = on_a_bin + [0.0, 1e9, -1e9, 6e8, -6e8]  # symexp(20) is 4.85e8
        assert n > len(edges)
        values[: len(edges)] = edges
    return jnp.asarray(values).reshape(lead + (1,))


def _gae_inputs(rng, T=16, B=6, trailing=(1,), dtype=np.float32):
    shape = (T, B) + trailing
    r = jnp.asarray(rng.normal(size=shape).astype(dtype))
    v = jnp.asarray(rng.normal(size=shape).astype(dtype))
    d = (jnp.asarray(rng.uniform(size=shape)) < 0.15).astype(jnp.float32)
    nv = jnp.asarray(rng.normal(size=shape[1:]).astype(dtype))
    return r, v, d, nv


def _tree(rng, leaves=64, filled=40):
    tree = st.init(leaves)
    pri = jnp.asarray(rng.uniform(0.1, 2.0, size=(filled,)).astype(np.float32))
    return st.update(tree, jnp.arange(filled), pri)


def _ring_case(rng, C=8, E=5, S=4, e=3, feat=(2,), dtype=np.float32, stored=True):
    """A ragged append: heads that wrap, slots that are dropped. ``stored``:
    ring and staged rows in the ring's stored view (``data.ring.ring_cell``),
    as the kernel takes them; else in the env's shape ``feat``."""
    from sheeprl_tpu.data.ring import ring_append_rows, ring_view

    heads = [1, C - 1, 3, 0][:e]  # includes a wrapping head
    pos = jnp.asarray(heads, jnp.int32)
    valid = jnp.asarray(heads, jnp.int32)
    mask = jnp.asarray([[1, 1, 1, 0], [1, 0, 1, 1], [0, 0, 1, 0], [1, 0, 0, 1]], jnp.int32)[:S, :e]
    row, _, _ = ring_append_rows(pos, valid, mask, C)
    storage = jnp.asarray((rng.normal(size=(C, E) + feat) * 50).astype(dtype))
    staged = jnp.asarray((rng.normal(size=(S, e) + feat) * 50).astype(dtype))
    if stored:
        storage, staged = ring_view(storage, feat), ring_view(staged, feat)
    return storage, staged, row, pos


# ---------------------------------------------------------------------------
# registry dispatch semantics
# ---------------------------------------------------------------------------


def test_registry_names():
    assert K.names() == EXPECTED_KERNELS


def test_auto_resolves_to_lax_on_cpu():
    # the CPU test mesh: auto must keep the plain-lax references
    with K.use_backend("auto"):
        for name in K.names():
            assert K.resolve(name) == "lax"
            assert K.dispatch(name).__wrapped__ is K.get(name).reference


def test_global_backend_switch():
    with K.use_backend("pallas"):
        assert all(K.resolve(n) == "pallas" for n in K.names())
        assert K.dispatch("gru_gates").__wrapped__ is K.get("gru_gates").pallas
    with K.use_backend("lax"):
        assert all(K.resolve(n) == "lax" for n in K.names())


def test_per_kernel_override_beats_global():
    with K.use_backend("pallas", gae="lax"):
        assert K.resolve("gae") == "lax"
        assert K.resolve("gru_gates") == "pallas"
    with K.use_backend("lax", sumtree_sample="pallas"):
        assert K.resolve("sumtree_sample") == "pallas"
        assert K.resolve("gae") == "lax"


def test_per_call_backend_beats_everything():
    with K.use_backend("lax", gae="lax"):
        assert K.resolve("gae", backend="pallas") == "pallas"


def test_unknown_backend_named_error():
    with pytest.raises(K.UnknownOpsBackendError, match="tpu-magic"):
        K.configure(backend="tpu-magic")
    with pytest.raises(K.UnknownOpsBackendError, match="gae"):
        K.configure(overrides={"gae": "cuda"})
    with pytest.raises(K.UnknownOpsBackendError):
        K.resolve("gae", backend="nope")


def test_unknown_kernel_named_error():
    with pytest.raises(K.UnknownKernelError, match="flash_attention"):
        K.get("flash_attention")
    with pytest.raises(K.UnknownKernelError):
        K.configure(overrides={"flash_attention": "pallas"})


def test_configure_from_config_and_env_shape():
    cfg = {"backend": "lax", "kernels": {"gae": "pallas"}}
    with K.use_backend():  # snapshot/restore
        K.configure_from_config(cfg)
        assert K.backend() == "lax"
        assert K.resolve("gae") == "pallas"
        K.configure_from_config(None)  # missing block is a no-op
        assert K.backend() == "lax"


def test_ops_gae_export_goes_through_registry():
    import sheeprl_tpu.ops as ops

    assert ops.gae is K.gae


def test_pallas_gru_shim_is_the_pallas_variant():
    from sheeprl_tpu.ops import pallas_gru

    assert pallas_gru.gru_gates is K.gru_gates_pallas
    assert pallas_gru.gru_gates_reference is K.gru_gates_reference


# ---------------------------------------------------------------------------
# forward + gradient parity (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gru_gates_parity(dtype):
    rng = _rng(1)
    fused = jnp.asarray(rng.normal(size=(7, 48)).astype(np.float32), dtype=dtype)
    h = jnp.asarray(rng.normal(size=(7, 16)).astype(np.float32), dtype=dtype)
    got = K.gru_gates(fused, h, backend="pallas")
    want = K.gru_gates_reference(fused, h)
    assert got.dtype == want.dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_gru_gates_grad_parity():
    rng = _rng(2)
    fused = jnp.asarray(rng.normal(size=(5, 24)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(5, 8)).astype(np.float32))
    g_got = jax.grad(lambda f, c: jnp.sum(K.gru_gates(f, c, backend="pallas") ** 2), (0, 1))(fused, h)
    g_want = jax.grad(lambda f, c: jnp.sum(K.gru_gates_reference(f, c) ** 2), (0, 1))(fused, h)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


# the loss in every shape it has to serve: K odd and even, few rows and a
# count that is no multiple of 8 or 128, targets between bins and on every
# edge of the two-hot
_TWO_HOT_LOSS_CASES = [
    pytest.param((6, 255), "random", id="flat"),
    pytest.param((3, 4, 63), "random", id="batched"),
    pytest.param((7, 41), "random", id="41-buckets"),
    pytest.param((24, 255), "edges", id="edges"),
    pytest.param((4, 6, 64), "edges", id="edges-even-buckets"),
    pytest.param((300, 255), "edges", id="edges-300-rows"),
]


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape,values", _TWO_HOT_LOSS_CASES)
def test_two_hot_symlog_loss_parity(dtype, shape, values):
    rng = _rng(3)
    logits = _norm_logits(rng, shape).astype(dtype)
    value = _two_hot_values(rng, shape[:-1], shape[-1], values).astype(dtype)
    got = K.two_hot_symlog_loss(logits, value, backend="pallas")
    want = K.two_hot_symlog_loss_reference(logits, value)
    assert got.shape == want.shape and got.dtype == want.dtype
    if dtype == jnp.bfloat16:
        # the kernel computes in f32 and casts at the boundary, so its truth
        # is the f32 reference (bf16-quantized bins can shift the two-hot
        # indices in the all-bf16 lax chain; see the GRU bf16 test)
        want = K.two_hot_symlog_loss_reference(
            logits.astype(jnp.float32), value.astype(jnp.float32)
        )
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=2e-2, atol=5e-2)
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,values", _TWO_HOT_LOSS_CASES)
def test_two_hot_symlog_loss_grad_parity(shape, values):
    # the backward is a closed form of the hat, not the reference's chain:
    # both arguments, a cotangent that differs by row
    rng = _rng(4)
    logits = _norm_logits(rng, shape)
    value = _two_hot_values(rng, shape[:-1], shape[-1], values)
    ct = jnp.asarray(rng.normal(size=shape[:-1]).astype(np.float32))
    g_got = jax.grad(lambda l, v: (K.two_hot_symlog_loss(l, v, backend="pallas") * ct).sum(), (0, 1))(logits, value)
    g_want = jax.grad(lambda l, v: (K.two_hot_symlog_loss_reference(l, v) * ct).sum(), (0, 1))(logits, value)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_two_hot_symexp_decode_parity(dtype):
    rng = _rng(5)
    logits = _norm_logits(rng, (6, 255)).astype(dtype)
    got = K.two_hot_symexp_decode(logits, backend="pallas")
    want = K.two_hot_symexp_decode_reference(logits)
    assert got.shape == want.shape and got.dtype == want.dtype
    if dtype == jnp.bfloat16:
        want = K.two_hot_symexp_decode_reference(logits.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=5e-2, atol=5e-2)
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_two_hot_symexp_decode_grad_parity():
    rng = _rng(6)
    logits = _norm_logits(rng, (6, 63))
    g_got = jax.grad(lambda l: K.two_hot_symexp_decode(l, backend="pallas").sum())(logits)
    g_want = jax.grad(lambda l: K.two_hot_symexp_decode_reference(l).sum())(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("trailing", [(1,), ()], ids=["TB1", "TB"])
def test_gae_parity(dtype, trailing):
    rng = _rng(7)
    r, v, d, nv = _gae_inputs(rng, trailing=trailing, dtype=np.float32)
    r, v, nv = (x.astype(dtype) for x in (r, v, nv))
    ret_p, adv_p = K.gae(r, v, d, nv, 0.99, 0.95, backend="pallas")
    ret_l, adv_l = K.gae(r, v, d, nv, 0.99, 0.95, backend="lax")
    assert ret_p.dtype == ret_l.dtype == jnp.float32  # f32 accumulation both ways
    np.testing.assert_allclose(np.asarray(ret_p), np.asarray(ret_l), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(adv_p), np.asarray(adv_l), rtol=1e-6, atol=1e-6)


def test_gae_grad_parity():
    rng = _rng(8)
    r, v, d, nv = _gae_inputs(rng)

    def loss(backend, r_, v_, nv_):
        ret, adv = K.gae(r_, v_, d, nv_, 0.99, 0.95, backend=backend)
        return (ret * adv).sum()

    g_got = jax.grad(lambda *a: loss("pallas", *a), (0, 1, 2))(r, v, nv)
    g_want = jax.grad(lambda *a: loss("lax", *a), (0, 1, 2))(r, v, nv)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_sumtree_sample_parity():
    rng = _rng(9)
    tree = _tree(rng)
    u = jnp.asarray(rng.uniform(size=(17,)).astype(np.float32))
    n_valid = jnp.asarray(40, jnp.int32)
    beta = jnp.asarray(0.4, jnp.float32)
    leaf_p, w_p = K.sumtree_sample(tree, u, n_valid, beta, backend="pallas")
    leaf_l, w_l = K.sumtree_sample(tree, u, n_valid, beta, backend="lax")
    np.testing.assert_array_equal(np.asarray(leaf_p), np.asarray(leaf_l))
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_l), rtol=1e-6, atol=1e-7)


def test_sumtree_sample_grad_parity():
    rng = _rng(10)
    tree = _tree(rng)
    u = jnp.asarray(rng.uniform(size=(9,)).astype(np.float32))
    n_valid = jnp.asarray(40, jnp.int32)

    def loss(backend, tree_, beta_):
        return K.sumtree_sample(tree_, u, n_valid, beta_, backend=backend)[1].sum()

    beta = jnp.asarray(0.4, jnp.float32)
    g_got = jax.grad(lambda t, b: loss("pallas", t, b), (0, 1))(tree, beta)
    g_want = jax.grad(lambda t, b: loss("lax", t, b), (0, 1))(tree, beta)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "dtype", [np.float32, jnp.bfloat16, np.uint8], ids=["f32", "bf16", "u8"]
)
@pytest.mark.parametrize(
    "feat,E,e,off",
    [((2,), 5, 3, 1), ((), 5, 3, 1), ((8, 16, 3), 5, 3, 1), ((18,), 4, 2, 2), ((4, 32), 4, 4, 0)],
    ids=["feature", "scalar", "pixel", "sebulba-vector", "one-lane-row"],
)
def test_ragged_ring_scatter_parity(dtype, feat, E, e, off):
    """Both tiers on the ring's stored view against the literal env-shaped
    scatter: a ``(1, feat)`` cell (vectors, scalars), a ``(feat // 128, 128)``
    cell (pixels), heads that wrap, dropped slots, and a Sebulba append of 2
    env columns at offset 2 of 4."""
    from sheeprl_tpu.data.ring import env_view

    def cast(x):
        return (jnp.abs(x) * 20).astype(jnp.uint8) if dtype == np.uint8 else x.astype(dtype)

    storage, staged, row, pos = _ring_case(_rng(11), E=E, e=e, feat=feat)
    storage, staged = cast(storage), cast(staged)
    env_storage, env_staged, _, _ = _ring_case(_rng(11), E=E, e=e, feat=feat, stored=False)
    off = jnp.asarray(off, jnp.int32)
    cols = off + jnp.broadcast_to(jnp.arange(e)[None, :], row.shape)
    want = cast(env_storage).at[row, cols].set(cast(env_staged), mode="drop")
    assert storage.ndim == 4 and storage.shape[:2] == want.shape[:2]
    # a scatter copies values: parity is exact for every dtype
    for backend in ("pallas", "lax"):
        got = K.ragged_ring_scatter(storage, staged, row, pos, off, backend=backend)
        assert got.shape == storage.shape
        np.testing.assert_array_equal(np.asarray(env_view(got, feat)), np.asarray(want), err_msg=backend)


def test_ragged_ring_scatter_all_dropped_column():
    """An env whose every slot is masked out must keep its column untouched
    (the dropped slots park on (pos-1) % C and write the old value back)."""
    rng = _rng(12)
    from sheeprl_tpu.data.ring import ring_append_rows

    C, S, e = 6, 3, 2
    pos = jnp.asarray([0, 4], jnp.int32)
    valid = jnp.asarray([0, 4], jnp.int32)
    mask = jnp.asarray([[0, 1], [0, 1], [0, 0]], jnp.int32)
    row, _, _ = ring_append_rows(pos, valid, mask, C)
    storage = jnp.asarray(rng.normal(size=(C, e, 1, 3)).astype(np.float32))
    staged = jnp.asarray(rng.normal(size=(S, e, 1, 3)).astype(np.float32))
    got = K.ragged_ring_scatter(storage, staged, row, pos, 0, backend="pallas")
    want = K.ragged_ring_scatter(storage, staged, row, pos, 0, backend="lax")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(storage[:, 0]))


def test_ragged_ring_scatter_grad_parity():
    rng = _rng(13)
    storage, staged, row, pos = _ring_case(rng)
    off = jnp.asarray(1, jnp.int32)

    def loss(backend, s, t):
        return (K.ragged_ring_scatter(s, t, row, pos, off, backend=backend) ** 2).sum()

    g_got = jax.grad(lambda s, t: loss("pallas", s, t), (0, 1))(storage, staged)
    g_want = jax.grad(lambda s, t: loss("lax", s, t), (0, 1))(storage, staged)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# jit stability: one cache entry per kernel under both backends
# ---------------------------------------------------------------------------


def _kernel_calls():
    rng = _rng(14)
    fused = jnp.asarray(rng.normal(size=(7, 24)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(7, 8)).astype(np.float32))
    logits = _norm_logits(rng, (6, 63))
    value = jnp.asarray(rng.normal(size=(6, 1)).astype(np.float32))
    r, v, d, nv = _gae_inputs(rng, T=8, B=4)
    tree = _tree(rng, leaves=32, filled=20)
    u = jnp.asarray(rng.uniform(size=(5,)).astype(np.float32))
    storage, staged, row, pos = _ring_case(rng)
    return {
        "gru_gates": (lambda b: lambda f_, h_: K.gru_gates(f_, h_, backend=b), (fused, h)),
        "two_hot_symlog_loss": (
            lambda b: lambda l_, v_: K.two_hot_symlog_loss(l_, v_, backend=b), (logits, value)
        ),
        "two_hot_symexp_decode": (
            lambda b: lambda l_: K.two_hot_symexp_decode(l_, backend=b), (logits,)
        ),
        "gae": (lambda b: lambda *a: K.gae(*a, 0.99, 0.95, backend=b), (r, v, d, nv)),
        "sumtree_sample": (
            lambda b: lambda t_, u_: K.sumtree_sample(t_, u_, jnp.asarray(20, jnp.int32), jnp.asarray(0.4, jnp.float32), backend=b),
            (tree, u),
        ),
        "ragged_ring_scatter": (
            lambda b: lambda s_, t_: K.ragged_ring_scatter(s_, t_, row, pos, 1, backend=b),
            (storage, staged),
        ),
    }


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_cache_size_one_per_kernel(backend):
    for name, (make, args) in _kernel_calls().items():
        jitted = jax.jit(make(backend))
        jax.block_until_ready(jitted(*args))
        jax.block_until_ready(jitted(*args))
        assert jitted._cache_size() == 1, f"{name} retraced under backend={backend}"


# ---------------------------------------------------------------------------
# what a process that holds a TPU gets (chipless: the TPU is pretended, the
# TPU compiler is libtpu's own, asked through an AOT topology)
# ---------------------------------------------------------------------------


@pytest.fixture()
def pretend_tpu(monkeypatch):
    from sheeprl_tpu.ops.kernels import registry

    monkeypatch.setattr(registry, "_process_has_tpu", lambda: True)


def _kernel_cases(rng):
    """(name, args) at call-site shapes: Dreamer-S scans and heads, the
    PPO-Anakin rollout, PER, and ring appends with ONE and with SEVERAL env
    columns."""
    f32 = np.float32
    tree = _tree(rng, leaves=64, filled=40)
    u = jnp.asarray(rng.uniform(size=(8,)).astype(f32))
    yield "gru_gates", (jnp.ones((16, 3 * 512), f32), jnp.ones((16, 512), f32))
    yield "two_hot_symlog_loss", (_norm_logits(rng, (64, 16, 255)), jnp.ones((64, 16, 1), f32))
    yield "two_hot_symexp_decode", (_norm_logits(rng, (16, 64, 255)),)
    yield "gae", _gae_inputs(rng, T=128, B=4) + (0.99, 0.95)
    yield "sumtree_sample", (tree, u, jnp.int32(40), jnp.float32(0.4))
    yield "ragged_ring_scatter", _ring_case(rng, C=8, E=3, S=4, e=3, feat=(18,))
    yield "ragged_ring_scatter", _ring_case(rng, C=8, E=1, S=4, e=1, feat=(64, 64, 3), dtype=np.uint8)
    yield "ragged_ring_scatter", _ring_case(rng, C=8, E=3, S=4, e=3, feat=(64, 64, 3), dtype=np.uint8)
    # the decoder policy: one sequence's sorted assignments over held experts at uneven loads (one empty), and
    # grouped-query attention at the published head size, whole and windowed
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(f32))  # noqa: E731
    yield "moe_grouped_ffn", (normal(1536, 256), normal(4, 256, 128), normal(4, 256, 128), normal(4, 128, 256),
                              jnp.asarray([0, 700, 90, 10], jnp.int32))
    yield "window_attention", (normal(1, 1024, 14, 128), normal(1, 1024, 2, 128), normal(1, 1024, 2, 128), 0)
    yield "window_attention", (normal(1, 1024, 14, 128), normal(1, 1024, 2, 128), normal(1, 1024, 2, 128), 256)
    # latent attention's expanded form: a key-value head a query head, 192-wide queries and keys, 128-wide values
    yield "window_attention", (normal(1, 1024, 4, 192), normal(1, 1024, 4, 192), normal(1, 1024, 4, 128), 0)
    yield "moe_grouped_ffn", (normal(1536, 256), normal(4, 256, 128), normal(4, 256, 128), normal(4, 128, 256),
                              jnp.asarray([0, 700, 90, 10], jnp.int32), "silu")


def _split_statics(args):
    arrays = tuple(a for a in args if isinstance(a, jax.Array))
    statics = tuple(a for a in args if not isinstance(a, jax.Array))
    return arrays, statics


def test_auto_on_a_tpu_is_pallas_except_the_kernels_routed_by_name(pretend_tpu):
    with K.use_backend("auto"):
        for name in K.names():
            want = "lax" if name in K.AUTO_LAX_ON_TPU else "pallas"
            assert K.resolve(name) == want and K.tier(name) == ("xla" if name in K.COMPILED_BY_XLA else want)
    assert set(K.AUTO_LAX_ON_TPU) == {"sumtree_sample"} and set(K.COMPILED_BY_XLA) == {"two_hot_symlog_loss"}
    with K.use_backend("pallas"):  # an explicit choice still reaches the kernel
        assert K.resolve("sumtree_sample") == "pallas"


def test_interpret_tier_is_named_without_a_tpu():
    with K.use_backend("pallas"):
        assert all(K.tier(name) == ("xla" if name in K.COMPILED_BY_XLA else "pallas-interpret") for name in K.names())


def test_cpu_lowering_in_a_tpu_process_takes_the_reference(pretend_tpu):
    # the hybrid host player's case: Pallas tier selected, lowering for CPU
    for name, args in dict(_kernel_cases(_rng(7))).items():  # one case per kernel
        kernel = K.get(name)
        arrays, statics = _split_statics(args)
        pallas = jax.jit(lambda *xs, _f=kernel.pallas, _s=statics: _f(*xs, *_s))
        reference = jax.jit(lambda *xs, _f=kernel.reference, _s=statics: _f(*xs, *_s))
        assert "custom_call" not in pallas.lower(*arrays).as_text(), name  # neither Mosaic nor the interpreter
        for got, want in zip(jax.tree.leaves(pallas(*arrays)), jax.tree.leaves(reference(*arrays))):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)


@pytest.fixture(scope="module")
def tpu_topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU compiler to ask: {e}")


def _compile_for_tpu(topology, fn, arrays):
    sharding = jax.sharding.SingleDeviceSharding(topology.devices[0])
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding) for a in arrays]
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",)).compile()


def test_every_pallas_kernel_compiles_for_tpu(pretend_tpu, tpu_topology):
    """The kernel AS DISPATCHED (custom_vjp, platform choice and all), at its
    call-site shapes, through the TPU compiler — no chip needed. Kernels the
    registry routes to lax by name must still be refused, or the routing is
    stale."""
    for name, args in _kernel_cases(_rng(8)):
        arrays, statics = _split_statics(args)
        fn = lambda *xs, _f=K.get(name).pallas, _s=statics: _f(*xs, *_s)  # noqa: E731
        if name in K.AUTO_LAX_ON_TPU:
            with pytest.raises(Exception, match="Shape mismatch in input, indices and output"):
                _compile_for_tpu(tpu_topology, fn, arrays)
            continue
        compiled = _compile_for_tpu(tpu_topology, fn, arrays)
        assert ("tpu_custom_call" in compiled.as_text()) == (name not in K.COMPILED_BY_XLA), name


@pytest.mark.parametrize("argnums,most", [(0, 4), ((0, 1), 8)], ids=["d-logits", "d-logits-d-value"])
def test_two_hot_loss_backward_for_tpu_is_a_closed_form(pretend_tpu, tpu_topology, argnums, most):
    """The loss and its gradient at the critic's shape (horizon 15 x 1024
    rows x 255 bins), as dispatched, through the TPU compiler: a handful of
    fusions, the contraction and ``d logits`` in one, and the support a
    constant. The reference's chain differentiated is 23 launches for
    ``d logits`` alone: the support rebuilt by ``_linspace``, two gathers
    over ``f32[15360]``, clamps, compare/selects."""
    import re

    f32 = jnp.float32
    avals = (jax.ShapeDtypeStruct((15, 1024, 255), f32), jax.ShapeDtypeStruct((15, 1024, 1), f32))
    step = jax.value_and_grad(lambda logits, value: K.two_hot_symlog_loss(logits, value).sum(), argnums)
    text = _compile_for_tpu(tpu_topology, step, avals).as_text()
    entry = [line for line in text[text.index("\nENTRY ") :].splitlines() if " = " in line]
    free = re.compile(r" (constant|parameter|bitcast|get-tuple-element|copy-start|copy-done)\(")
    launched = [line for line in entry if not free.search(line)]
    assert any("kernel.two_hot_symlog_loss" in line for line in launched), launched  # the scope the trace is read by
    assert "_linspace" not in text and " gather(" not in text and "custom-call" not in text
    assert len(launched) <= most, launched


@pytest.mark.parametrize("stored", [True, False], ids=["stored-view", "env-shaped-ring"])
def test_pixel_ring_append_for_tpu_rewrites_no_whole_ring(pretend_tpu, tpu_topology, stored):
    """What the CPU cannot see: under the TPU's tiled layouts the donated
    append of a pixel ring kept in its stored view compiles to the Mosaic call
    alone, and the same ring kept env-shaped (``u8[C, 1, 64, 64, 3]``,
    reshaped around the call, as until PR 28) is copied whole, several times."""
    from sheeprl_tpu.analysis.hlo import whole_array_relayouts
    from sheeprl_tpu.data.ring import env_view, ring_cell, ring_view

    C, S, shape = 4096, 19, (64, 64, 3)
    sharding = jax.sharding.SingleDeviceSharding(tpu_topology.devices[0])

    def aval(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def append(ring, staged, row, pos):
        if stored:
            return K.get("ragged_ring_scatter").pallas(ring, staged, row, pos)
        out = K.get("ragged_ring_scatter").pallas(ring_view(ring, shape), ring_view(staged, shape), row, pos)
        return env_view(out, shape)

    tail = ring_cell(shape) if stored else shape
    avals = (aval((C, 1) + tail, jnp.uint8), aval((S, 1) + tail, jnp.uint8), aval((S, 1), jnp.int32), aval((1,), jnp.int32))
    text = jax.jit(append, donate_argnums=(0,)).trace(*avals).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    rewrites = whole_array_relayouts(text, C)
    assert (rewrites == []) if stored else (len(rewrites) >= 2), rewrites


@pytest.mark.parametrize(
    "hidden,dtype",
    [(512, jnp.float32), (1024, jnp.float32), (2048, jnp.float32), (4096, jnp.float32), (4096, jnp.bfloat16)],
    ids=["S", "M", "L", "XL", "XL-bf16"],
)
def test_gru_gates_fits_scoped_vmem_at_every_configured_width(pretend_tpu, tpu_topology, hidden, dtype):
    # the imagination scan's rows: batch 16 x sequence 64
    arrays = (jnp.zeros((1024, 3 * hidden), dtype), jnp.zeros((1024, hidden), dtype))
    _compile_for_tpu(tpu_topology, K.get("gru_gates").pallas, arrays)


def test_gru_gates_block_that_cannot_fit_is_named():
    from sheeprl_tpu.ops.kernels import gru

    assert gru._block_rows(16, 512, 4) == 16  # a batch under one block is one block
    with pytest.raises(ValueError, match="H=1048576"):
        gru._block_rows(1024, 1 << 20, 4)
