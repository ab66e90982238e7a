"""The decoder policy's two kernels against dense references: forward and
every gradient, at uneven loads, the lax tier and (in interpret mode) the
library kernels of the TPU tier."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops import kernels as K
from sheeprl_tpu.ops.kernels import attn, moe


def _dense_attention(q, k, v, window):
    B, T, Hq, D = q.shape
    g = Hq // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D**-0.5
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    if window:
        mask &= i[:, None] - i[None, :] < window
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)


def _qkv(T, Hq=4, Hkv=2, D=16, B=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, h, D)), jnp.float32) for h in (Hq, Hkv, Hkv))


@pytest.mark.parametrize("window", [0, 8, 11, 64], ids=["full", "window-8", "window-11", "window-over-seq"])
def test_window_attention_lax_tier_forward_and_gradients(window, monkeypatch):
    monkeypatch.setattr(attn, "BLOCK", 8)  # 4 blocks of 8: the window reaches 2 or 3 of them
    q, k, v = _qkv(32)
    np.testing.assert_allclose(K.window_attention_reference(q, k, v, window), _dense_attention(q, k, v, window), atol=2e-6)
    ours = jax.grad(lambda *a: jnp.sum(jnp.sin(K.window_attention_reference(*a, window))), (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense_attention(*a, window))), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, dense):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_window_attention_never_builds_a_whole_score_matrix():
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((1, 2048, 4, 16), (1, 2048, 2, 16), (1, 2048, 2, 16)))
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(K.window_attention_reference(q, k, v, 512)), (0, 1, 2))).lower(q, k, v).as_text()
    assert "2048x2048" not in text and "512x512" in text


@pytest.mark.parametrize("window", [0, 128], ids=["full", "window"])
def test_window_attention_kernel_tier_in_interpret_mode(window):
    q, k, v = _qkv(256, D=128, B=1)
    got = attn._splash(q, k, v, window, interpret=True)
    want = _dense_attention(q, k, v, window)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2  # bfloat16 operands
    ours = jax.grad(lambda *a: jnp.sum(attn._splash(*a, window, interpret=True) * want), (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(_dense_attention(*a, window) * want), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, dense):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-2 * (1 + float(jnp.max(jnp.abs(b))))


def test_window_attention_dispatch():
    q, k, v = _qkv(16)
    with K.use_backend("lax"):
        np.testing.assert_allclose(K.window_attention(q, k, v, 4), _dense_attention(q, k, v, 4), atol=2e-6)


# -- the grouped expert feed-forward -------------------------------------------
LOADS = {"even": [64, 64, 64, 64], "an-empty-expert": [0, 200, 20, 4], "one-takes-most": [250, 2, 2, 2],
         "rows-held-elsewhere": [30, 0, 100, 10]}


def _moe_case(loads, H=128, F=128, M=256, seed=0):
    rng = np.random.default_rng(seed)
    E = len(loads)
    xs = jnp.asarray(rng.normal(size=(M, H)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32) for s in ((E, H, F), (E, H, F), (E, F, H))]
    return xs, *w, jnp.asarray(loads, jnp.int32)


def _dense_moe(xs, wg, wu, wd, loads):
    out, start = jnp.zeros_like(xs), 0
    for e, n in enumerate(loads):
        x = xs[start : start + n]
        out = out.at[start : start + n].set((jax.nn.relu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        start += n
    return out


@pytest.mark.parametrize("loads", list(LOADS.values()), ids=list(LOADS))
def test_moe_grouped_ffn_lax_tier_forward_and_gradients(loads):
    xs, wg, wu, wd, sizes = _moe_case(loads)
    want = _dense_moe(xs, wg, wu, wd, loads)
    np.testing.assert_allclose(K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes), want, rtol=1e-4, atol=1e-4)
    assert not np.asarray(K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes))[sum(loads):].any()  # rows of no held expert
    ours = jax.grad(lambda *a: jnp.sum(jnp.sin(K.moe_grouped_ffn_reference(*a, sizes))), (0, 1, 2, 3))(xs, wg, wu, wd)
    dense = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense_moe(*a, loads))), (0, 1, 2, 3))(xs, wg, wu, wd)
    for a, b in zip(ours, dense):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("loads", [LOADS["an-empty-expert"], LOADS["rows-held-elsewhere"]], ids=["an-empty-expert", "rows-held-elsewhere"])
def test_moe_grouped_ffn_kernel_tier_in_interpret_mode(loads):
    xs, wg, wu, wd, sizes = _moe_case(loads)
    want = _dense_moe(xs, wg, wu, wd, loads)
    got = moe._grouped_ffn_gmm(xs, wg, wu, wd, sizes, interpret=True)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * scale  # bfloat16 operands
    assert not np.asarray(got)[sum(loads):].any()  # the kernel leaves them unwritten: zeroed, not NaN
    ours = jax.grad(lambda *a: jnp.sum(moe._grouped_ffn_gmm(*a, sizes, interpret=True) * want), (0, 1, 2, 3))(xs, wg, wu, wd)
    dense = jax.grad(lambda *a: jnp.sum(_dense_moe(*a, loads) * want), (0, 1, 2, 3))(xs, wg, wu, wd)
    for a, b in zip(ours, dense):
        assert bool(jnp.isfinite(a).all())
        # in norm: a gate next to zero flips its relu under bfloat16 rounding, which moves single elements a lot
        assert float(jnp.linalg.norm(a - b)) < 3e-2 * float(jnp.linalg.norm(b))


def test_moe_tile_choice():
    assert moe._tile(2560, 1024) == 640 and moe._tile(768, 1024) == 768 and moe._tile(48, 512) == 48


# -- values of their own head size (latent attention's expanded form: q and k wider than v) ---------------
def _qkv_narrow_v(T, Hq=4, Hkv=4, D=24, Dv=16, B=2, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, h, d)), jnp.float32) for h, d in ((Hq, D), (Hkv, D), (Hkv, Dv)))


@pytest.mark.parametrize("heads", [(4, 4), (4, 2)], ids=["a-key-value-head-a-query-head", "grouped"])
@pytest.mark.parametrize("window", [0, 11], ids=["full", "window-11"])
def test_window_attention_lax_tier_with_narrower_values(window, heads, monkeypatch):
    monkeypatch.setattr(attn, "BLOCK", 8)
    q, k, v = _qkv_narrow_v(32, *heads)
    out = K.window_attention_reference(q, k, v, window)
    assert out.shape == (2, 32, 4, 16)  # the values' head size is the output's
    np.testing.assert_allclose(out, _dense_attention(q, k, v, window), atol=2e-6)
    ours = jax.grad(lambda *a: jnp.sum(jnp.sin(K.window_attention_reference(*a, window))), (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense_attention(*a, window))), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, dense):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_window_attention_kernel_tier_with_narrower_values_in_interpret_mode():
    """The published head sizes: 192 for queries and keys (not a whole number of 128-lane tiles), 128 for values."""
    q, k, v = _qkv_narrow_v(256, Hq=2, Hkv=2, D=192, Dv=128, B=1)
    got = attn._splash(q, k, v, 0, interpret=True)
    want = _dense_attention(q, k, v, 0)
    assert got.shape == (1, 256, 2, 128) and float(jnp.max(jnp.abs(got - want))) < 3e-2  # bfloat16 operands
    ours = jax.grad(lambda *a: jnp.sum(attn._splash(*a, 0, interpret=True) * want), (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(_dense_attention(*a, 0) * want), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, dense):
        assert a.shape == b.shape and float(jnp.max(jnp.abs(a - b))) < 5e-2 * (1 + float(jnp.max(jnp.abs(b))))


# -- SiLU-gated experts beside the ReGLU ones --------------------------------------------------------------
def _dense_moe_with(act, xs, wg, wu, wd, loads):
    out, start = jnp.zeros_like(xs), 0
    for e, n in enumerate(loads):
        x = xs[start : start + n]
        out = out.at[start : start + n].set((act(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        start += n
    return out


@pytest.mark.parametrize("loads", [LOADS["an-empty-expert"], LOADS["rows-held-elsewhere"]], ids=["an-empty-expert", "rows-held-elsewhere"])
def test_moe_grouped_ffn_silu_lax_tier_forward_and_gradients(loads):
    xs, wg, wu, wd, sizes = _moe_case(loads)
    want = _dense_moe_with(jax.nn.silu, xs, wg, wu, wd, loads)
    got = K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes, "silu")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert not np.asarray(got)[sum(loads):].any()
    ours = jax.grad(lambda *a: jnp.sum(jnp.sin(K.moe_grouped_ffn_reference(*a, sizes, "silu"))), (0, 1, 2, 3))(xs, wg, wu, wd)
    dense = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense_moe_with(jax.nn.silu, *a, loads))), (0, 1, 2, 3))(xs, wg, wu, wd)
    for a, b in zip(ours, dense):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_moe_grouped_ffn_silu_kernel_tier_in_interpret_mode():
    loads = LOADS["rows-held-elsewhere"]
    xs, wg, wu, wd, sizes = _moe_case(loads)
    want = _dense_moe_with(jax.nn.silu, xs, wg, wu, wd, loads)
    got = moe._grouped_ffn_gmm(xs, wg, wu, wd, sizes, "silu", interpret=True)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * float(jnp.max(jnp.abs(want)))  # bfloat16 operands
    assert not np.asarray(got)[sum(loads):].any()
    ours = jax.grad(lambda *a: jnp.sum(moe._grouped_ffn_gmm(*a, sizes, "silu", interpret=True) * want), (0, 1, 2, 3))(xs, wg, wu, wd)
    dense = jax.grad(lambda *a: jnp.sum(_dense_moe_with(jax.nn.silu, *a, loads) * want), (0, 1, 2, 3))(xs, wg, wu, wd)
    for a, b in zip(ours, dense):
        assert bool(jnp.isfinite(a).all()) and float(jnp.linalg.norm(a - b)) < 3e-2 * float(jnp.linalg.norm(b))


def test_moe_grouped_ffn_relu_is_the_default_and_unchanged():
    """The activation is the call's static argument: left out it is the ReGLU it was, bit for bit, through the
    registry too; another name is refused."""
    xs, wg, wu, wd, sizes = _moe_case(LOADS["one-takes-most"])
    default = K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes)
    np.testing.assert_array_equal(default, K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes, "relu"))
    np.testing.assert_allclose(default, _dense_moe(xs, wg, wu, wd, LOADS["one-takes-most"]), rtol=1e-4, atol=1e-4)
    with K.use_backend("lax"):
        np.testing.assert_array_equal(K.moe_grouped_ffn(xs, wg, wu, wd, sizes), default)
        silu = K.moe_grouped_ffn(xs, wg, wu, wd, sizes, "silu")
    assert float(jnp.max(jnp.abs(silu - default))) > 1e-2
    same = lambda f: str(jax.make_jaxpr(f)(xs, wg, wu, wd, sizes))  # noqa: E731
    assert same(K.moe_grouped_ffn_reference) == same(lambda *a: K.moe_grouped_ffn_reference(*a, "relu"))
    with pytest.raises(KeyError):
        K.moe_grouped_ffn_reference(xs, wg, wu, wd, sizes, "gelu")
