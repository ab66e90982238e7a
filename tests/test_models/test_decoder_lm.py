"""The decoder language model against the benchmark's plain reference
(chipbench/reference/ppo_lm_ref.py), at a small size on the CPU: full forward,
prefill + decode through the two-kind cache, the expert and vocabulary shares,
rematerialisation, and an adversarial router."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder_lm as lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_reference():
    spec = importlib.util.spec_from_file_location("ppo_lm_ref", os.path.join(ROOT, "chipbench", "reference", "ppo_lm_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
SMALL = lm.DecoderConfig(
    hidden=64, heads=4, kv_heads=2, head_dim=16, layers=4, experts=8, top_k=2, expert_width=32, experts_held=8,
    expert_offset=0, vocab_held=48, window=8, rope_theta=1.5e6, eps=1e-6, rope_layout=(0, 1, 1, 1),
    window_layout=(0, 1, 1, 1), remat=True,
)


def ref_hyper(cfg: lm.DecoderConfig, prompt_len=24, response_len=8):
    return {
        "hidden": cfg.hidden, "heads": cfg.heads, "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim, "layers": cfg.layers,
        "experts": cfg.experts, "top_k": cfg.top_k, "expert_width": cfg.expert_width, "experts_held": cfg.experts_held,
        "expert_offset": cfg.expert_offset, "vocab": cfg.vocab_held, "window": cfg.window, "theta": cfg.rope_theta,
        "eps": cfg.eps, "rope_layout": list(cfg.rope_layout), "window_layout": list(cfg.window_layout),
        "prompt_len": prompt_len, "response_len": response_len, "init_std": 0.3,
    }


def small_params(cfg=SMALL, seed=0):
    # a large init so that attention and routing are far from uniform
    return REF.init_params(ref_hyper(cfg), seed)


def tokens_of(cfg, batch, seq, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_held, jnp.int32)


def test_reference_and_program_share_a_parameter_layout():
    ours = lm.init_params(SMALL, jax.random.PRNGKey(0))
    theirs = small_params()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [x.shape for x in jax.tree.leaves(theirs)]
    assert lm.parameter_count(ours) == sum(x.size for x in jax.tree.leaves(theirs))


def test_full_forward_matches_the_reference():
    params, tokens = small_params(), tokens_of(SMALL, 2, 32)  # 32 positions > the window of 8
    x, counters, _ = jax.jit(lambda p, t: lm.forward(SMALL, p, t))(params, tokens)
    logits, values = lm.heads(SMALL, params, x)
    h = ref_hyper(SMALL)
    for b in range(2):
        rx, rcounts = REF.forward(h, params, tokens[b])
        rlogits, rvalues = REF.heads(h, params, rx)
        np.testing.assert_allclose(logits[b], rlogits, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(values[b], rvalues, rtol=2e-4, atol=2e-4)
    # every assignment lands on a held expert when all are held, and none is dropped
    np.testing.assert_array_equal(counters[:, 0], np.full(4, 2 * 32 * SMALL.top_k))


def test_prefill_then_decode_through_the_cache_equals_the_full_forward():
    """Position by position, past the point where the window's ring wraps
    (prompt 12 > window 8, then 14 decoded tokens: the ring wraps twice)."""
    params, tokens = small_params(), tokens_of(SMALL, 3, 26)
    P = 12
    x_full, _, _ = lm.forward(SMALL, params, tokens)
    full_logits, full_values = lm.heads(SMALL, params, x_full)
    x, cache, _ = lm.prefill(SMALL, params, tokens[:, :P], 26)
    assert [c[0].shape[1] for c in cache] == [26, 8, 8, 8]  # two kinds of state side by side
    step = jax.jit(lambda cache, tok, pos: lm.decode_step(SMALL, params, cache, tok, pos))
    for pos in range(P - 1, 26):
        if pos >= P:
            x, cache, _ = step(cache, tokens[:, pos], jnp.int32(pos))
        logits, values = lm.heads(SMALL, params, x)
        np.testing.assert_allclose(logits, full_logits[:, pos], rtol=2e-4, atol=2e-4, err_msg=f"position {pos}")
        np.testing.assert_allclose(values, full_values[:, pos], rtol=2e-4, atol=2e-4, err_msg=f"position {pos}")


@pytest.mark.parametrize("shares", [1, 4])
def test_the_expert_shares_add_up_to_the_uncut_layer(shares):
    params = small_params()
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (40, SMALL.hidden))
    weights, experts = lm.route(SMALL, u, layer["router"])
    held = SMALL.experts // shares
    total, assignments = 0.0, 0
    for s in range(shares):
        cfg = dataclasses.replace(SMALL, experts_held=held, expert_offset=s * held)
        part = {k: (v[s * held : (s + 1) * held] if k.startswith("w_") else v) for k, v in layer.items()}
        out, (n_held, _, dropped) = lm.moe_share(cfg, part, u, weights, experts)
        assert int(dropped) == 0
        total, assignments = total + out, assignments + int(n_held)
    # the uncut layer, token by token, in numpy
    want = np.zeros((40, SMALL.hidden), np.float32)
    for n in range(40):
        for w, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            hid = np.maximum(np.asarray(u[n]) @ np.asarray(layer["w_gate"][e]), 0) * (np.asarray(u[n]) @ np.asarray(layer["w_up"][e]))
            want[n] += w * (hid @ np.asarray(layer["w_down"][e]))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)
    assert assignments == 40 * SMALL.top_k


def test_the_vocabulary_slice_is_the_matching_rows_of_the_whole_head():
    params, tokens = small_params(), tokens_of(SMALL, 1, 16)
    cut = dataclasses.replace(SMALL, vocab_held=12)
    sliced = {**params, "embed": params["embed"][:12], "head": params["head"][:, :12]}
    tokens = tokens % 12
    logits = jax.jit(lambda cfg, p: lm.heads(cfg, p, lm.forward(cfg, p, tokens)[0])[0], static_argnums=0)
    whole, part = logits(SMALL, params), logits(cut, sliced)
    np.testing.assert_allclose(part, whole[..., :12], rtol=1e-5, atol=1e-5)


def test_rematerialised_gradients_equal_plain_ones():
    params, tokens = small_params(), tokens_of(SMALL, 2, 16)

    def loss(p, cfg):
        logits, values = lm.heads(cfg, p, lm.forward(cfg, p, tokens)[0])
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0]) + jnp.mean(values**2)

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    with_remat = grad(params, SMALL)
    without = grad(params, dataclasses.replace(SMALL, remat=False))
    for a, b in zip(jax.tree.leaves(with_remat), jax.tree.leaves(without)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_an_adversarial_router_drops_nothing():
    """Every token to the same two experts: the grouped product takes the whole
    load (no capacity), the other experts take none, and the result is still
    the reference's."""
    params = small_params()
    cfg = dataclasses.replace(SMALL, experts_held=4, expert_offset=2)
    layer = {k: (v[2:6] if k.startswith("w_") else v) for k, v in params["layers"][2].items()}
    bias = jnp.zeros((SMALL.experts,)).at[jnp.array([3, 5])].set(50.0)
    u = jax.random.normal(jax.random.PRNGKey(4), (64, SMALL.hidden))
    top, experts = jax.lax.top_k(u @ layer["router"] + bias, cfg.top_k)
    weights = jax.nn.softmax(top, axis=-1)
    assert set(np.asarray(experts).ravel()) == {3, 5}
    out, (n_held, largest, dropped) = lm.moe_share(cfg, layer, u, weights, experts)
    assert int(n_held) == 64 * 2 and int(largest) == 64 and int(dropped) == 0
    want = 0.0
    for k in range(2):
        e = np.asarray(experts[:, k]) - 2
        hid = jax.nn.relu(jnp.einsum("nh,nhf->nf", u, layer["w_gate"][e])) * jnp.einsum("nh,nhf->nf", u, layer["w_up"][e])
        want = want + weights[:, k, None] * jnp.einsum("nf,nfh->nh", hid, layer["w_down"][e])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    # every token to experts held elsewhere: nothing is computed here, and nothing counts as dropped
    elsewhere = jnp.zeros_like(experts).at[:, 1].set(7)
    out, (n_held, largest, dropped) = lm.moe_share(cfg, layer, u, weights, elsewhere)
    assert int(n_held) == 0 and int(largest) == 0 and int(dropped) == 0 and not np.asarray(out).any()
