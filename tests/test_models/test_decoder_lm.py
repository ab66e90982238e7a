"""The decoder language model against the benchmark's plain reference
(chipbench/reference/ppo_lm_ref.py), at a small size on the CPU: full forward,
prefill + decode through the two-kind cache, the expert and vocabulary shares,
rematerialisation (and what it keeps of the attention kernel), and an
adversarial router."""

import collections
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder_lm as lm
from sheeprl_tpu.ops import kernels as K
from sheeprl_tpu.ops.kernels import attn

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
        out, (n_held, _, dropped, *_) = lm.moe_share(cfg, part, u, weights, experts)
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
    out, (n_held, largest, dropped, *_) = lm.moe_share(cfg, layer, u, weights, experts)
    assert int(n_held) == 64 * 2 and int(largest) == 64 and int(dropped) == 0
    want = 0.0
    for k in range(2):
        e = np.asarray(experts[:, k]) - 2
        hid = jax.nn.relu(jnp.einsum("nh,nhf->nf", u, layer["w_gate"][e])) * jnp.einsum("nh,nhf->nf", u, layer["w_up"][e])
        want = want + weights[:, k, None] * jnp.einsum("nf,nfh->nh", hid, layer["w_down"][e])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    # every token to experts held elsewhere: nothing is computed here, and nothing counts as dropped
    elsewhere = jnp.zeros_like(experts).at[:, 1].set(7)
    out, (n_held, largest, dropped, *_) = lm.moe_share(cfg, layer, u, weights, elsewhere)
    assert int(n_held) == 0 and int(largest) == 0 and int(dropped) == 0 and not np.asarray(out).any()


# -- the routed share's rows: slot-major, compacted to the experts held ----------
# 2 of 8 experts held, top 2 of 1024 tokens: 2048 assignments, of which the share moves 1024 rows (twice the mean 512)
CUT = dataclasses.replace(SMALL, experts_held=2, expert_offset=2)
CUT_TOKENS = 1024


def _assignments_with(held_rows, tokens=CUT_TOKENS):
    """Experts ``(tokens, 2)`` with exactly ``held_rows`` assignments on the experts 2 and 3 that ``CUT`` holds."""
    both, one = divmod(held_rows, 2)
    experts = np.tile(np.array([[0, 1]], np.int32), (tokens, 1))
    experts[:both] = [3, 2]
    experts[both : both + one] = [5, 2]
    return jnp.asarray(np.random.default_rng(0).permutation(experts))


def _plain_share(cfg, layer, u, weights, experts):
    """``sum_k w * E_e(u)`` with a mask, every held expert over every token: no sort, no gather."""
    out = 0.0
    for e in range(cfg.experts_held):
        w = jnp.sum(jnp.where(experts == e + cfg.expert_offset, weights, 0.0), axis=-1)
        hid = jax.nn.relu(u @ layer["w_gate"][e]) * (u @ layer["w_up"][e])
        out = out + w[:, None] * (hid @ layer["w_down"][e])
    return out


SHARE_CASES = {
    # name: (config, tokens, assignments held (None: the router's own), compacted, could compact)
    "under_capacity": (CUT, CUT_TOKENS, None, 1, 1),
    "at_capacity": (CUT, CUT_TOKENS, 1024, 1, 1),
    "over_capacity": (CUT, CUT_TOKENS, 1025, 0, 1),
    "uncut_layer": (SMALL, CUT_TOKENS, None, 0, 0),
    "decode_shape": (CUT, 8, None, 0, 0),
}


@pytest.mark.parametrize("case", list(SHARE_CASES))
def test_the_share_equals_a_plain_unsorted_sum_forward_and_backward(case):
    cfg, tokens, held_rows, compacted, compactable = SHARE_CASES[case]
    assert lm.compact_rows(cfg, tokens) == (1024 if compactable else tokens * cfg.top_k)
    full = small_params()["layers"][1]
    lo, hi = cfg.expert_offset, cfg.expert_offset + cfg.experts_held
    layer = {k: (v[lo:hi] if k.startswith("w_") else v) for k, v in full.items()}
    leaves = {k: layer[k] for k in ("w_gate", "w_up", "w_down")}
    u = jax.random.normal(jax.random.PRNGKey(5), (tokens, cfg.hidden))
    weights, experts = lm.route(cfg, u, layer["router"])
    if held_rows is not None:
        experts = _assignments_with(held_rows, tokens)
    g = jax.random.normal(jax.random.PRNGKey(6), (tokens, cfg.hidden))

    def ours(leaves, u, weights):
        out, counters = lm.moe_share(cfg, {**layer, **leaves}, u, weights, experts)
        return jnp.sum(out * g), (out, counters)

    def plain(leaves, u, weights):
        out = _plain_share(cfg, leaves, u, weights, experts)
        return jnp.sum(out * g), out

    (_, (out, counters)), grads = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2), has_aux=True))(leaves, u, weights)
    (_, want), want_grads = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True))(leaves, u, weights)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-4)
    on_held = np.asarray((experts >= lo) & (experts < hi))
    loads = [int(np.sum(np.asarray(experts) == e)) for e in range(lo, hi)]
    assert [int(c) for c in counters] == [int(on_held.sum()), max(loads), 0, compacted, compactable]
    if held_rows is not None:
        assert int(on_held.sum()) == held_rows


def test_only_a_call_that_could_compact_holds_a_branch():
    def conds(cfg, tokens):
        layer = {k: (v[: cfg.experts_held] if k.startswith("w_") else v) for k, v in small_params()["layers"][1].items()}
        u = jnp.zeros((tokens, cfg.hidden))
        weights, experts = jnp.full((tokens, cfg.top_k), 0.5), jnp.zeros((tokens, cfg.top_k), jnp.int32)
        share = lambda layer, u, weights: lm.moe_share(cfg, layer, u, weights, experts)[0].sum()  # noqa: E731
        return (str(jax.make_jaxpr(share)(layer, u, weights)).count("cond["),
                str(jax.make_jaxpr(jax.grad(share, argnums=(0, 1, 2)))(layer, u, weights)).count("cond["))

    assert conds(CUT, 8) == (0, 0)  # a decode step's 16 assignments: every row, statically
    assert conds(SMALL, CUT_TOKENS) == (0, 0)  # an uncut layer: there is nothing to leave out
    assert conds(CUT, CUT_TOKENS) == (1, 2)  # one branch forward; differentiated, one forward and one backward


def test_dispatch_and_combine_backward_are_the_plain_gathers_gradients():
    """The two custom VJPs (gathers both ways) against ``jax.grad`` of the same gathers written plainly, on a real
    sorted order cut to 1024 of its 2048 rows."""
    N, K, H, rows = CUT_TOKENS, 2, 16, 1024
    held = jnp.asarray(np.random.default_rng(1).random((K, N)) < 0.3)
    slot = jnp.where(held, 0, 1).reshape(-1)
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(N * K, dtype=jnp.int32))
    picked, index = order[:rows], jnp.minimum(inverse, rows - 1).reshape(K, N)
    routed = int(held.sum())
    assert routed < rows
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    u, ys = jax.random.normal(keys[0], (N, H)), jax.random.normal(keys[1], (rows, H))
    weights = jnp.where(held, jax.random.uniform(keys[2], (K, N)), 0.0)
    # the grouped product hands back zeros for the rows past the last group, forward and backward
    g_rows = jnp.where(jnp.arange(rows)[:, None] < routed, jax.random.normal(keys[3], (rows, H)), 0.0)
    g_tokens = jax.random.normal(keys[4], (N, H))

    ours = jax.grad(lambda u: jnp.sum(lm._gather_sorted(u, picked % N, index, held) * g_rows))(u)
    plain = jax.grad(lambda u: jnp.sum(u[picked % N] * g_rows))(u)
    np.testing.assert_allclose(ours, plain, rtol=1e-5, atol=1e-5)

    ours = jax.grad(lambda ys, w: jnp.sum(lm._combine(ys, w, picked, index) * g_tokens), argnums=(0, 1))(ys, weights)
    plain = jax.grad(lambda ys, w: jnp.sum(jnp.sum(w[..., None] * ys[index], axis=0) * g_tokens), argnums=(0, 1))(ys, weights)
    np.testing.assert_allclose(ours[0], plain[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ours[1], plain[1], rtol=1e-5, atol=1e-5)


# -- what the per-layer remat keeps: the attention kernel's output and log-sum-exp ----------------------------------
# 256 positions on blocks of 128 (monkeypatched, as the kernels' own tests do): two blocks a call, a full layer and a
# 128-position window layer
KERNEL_TIER = dataclasses.replace(SMALL, layers=2, window=128, rope_layout=(0, 1), window_layout=(0, 1))


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def splash_forwards(jaxpr) -> int:
    """The splash forward kernels a program holds, at any depth."""
    return sum(e.primitive.name == "pallas_call" and str(e.params.get("name", "")).startswith("splash_mqa_fwd")
               for e in _eqns(jaxpr.jaxpr))


def primitive_counts(jaxpr) -> collections.Counter:
    return collections.Counter(e.primitive.name for e in _eqns(jaxpr.jaxpr))


def update_loss(cfg, params, tokens):
    logits, values = lm.heads(cfg, params, lm.forward(cfg, params, tokens)[0])
    return jnp.mean(jax.nn.log_softmax(logits)[..., 0]) + jnp.mean(values**2)


def gradient_program(cfg, params, tokens):
    """``jax.grad`` of the loss through ``forward``: its jaxpr and its gradients, traced anew (the policy is read
    at trace time)."""
    grad = jax.grad(functools.partial(update_loss, cfg))
    return jax.make_jaxpr(grad)(params, tokens), jax.jit(grad)(params, tokens)


def remat_programs(cfg, params, tokens, monkeypatch, plain_policy=None):
    """The gradient program under the layer's policy, and under ``plain_policy`` (by default a plain
    ``jax.checkpoint``: the policy left out)."""
    kept = gradient_program(cfg, params, tokens)
    with monkeypatch.context() as m:
        m.setattr(lm, "REMAT_POLICY", plain_policy)
        plain = gradient_program(cfg, params, tokens)
    return kept, plain


def assert_the_kernel_tiers_remat_keeps_its_residuals(cfg, params, tokens, monkeypatch):
    """One splash forward a layer under the policy (two under a plain checkpoint: the backward's recompute), the
    plain checkpoint's gradients within 1e-6 and those without remat within the remat test's tolerance."""
    monkeypatch.setattr(attn, "SPLASH_BLOCK", 128)
    with K.use_backend("lax", window_attention="pallas"):
        (kept_jaxpr, kept), (plain_jaxpr, plain) = remat_programs(cfg, params, tokens, monkeypatch)
        unremat = gradient_program(dataclasses.replace(cfg, remat=False), params, tokens)[1]
    assert splash_forwards(kept_jaxpr) == cfg.layers
    assert splash_forwards(plain_jaxpr) == 2 * cfg.layers
    for a, b, c in zip(jax.tree.leaves(kept), jax.tree.leaves(plain), jax.tree.leaves(unremat)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)


def assert_the_reference_tiers_remat_is_a_plain_checkpoint(cfg, params, tokens, monkeypatch):
    """The reference tier's attention names nothing: under the policy its gradient program holds every primitive as
    often as under a plain checkpoint (one that keeps the sublayer outputs alone, where ``post_norms`` names them),
    and its gradients are the same bits."""
    plain_policy = jax.checkpoint_policies.save_only_these_names(lm.SUBLAYER_OUT) if cfg.post_norms else None
    with K.use_backend("lax"):
        (kept_jaxpr, kept), (plain_jaxpr, plain) = remat_programs(cfg, params, tokens, monkeypatch, plain_policy)
    assert splash_forwards(kept_jaxpr) == 0
    assert primitive_counts(kept_jaxpr) == primitive_counts(plain_jaxpr)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


def test_the_remat_keeps_the_splash_kernels_residuals_a_full_and_a_window_layer(monkeypatch):
    params = small_params(KERNEL_TIER)
    assert_the_kernel_tiers_remat_keeps_its_residuals(KERNEL_TIER, params, tokens_of(KERNEL_TIER, 1, 256), monkeypatch)


def test_the_reference_tiers_remat_is_the_plain_checkpoints_program(monkeypatch):
    assert_the_reference_tiers_remat_is_a_plain_checkpoint(SMALL, small_params(), tokens_of(SMALL, 2, 32), monkeypatch)
