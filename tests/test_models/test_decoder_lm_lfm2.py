"""The convolution policy of :mod:`sheeprl_tpu.models.decoder_lm` (the
``lfm2_moe`` layer) against the benchmark's plain reference
(chipbench/reference/ppo_lm_lfm2_ref.py), at a small size on the CPU: the full
forward, the loss and every gradient; prefill and then decode through the
convolution states and the full buffer against the reference's full forward,
at prompts shorter than the convolution, as long and longer; the expert shares
with the mixers and the dense layer counted once; the tied leaf's gradient as
the sum of its two ends'; the router's normalisation epsilon and a routed
layer without shared experts; what ``from_config`` refuses; the cut's
parameter count; and that each planted fault of the reference, and bfloat16
weights, fail the tolerance the program passes."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder_lm as lm
from sheeprl_tpu.ops import kernels as K
from sheeprl_tpu.ops.kernels import attn
from tests.test_models.test_decoder_lm import (
    assert_the_reference_tiers_remat_is_a_plain_checkpoint,
    remat_programs,
    splash_forwards,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "lfm2_24b_a2b_ep8.json")


def load_reference():
    path = os.path.join(ROOT, "chipbench", "reference", "ppo_lm_lfm2_ref.py")
    spec = importlib.util.spec_from_file_location("ppo_lm_lfm2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
CONV = (1, 1, 0, 1, 1)  # the published period's first five layers: conv, conv, full_attention, conv, conv
# a dense layer, then four routed ones without a shared expert; every expert held; a tied head
SMALL = lm.DecoderConfig(
    hidden=64, heads=4, kv_heads=2, head_dim=16, layers=5, experts=8, top_k=2, expert_width=32, experts_held=8,
    expert_offset=0, vocab_held=48, window=0, rope_theta=1e6, eps=1e-5, rope_layout=tuple(1 - c for c in CONV),
    window_layout=(0,) * 5, remat=True, router="sigmoid_bias", activation="silu", dense_layers=1, dense_width=96,
    routed_scale=1.0, qk_norm=True, route_eps=1e-6, conv_layout=CONV, conv_kernel=3, tied_head=True,
)
P, R = 24, 8
# float32 against float32 at `highest`: the gap is rounding, 1e-5 at these sizes; each planted fault and bfloat16
# weights move the logits by 1e-2 or more (the fault tests)
TOL = dict(rtol=2e-4, atol=2e-4)


def ref_hyper(cfg: lm.DecoderConfig, bias_scale=0.05, prompt_len=P):
    return {
        "hidden": cfg.hidden, "heads": cfg.heads, "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "layers": cfg.layers, "dense_layers": cfg.dense_layers, "dense_width": cfg.dense_width, "experts": cfg.experts,
        "top_k": cfg.top_k, "expert_width": cfg.expert_width, "routed_scale": cfg.routed_scale,
        "experts_held": cfg.experts_held, "expert_offset": cfg.expert_offset, "vocab": cfg.vocab_held,
        "theta": cfg.rope_theta, "eps": cfg.eps, "conv_kernel": cfg.conv_kernel,
        "conv": [bool(c) for c in cfg.conv_layout], "bias_scale": bias_scale, "prompt_len": prompt_len,
        "response_len": R, "init_std": 0.3,
    }


def small_params(cfg=SMALL, seed=0):
    # a large init so that the convolution's gates, attention and routing are far from uniform, and a bias that
    # moves selections
    return REF.init_params(ref_hyper(cfg), seed)


def tokens_of(cfg, batch, seq, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_held, jnp.int32)


def program_logits(cfg, params, tokens):
    x, counters, _ = jax.jit(lambda p, t: lm.forward(cfg, p, t))(params, tokens)
    return lm.heads(cfg, params, x), counters


@functools.lru_cache(maxsize=None)
def _reference(fault, prompt_len):
    h = ref_hyper(SMALL, prompt_len=prompt_len)
    return jax.jit(lambda params, tokens: REF.heads(h, params, REF.forward(h, params, tokens, fault)[0]))


def reference_logits(params, tokens, fault="", prompt_len=P):
    """The reference's logits and values of one sequence, by its full forward (jitted once a fault and prompt)."""
    return _reference(fault, prompt_len)(params, tokens)


# one decode step and the heads, compiled once for every test that decodes
DECODE = jax.jit(lambda params, cache, token, position: lm.decode_step(SMALL, params, cache, token, position))
HEADS = jax.jit(lambda params, x: lm.heads(SMALL, params, x))


def test_reference_and_program_share_a_parameter_layout():
    ours = lm.init_params(SMALL, jax.random.PRNGKey(0))
    theirs = small_params()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [x.shape for x in jax.tree.leaves(theirs)]
    three = {jax.tree_util.keystr(p).rsplit("'", 2)[-2] for p, x in jax.tree_util.tree_leaves_with_path(ours) if x.ndim == 3}
    assert three == {"w_gate", "w_up", "w_down"}
    assert "head" not in ours and "dense_gate" in ours["layers"][0] and "conv_taps" in ours["layers"][0]
    assert set(ours["layers"][1]) == {"ln_in", "conv_in", "conv_taps", "conv_out", "ln_post", "router", "router_bias",
                                      "w_gate", "w_up", "w_down"}  # no shared expert, no attention
    assert {"wq", "wk", "wv", "wo", "ln_q", "ln_k"} <= set(ours["layers"][2]) and "conv_in" not in ours["layers"][2]
    assert ours["layers"][1]["conv_in"].shape == (64, 3 * 64) and ours["layers"][1]["conv_taps"].shape == (3, 64)


def _cut(conf):
    from sheeprl_tpu.config import compose

    cfg = compose(["exp=ppo_anakin_lm", *conf["overrides"]])
    return lm.DecoderConfig.from_config(cfg.algo.lm)


def test_the_cut_has_the_parameters_its_configuration_file_states():
    with open(CONFIG) as f:
        conf = json.load(f)
    model = _cut(conf)
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim, model.conv_kernel) == (2048, 32, 8, 64, 3)
    assert (model.experts, model.experts_held, model.top_k, model.expert_width, model.vocab_held) == (64, 8, 4, 1536, 8192)
    assert (model.dense_layers, model.dense_width, model.shared_width, model.routed_scale) == (1, 11776, 0, 1.0)
    assert model.conv_layout == CONV and model.rope_layout == (0, 0, 1, 0, 0) and model.window_layout == (0,) * 5
    assert (model.tied_head, model.qk_norm, model.route_eps, model.rope_theta, model.eps) == (True, True, 1e-6, 1e6, 1e-5)
    assert (model.attn_gate, model.post_norms, model.embed_scale, model.router) == (False, False, 1.0, "sigmoid_bias")
    shapes = jax.eval_shape(lambda key: lm.init_params(model, key), jax.random.PRNGKey(0))
    assert lm.parameter_count(shapes) == conf["parameters"] == 469_287_297
    # the count part by part: the dense convolution layer, a routed convolution layer, the routed attention layer,
    # the embedding (which is the head), the final norm and the critic
    layers = [sum(int(x.size) for x in jax.tree.leaves(layer)) for layer in shapes["layers"]]
    assert layers == [89_139_200, 92_416_064, 86_118_592, 92_416_064, 92_416_064]
    assert sum(int(shapes["layers"][1][k].size) for k in ("conv_in", "conv_taps", "conv_out")) == 16_783_360
    assert sum(int(shapes["layers"][2][k].size) for k in ("wq", "wk", "wv", "wo", "ln_q", "ln_k")) == 10_485_888
    assert sum(int(shapes["layers"][1][k].size) for k in ("w_gate", "w_up", "w_down")) == 8 * 9_437_184
    assert shapes["embed"].size == 16_777_216 and "head" not in shapes
    assert shapes["ln_f"].size + shapes["value_w"].size + shapes["value_b"].size == 4_097


def test_full_forward_matches_the_reference():
    params, tokens = small_params(), tokens_of(SMALL, 2, P + R)
    (logits, values), counters = program_logits(SMALL, params, tokens)
    for b in range(2):
        rlogits, rvalues = reference_logits(params, tokens[b])
        np.testing.assert_allclose(logits[b], rlogits, **TOL)
        np.testing.assert_allclose(values[b], rvalues, **TOL)
    # every assignment lands on a held expert when all are held; the dense layer's row is zeros
    assert counters.shape == (5, 6) and not np.asarray(counters[0]).any()
    np.testing.assert_array_equal(counters[1:, 0], np.full(4, 2 * (P + R) * SMALL.top_k))
    assert not np.asarray(counters[:, 2]).any() and int(counters[1:, 5].sum()) > 0  # none dropped; the bias moved some


def _ppo_like_loss(logp, entropy, values, advantages):
    return -jnp.mean(logp * advantages) + 0.5 * jnp.mean((values - 0.3) ** 2) - 0.01 * jnp.mean(entropy)


def _share(params, lo, hi):
    return {**params, "layers": [{k: (v[lo:hi] if v.ndim == 3 else v) for k, v in layer.items()} for layer in params["layers"]]}


def test_loss_and_every_gradient_match_the_reference():
    from sheeprl_tpu.algos.ppo.ppo_anakin_lm import LMPolicy, _response_outputs

    cfg = dataclasses.replace(SMALL, experts_held=4, expert_offset=2)  # a share: experts 2..5 of 8
    params = _share(small_params(), 2, 6)
    tokens = tokens_of(cfg, 2, P + R)
    advantages = jax.random.normal(jax.random.PRNGKey(5), (2, R))
    policy, h = LMPolicy(cfg, P, R), ref_hyper(cfg)

    def ours(p):
        logp, entropy, values, _ = _response_outputs(policy, p, tokens)
        return _ppo_like_loss(logp, entropy, values, advantages)

    def theirs(p):
        outs = [REF.response_outputs(h, p, t) for t in tokens]
        return _ppo_like_loss(*(jnp.stack([o[i] for o in outs]) for i in range(3)), advantages)

    loss, grads = jax.jit(jax.value_and_grad(ours))(params)
    want, want_grads = jax.jit(jax.value_and_grad(theirs))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4 * max(scale, 1e-3), err_msg=jax.tree_util.keystr(path))
        if "router_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(a).any() and not np.asarray(b).any()  # no gradient reaches the bias, in either
        else:
            assert scale > 0, jax.tree_util.keystr(path)


def test_the_tied_leafs_gradient_is_the_sum_of_its_input_ends_and_its_heads():
    """The embedding is read at both ends: its gradient is the gradient through the lookup with the head's
    matrix held fixed plus the gradient through the head with the lookup held fixed, and neither part is zero."""
    params, tokens = small_params(), tokens_of(SMALL, 2, P + R)
    untied = {**params, "head": params["embed"].T}  # the same numbers as a separate head leaf

    def loss(cfg, p):
        logits, values = lm.heads(cfg, p, lm.forward(cfg, p, tokens)[0])
        return jnp.mean(jax.nn.log_softmax(logits)[..., 3]) + jnp.mean(values**2)

    tied = jax.jit(jax.grad(lambda p: loss(SMALL, p)))(params)["embed"]
    parts = jax.jit(jax.grad(lambda p: loss(dataclasses.replace(SMALL, tied_head=False), p)))(untied)
    lookup, head = parts["embed"], parts["head"].T
    assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(tied, lookup + head, rtol=1e-5, atol=1e-7)
    # the program and the reference agree on the sum, and the reference's untied-head fault loses the head's part
    h = ref_hyper(SMALL)

    def ref_grad(fault):
        def ref_loss(p):
            logits, values = jax.vmap(lambda t: REF.heads(h, p, REF.forward(h, p, t)[0], fault))(tokens)
            return jnp.mean(jax.nn.log_softmax(logits)[..., 3]) + jnp.mean(values**2)

        return jax.jit(jax.grad(ref_loss))(params)["embed"]

    np.testing.assert_allclose(ref_grad(""), tied, rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(ref_grad("head_untied"), lookup, rtol=1e-3, atol=1e-6)


@pytest.mark.parametrize("prompt", [1, 2, 3, 12])
def test_prefill_then_decode_through_the_convolution_states_and_the_full_buffer_equals_the_references_full_forward(prompt):
    """Position by position to 20 after a prompt shorter than the convolution (its state zero-padded on the left),
    as long as it, and longer: the states shifted one position a decoded token, against the reference's full
    forward, on logits and values."""
    params, tokens = small_params(), tokens_of(SMALL, 3, 20)
    want = [reference_logits(params, tokens[b]) for b in range(3)]
    x, cache, _ = jax.jit(lambda p, t: lm.prefill(SMALL, p, t, 20))(params, tokens[:, :prompt])
    state, buffer = (3, SMALL.conv_kernel, SMALL.hidden), ((3, 20, 2, 16),) * 2
    assert [tuple(s.shape) if SMALL.is_conv(i) else (s[0].shape, s[1].shape) for i, s in enumerate(cache)] == [
        state, state, buffer, state, state]
    if prompt < SMALL.conv_kernel:  # the slots before the prompt's first position hold zeros
        assert not np.asarray(cache[0][:, : SMALL.conv_kernel - prompt]).any()
        assert np.asarray(cache[0][:, SMALL.conv_kernel - prompt :]).all()
    for pos in range(prompt - 1, 20):
        if pos >= prompt:
            x, cache, counters = DECODE(params, cache, tokens[:, pos], jnp.int32(pos))
            assert counters.shape == (5, 6) and not np.asarray(counters[:, 5]).any()  # decode counts no moved selection
        logits, values = HEADS(params, x)
        for b in range(3):
            np.testing.assert_allclose(logits[b], want[b][0][pos], **TOL, err_msg=f"position {pos}")
            np.testing.assert_allclose(values[b], want[b][1][pos], **TOL, err_msg=f"position {pos}")


@pytest.mark.parametrize("layer_index", [1, 2], ids=["conv", "attention"])
@pytest.mark.parametrize("shares", [1, 8])
def test_the_routed_shares_with_the_mixer_and_the_dense_layer_once_add_up_to_the_uncut_layer(shares, layer_index):
    """Each share's layer less its input and its mixer's part (the routed part alone, which a deployment sums over
    the exchange) summed over the shares, the mixer's part added once, against the reference's uncut layer; the
    dense layer is the same on every share."""
    params = small_params()
    layer, dense = params["layers"][layer_index], params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, SMALL.hidden))
    held = SMALL.experts // shares
    mixer = np.asarray(REF._mixer(ref_hyper(SMALL), layer_index, layer, lm.rms_norm(x[0], layer["ln_in"], SMALL.eps), ""))
    routed_sum, assignments, dense_outs = 0.0, 0, []
    for s in range(shares):
        cfg = dataclasses.replace(SMALL, experts_held=held, expert_offset=s * held)
        part = {k: (v[s * held : (s + 1) * held] if v.ndim == 3 else v) for k, v in layer.items()}
        out, _, counters = lm.layer_forward(cfg, layer_index, part, x)
        routed_sum = routed_sum + (np.asarray(out[0]) - np.asarray(x[0]) - mixer)  # this share's routed part alone
        dense_outs.append(lm.layer_forward(cfg, 0, dense, x)[0])
        assignments += int(counters[0])
        assert int(counters[2]) == 0
    want, held_here = REF._layer(dict(ref_hyper(SMALL), prompt_len=40), layer_index, layer, x[0], "")
    np.testing.assert_allclose(np.asarray(x[0]) + mixer + routed_sum, want, rtol=2e-4, atol=2e-4)
    assert assignments == int(held_here) == 40 * SMALL.top_k
    for other in dense_outs[1:]:
        np.testing.assert_array_equal(other, dense_outs[0])


def test_the_routers_normalisation_epsilon_is_the_configs():
    """The kept scores are divided by their sum plus ``route_eps``: the three other families keep 1e-20, this one
    1e-6, and the weights follow it."""
    from sheeprl_tpu.algos.ppo import ppo_anakin_lm
    from sheeprl_tpu.config import compose

    for overrides in (ppo_anakin_lm.TOY_OVERRIDES, ppo_anakin_lm.TOY_OVERRIDES_LATENT, ppo_anakin_lm.TOY_OVERRIDES_AFMOE):
        assert lm.DecoderConfig.from_config(compose(list(overrides)).algo.lm).route_eps == 1e-20
    assert lm.DecoderConfig.from_config(compose(list(ppo_anakin_lm.TOY_OVERRIDES_LFM2)).algo.lm).route_eps == 1e-6
    u = jax.random.normal(jax.random.PRNGKey(2), (16, SMALL.hidden))
    router = -3.0 * jnp.ones((SMALL.hidden, SMALL.experts)) + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (SMALL.hidden, SMALL.experts))
    bias = jnp.zeros((SMALL.experts,))
    for eps in (1e-20, 1e-6, 1.0):
        weights, experts = lm.route(dataclasses.replace(SMALL, route_eps=eps), u, router, bias)
        kept = jnp.take_along_axis(jax.nn.sigmoid(u @ router), experts, axis=-1)
        np.testing.assert_allclose(weights, kept / (kept.sum(axis=-1, keepdims=True) + eps), rtol=1e-5)
    assert not np.allclose(lm.route(dataclasses.replace(SMALL, route_eps=1.0), u, router, bias)[0],
                           lm.route(SMALL, u, router, bias)[0])


def test_a_routed_layer_without_shared_experts_reads_no_shared_leaf():
    """``shared_width`` 0: the layer holds no shared leaves, and its feed-forward is the routed share alone."""
    layer = small_params()["layers"][1]
    assert not {k for k in layer if k.startswith("shared_")}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 10, SMALL.hidden))
    y, counters = lm._feed_forward(SMALL, 1, layer, x)
    u = lm.rms_norm(x, layer["ln_post"], SMALL.eps).reshape(10, SMALL.hidden)
    weights, experts = lm.route(SMALL, u, layer["router"], layer["router_bias"])
    routed, _ = lm.moe_share(SMALL, layer, u, weights, experts)
    np.testing.assert_array_equal(y, x + routed.reshape(1, 10, SMALL.hidden))
    assert counters[0] == 10 * SMALL.top_k


def test_the_remat_keeps_the_splash_kernels_residuals_at_64_wide_heads(monkeypatch):
    """A dense convolution layer and a routed attention layer of 64-wide heads, 256 positions on blocks of 128:
    one splash forward (the attention layer's) under the policy, two under a plain checkpoint."""
    cfg = dataclasses.replace(SMALL, hidden=128, heads=2, kv_heads=1, head_dim=64, layers=2, conv_layout=(1, 0),
                              rope_layout=(0, 1), window_layout=(0, 0))
    params, tokens = small_params(cfg), tokens_of(cfg, 1, 256)
    monkeypatch.setattr(attn, "SPLASH_BLOCK", 128)
    with K.use_backend("lax", window_attention="pallas"):
        (kept_jaxpr, kept), (plain_jaxpr, plain) = remat_programs(cfg, params, tokens, monkeypatch)
    assert (splash_forwards(kept_jaxpr), splash_forwards(plain_jaxpr)) == (1, 2)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_the_reference_tiers_remat_is_the_plain_checkpoints_program_convolution(monkeypatch):
    assert_the_reference_tiers_remat_is_a_plain_checkpoint(SMALL, small_params(), tokens_of(SMALL, 2, P + R), monkeypatch)


UNWRITTEN = {
    "conv_bias": "algo.lm.conv_bias=True", "unnormalised_weights": "algo.lm.norm_topk_prob=False",
    "no_expert_bias": "algo.lm.use_expert_bias=False", "rotary_scaling": "algo.lm.rope_parameters.rope_type=yarn",
    "another_layer_kind": "algo.lm.layer_types=[conv, sliding_attention]",
    "untied_head": "algo.lm.tie_word_embeddings=False", "untied_head_by_lfm2s_key": "algo.lm.tie_embedding=False",
    "more_experts_held_than_there_are": "algo.lm.experts_held=200",
}


@pytest.mark.parametrize("case", list(UNWRITTEN))
def test_from_config_raises_on_what_is_not_written_down(case):
    from sheeprl_tpu.config import compose

    with pytest.raises(ValueError, match="written down|held of"):
        lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm_lfm2", UNWRITTEN[case]]).algo.lm)


def test_from_config_reads_a_stated_head_dim_and_a_stated_tie():
    from sheeprl_tpu.config import compose

    model = lm.DecoderConfig.from_config(
        compose(["exp=ppo_anakin_lm_lfm2", "algo.lm.head_dim=32", "algo.lm.tie_word_embeddings=True"]).algo.lm)
    assert model.head_dim == 32 and model.tied_head
    assert lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm_lfm2"]).algo.lm).head_dim == 2048 // 32


FAULTS = ("conv_gate_dropped", "taps_reversed", "qk_norm_dropped", "bias_dropped", "bfloat16_weights")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_element_left_out_fails_the_tolerance_the_program_passes(fault):
    """The comparison is tight enough: the reference with one element of the layer left out or changed, or with
    its weights rounded to bfloat16, is not within the tolerance of the program's logits."""
    params, tokens = small_params(), tokens_of(SMALL, 1, P + R)
    (logits, _), _ = program_logits(SMALL, params, tokens)
    if fault == "bfloat16_weights":
        rlogits, _ = reference_logits(jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params), tokens[0])
    else:
        rlogits, _ = reference_logits(params, tokens[0], fault)
    gap = np.abs(np.asarray(logits[0]) - np.asarray(rlogits))
    allowed = TOL["atol"] + TOL["rtol"] * np.abs(np.asarray(rlogits))
    assert (gap > allowed).any() and float(gap.max()) > 1e-2


def test_the_decode_that_drops_prefills_convolution_state_is_the_references_fault():
    """A decode that starts its convolution states at zero, not at what prefill left, departs from the full forward
    at the first decoded positions, as the reference's `conv_state_dropped` does, and the two agree."""
    prompt = 12
    params, tokens = small_params(), tokens_of(SMALL, 1, 20)
    x, cache, _ = jax.jit(lambda p, t: lm.prefill(SMALL, p, t, 20))(params, tokens[:, :prompt])
    cache = [jnp.zeros_like(s) if SMALL.is_conv(i) else s for i, s in enumerate(cache)]
    got = []
    for pos in range(prompt, 20):
        x, cache, _ = DECODE(params, cache, tokens[:, pos], jnp.int32(pos))
        got.append(HEADS(params, x)[0][0])
    sound = reference_logits(params, tokens[0])[0][prompt:]
    dropped = reference_logits(params, tokens[0], "conv_state_dropped", prompt)[0][prompt:]
    np.testing.assert_allclose(np.stack(got), dropped, **TOL)
    assert float(np.abs(np.stack(got) - sound).max()) > 1e-2


@pytest.mark.parametrize("kind", ["conv_layout", "qk_norm", "tied_head"])
def test_the_program_without_each_element_fails_the_references_tolerance(kind):
    """The other way round: the program with one of its static kinds turned off (attention in every layer, no q/k
    norms, an untied head that is the embedding's transpose) is not within the tolerance of the reference."""
    params, tokens = small_params(), tokens_of(SMALL, 1, P + R)
    if kind == "conv_layout":
        cfg = dataclasses.replace(SMALL, conv_layout=(), rope_layout=(1,) * 5)
        scale = lambda shape: 0.3 * jax.random.normal(jax.random.PRNGKey(9), shape)  # noqa: E731
        attention = {k: params["layers"][2][k] for k in ("wq", "wk", "wv", "wo", "ln_q", "ln_k")}
        params = {**params, "layers": [{**{k: v for k, v in layer.items() if not k.startswith("conv_")},
                                        **jax.tree.map(lambda a: scale(a.shape), attention)} if CONV[i] else layer
                                       for i, layer in enumerate(params["layers"])]}
    elif kind == "qk_norm":
        cfg = dataclasses.replace(SMALL, qk_norm=False)
    else:
        cfg = dataclasses.replace(SMALL, tied_head=False)
        params = {**params, "head": jax.random.permutation(jax.random.PRNGKey(8), params["embed"]).T}
    (logits, _), _ = program_logits(cfg, params, tokens)
    rlogits, _ = reference_logits(small_params(), tokens[0])
    gap = np.abs(np.asarray(logits[0]) - np.asarray(rlogits))
    assert (gap > TOL["atol"] + TOL["rtol"] * np.abs(np.asarray(rlogits))).any() and float(gap.max()) > 1e-2
