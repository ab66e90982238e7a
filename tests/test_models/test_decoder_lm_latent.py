"""The latent-attention policy of :mod:`sheeprl_tpu.models.decoder_lm` against
the benchmark's plain reference (chipbench/reference/ppo_lm_mla_ref.py), at a
small size on the CPU: the full (expanded) forward, the loss and every
gradient; prefill and then decode through the latent cache (absorbed) against
the reference's full forward; the expert shares and the shared experts; the
sigmoid router with its selection bias; and that each planted fault of the
reference, and bfloat16 weights, fail the tolerance the program passes."""

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
    path = os.path.join(ROOT, "chipbench", "reference", "ppo_lm_mla_ref.py")
    spec = importlib.util.spec_from_file_location("ppo_lm_mla_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
# a dense layer and three routed ones with shared experts; every expert held
SMALL = lm.DecoderConfig(
    hidden=64, heads=4, kv_heads=4, head_dim=24, layers=4, experts=8, top_k=2, expert_width=32, experts_held=8,
    expert_offset=0, vocab_held=48, window=0, rope_theta=1e6, eps=1e-6, rope_layout=(1,) * 4, window_layout=(0,) * 4,
    remat=True, attention="mla", router="sigmoid_bias", activation="silu", dense_layers=1, dense_width=96,
    shared_width=64, routed_scale=2.448, latent=32, nope_dim=16, rope_dim=8, v_dim=16,
)
P, R = 24, 8
# float32 against float32 at `highest`, sums of 64..96 products in another order: the gap is rounding, 1e-5 at these
# sizes; every planted fault and bfloat16 weights move the logits by 1e-2 or more (the last test)
TOL = dict(rtol=2e-4, atol=2e-4)


def ref_hyper(cfg: lm.DecoderConfig, bias_scale=0.05):
    return {
        "hidden": cfg.hidden, "heads": cfg.heads, "nope": cfg.nope_dim, "rope": cfg.rope_dim, "v": cfg.v_dim,
        "latent": cfg.latent, "layers": cfg.layers, "dense_layers": cfg.dense_layers, "dense_width": cfg.dense_width,
        "experts": cfg.experts, "top_k": cfg.top_k, "expert_width": cfg.expert_width, "shared_width": cfg.shared_width,
        "routed_scale": cfg.routed_scale, "experts_held": cfg.experts_held, "expert_offset": cfg.expert_offset,
        "vocab": cfg.vocab_held, "theta": cfg.rope_theta, "eps": cfg.eps, "bias_scale": bias_scale,
        "prompt_len": P, "response_len": R, "init_std": 0.3,
    }


def small_params(cfg=SMALL, seed=0):
    # a large init so that attention and routing are far from uniform, and a bias that moves selections
    return REF.init_params(ref_hyper(cfg), seed)


def tokens_of(cfg, batch, seq, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_held, jnp.int32)


def program_logits(cfg, params, tokens):
    x, counters, _ = jax.jit(lambda p, t: lm.forward(cfg, p, t))(params, tokens)
    return lm.heads(cfg, params, x), counters


def test_reference_and_program_share_a_parameter_layout():
    ours = lm.init_params(SMALL, jax.random.PRNGKey(0))
    theirs = small_params()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == [x.shape for x in jax.tree.leaves(theirs)]
    assert lm.parameter_count(ours) == sum(x.size for x in jax.tree.leaves(theirs))
    # the harness reads a leaf of three dimensions as the routed experts', one entry an expert: no other leaf has three
    three = {jax.tree_util.keystr(p).rsplit("'", 2)[-2] for p, x in jax.tree_util.tree_leaves_with_path(ours) if x.ndim == 3}
    assert three == {"w_gate", "w_up", "w_down"}
    assert "dense_gate" in ours["layers"][0] and "router" not in ours["layers"][0]
    assert "shared_gate" in ours["layers"][1] and ours["layers"][1]["router_bias"].shape == (SMALL.experts,)


def test_the_published_share_has_the_parameters_the_configuration_states():
    from sheeprl_tpu.config import compose

    cfg = compose(["exp=ppo_anakin_lm_kanana2", "algo.lm.num_hidden_layers=5", "algo.lm.experts_held=16",
                   "algo.lm.vocab_held=16032"])
    model = lm.DecoderConfig.from_config(cfg.algo.lm)
    assert (model.heads, model.head_dim, model.nope_dim, model.rope_dim, model.v_dim, model.latent) == (32, 192, 128, 64, 128, 512)
    assert (model.dense_layers, model.dense_width, model.shared_width, model.routed_scale) == (1, 6144, 1536, 2.448)
    shapes = jax.eval_shape(lambda key: lm.init_params(model, key), jax.random.PRNGKey(0))
    assert lm.parameter_count(shapes) == 575_958_017


def test_full_forward_matches_the_reference():
    params, tokens = small_params(), tokens_of(SMALL, 2, P + R)
    (logits, values), counters = program_logits(SMALL, params, tokens)
    h = ref_hyper(SMALL)
    for b in range(2):
        rx, rcounts = REF.forward(h, params, tokens[b])
        rlogits, rvalues = REF.heads(h, params, rx)
        np.testing.assert_allclose(logits[b], rlogits, **TOL)
        np.testing.assert_allclose(values[b], rvalues, **TOL)
    # every assignment lands on a held expert when all are held; the dense layer's row is zeros
    assert counters.shape == (4, 6) and not np.asarray(counters[0]).any()
    np.testing.assert_array_equal(counters[1:, 0], np.full(3, 2 * (P + R) * SMALL.top_k))
    assert not np.asarray(counters[:, 2]).any() and int(counters[1:, 5].sum()) > 0  # none dropped; the bias moved some


def _ppo_like_loss(logp, entropy, values, advantages):
    return -jnp.mean(logp * advantages) + 0.5 * jnp.mean((values - 0.3) ** 2) - 0.01 * jnp.mean(entropy)


def test_loss_and_every_gradient_match_the_reference():
    from sheeprl_tpu.algos.ppo.ppo_anakin_lm import LMPolicy, _response_outputs

    cfg = dataclasses.replace(SMALL, experts_held=4, expert_offset=2)  # a share: experts 2..5 of 8
    full = small_params()
    params = {**full, "layers": [{k: (v[2:6] if v.ndim == 3 else v) for k, v in layer.items()} for layer in full["layers"]]}
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
            assert scale > 0


def test_prefill_then_decode_through_the_latent_cache_equals_the_references_full_forward():
    """Position by position, 14 steps past the prompt: the absorbed path over
    the cached latents against the reference's expanded full forward, on
    logits and values."""
    params, tokens = small_params(), tokens_of(SMALL, 3, 26)
    prompt = 12
    h = ref_hyper(SMALL)
    want = [REF.heads(h, params, REF.forward(h, params, tokens[b])[0]) for b in range(3)]
    x, cache, _ = lm.prefill(SMALL, params, tokens[:, :prompt], 26)
    # the third kind of state: no head axis, latent + rope numbers a position
    assert [(c.shape, r.shape) for c, r in cache] == [((3, 26, 32), (3, 26, 8))] * 4
    step = jax.jit(lambda cache, tok, pos: lm.decode_step(SMALL, params, cache, tok, pos))
    for pos in range(prompt - 1, 26):
        if pos >= prompt:
            x, cache, counters = step(cache, tokens[:, pos], jnp.int32(pos))
            assert counters.shape == (4, 6) and not np.asarray(counters[:, 5]).any()  # decode counts no moved selection
        logits, values = lm.heads(SMALL, params, x)
        for b in range(3):
            np.testing.assert_allclose(logits[b], want[b][0][pos], **TOL, err_msg=f"position {pos}")
            np.testing.assert_allclose(values[b], want[b][1][pos], **TOL, err_msg=f"position {pos}")


def test_the_absorbed_and_the_expanded_path_agree_on_one_layers_attention():
    """The two paths through one attention, side by side on one layer's
    weights: the last position's output of the expanded form against a decode
    step over the cache the same positions filled."""
    params = small_params()
    layer, T = params["layers"][2], 20
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T, SMALL.hidden))
    expanded, (c, r), _ = lm._latent_layer(SMALL, 2, layer, x)
    state = (c.at[:, T - 1].set(0.0), r.at[:, T - 1].set(0.0))  # the step writes its own slot first
    absorbed, (c2, r2), _ = lm._latent_decode(SMALL, 2, layer, state, x[:, T - 1], jnp.int32(T - 1))
    np.testing.assert_allclose(absorbed, expanded[:, T - 1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c2, c, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(r2, r, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shares", [1, 8])
def test_the_routed_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer(shares):
    params = small_params()
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, SMALL.hidden))
    held = SMALL.experts // shares
    routed_sum, assignments, whole_on_each = 0.0, 0, []
    for s in range(shares):
        cfg = dataclasses.replace(SMALL, experts_held=held, expert_offset=s * held, shared_width=0)
        part = {k: (v[s * held : (s + 1) * held] if v.ndim == 3 else v) for k, v in layer.items()}
        # this share's layer output less its input and less the shared experts: its routed part alone
        with_shared, counters = lm._latent_feed_forward(dataclasses.replace(cfg, shared_width=64), 1, part, x)
        u = lm.rms_norm(x, layer["ln_post"], SMALL.eps)
        shared = lm._gated_mlp(SMALL, u, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        routed_sum = routed_sum + (with_shared - x - shared)
        whole_on_each.append(shared)
        assignments += int(counters[0])
        assert int(counters[2]) == 0
    total = x + routed_sum + whole_on_each[0]  # what every chip computes alike, counted once
    # the uncut layer, token by token, in numpy
    u = np.asarray(lm.rms_norm(x, layer["ln_post"], SMALL.eps))[0]
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    scores = 1 / (1 + np.exp(-(u @ np.asarray(layer["router"]))))
    want = np.asarray(x)[0].copy()
    for n in range(40):
        kept = np.argsort(-(scores[n] + np.asarray(layer["router_bias"])), kind="stable")[: SMALL.top_k]
        for e in kept:
            w = SMALL.routed_scale * scores[n, e] / scores[n, kept].sum()
            hid = silu(u[n] @ np.asarray(layer["w_gate"][e])) * (u[n] @ np.asarray(layer["w_up"][e]))
            want[n] += w * (hid @ np.asarray(layer["w_down"][e]))
        hid = silu(u[n] @ np.asarray(layer["shared_gate"])) * (u[n] @ np.asarray(layer["shared_up"]))
        want[n] += hid @ np.asarray(layer["shared_down"])
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=2e-4)
    assert assignments == 40 * SMALL.top_k


def test_the_bias_changes_who_is_kept_and_no_weight():
    router = small_params()["layers"][1]["router"]
    u = jax.random.normal(jax.random.PRNGKey(4), (64, SMALL.hidden))
    scores = jax.nn.sigmoid(u @ router)
    none = jnp.zeros((SMALL.experts,))
    weights0, experts0, moved0 = lm._route(SMALL, u, router, none)
    assert int(moved0) == 0
    np.testing.assert_array_equal(experts0, jax.lax.top_k(scores, SMALL.top_k)[1])
    # a bias that lifts expert 7 over everyone: it is kept by every token, weighted by its own unbiased score
    lifted = none.at[7].set(10.0)
    weights, experts, moved = lm._route(SMALL, u, router, lifted)
    assert (np.asarray(experts[:, 0]) == 7).all()
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(weights, SMALL.routed_scale * kept / kept.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), np.full(64, 2.448), rtol=1e-6)  # whoever is kept
    np.testing.assert_allclose(weights0.sum(-1), np.full(64, 2.448), rtol=1e-6)
    # moved: the tokens whose unbiased top 2 did not hold expert 7
    assert int(moved) == int(np.sum(~np.any(np.asarray(experts0) == 7, axis=-1)))
    # the public pair is the same routing
    np.testing.assert_array_equal(lm.route(SMALL, u, router, lifted)[1], experts)
    # and no gradient reaches the bias, while the router's weights get theirs
    g_bias, g_router = jax.grad(lambda b, r: jnp.sum(lm.route(SMALL, u, r, b)[0] ** 2), argnums=(0, 1))(lifted, router)
    assert not np.asarray(g_bias).any() and np.asarray(g_router).any()


def test_ties_go_to_the_lower_index_in_program_and_reference_alike():
    """All scores equal (a zero router): `top_k` keeps the lowest indices, and
    a bias reorders them; weights are equal shares of 2.448 either way."""
    u = jax.random.normal(jax.random.PRNGKey(4), (5, SMALL.hidden))
    router = jnp.zeros((SMALL.hidden, SMALL.experts))
    weights, experts, moved = lm._route(SMALL, u, router, jnp.zeros((SMALL.experts,)))
    np.testing.assert_array_equal(experts, np.tile([0, 1], (5, 1)))
    np.testing.assert_allclose(weights, np.full((5, 2), 1.224), rtol=1e-6)
    assert int(moved) == 0  # a tie is not a move: the kept are not under the unbiased threshold
    bias = jnp.zeros((SMALL.experts,)).at[jnp.array([6, 3])].set(jnp.array([0.2, 0.1]))
    weights, experts, _ = lm._route(SMALL, u, router, bias)
    np.testing.assert_array_equal(experts, np.tile([6, 3], (5, 1)))
    np.testing.assert_allclose(weights, np.full((5, 2), 1.224), rtol=1e-6)


UNWRITTEN = {
    "low_rank_queries": "algo.lm.q_lora_rank=1536", "rotary_scaling": "algo.lm.rope_scaling={type: yarn, factor: 4}",
    "selection_groups": "algo.lm.n_group=8", "kept_groups": "algo.lm.topk_group=4",
    "softmax_scores": "algo.lm.scoring_func=softmax", "greedy_selection": "algo.lm.topk_method=greedy",
    "unnormalised_weights": "algo.lm.norm_topk_prob=False", "rotate_half": "algo.lm.rope_interleave=False",
    "more_experts_held_than_there_are": "algo.lm.experts_held=200",
}


@pytest.mark.parametrize("case", list(UNWRITTEN))
def test_from_config_raises_on_what_is_not_written_down(case):
    from sheeprl_tpu.config import compose

    with pytest.raises(ValueError, match="written down|held of"):
        lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm_kanana2", UNWRITTEN[case]]).algo.lm)


def test_the_first_policys_error_names_the_other_router():
    from sheeprl_tpu.config import compose

    with pytest.raises(ValueError, match="latent-attention"):
        lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm", "algo.lm.norm_topk_prob=False"]).algo.lm)


FAULTS = ("rope_dropped", "latent_norm_dropped", "shared_skipped", "bias_dropped", "bfloat16_weights")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_the_tolerance_the_program_passes(fault):
    """The comparison is tight enough: the reference with one piece of the
    mathematics left out, or with its weights rounded to bfloat16, is not
    within the tolerance of the program's logits."""
    params, tokens = small_params(), tokens_of(SMALL, 1, P + R)
    (logits, _), _ = program_logits(SMALL, params, tokens)
    h = ref_hyper(SMALL)
    if fault == "bfloat16_weights":
        rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
        rlogits, _ = REF.heads(h, rounded, REF.forward(h, rounded, tokens[0])[0])
    else:
        rlogits, _ = REF.heads(h, params, REF.forward(h, params, tokens[0], fault)[0])
    gap = np.abs(np.asarray(logits[0]) - np.asarray(rlogits))
    allowed = TOL["atol"] + TOL["rtol"] * np.abs(np.asarray(rlogits))
    assert (gap > allowed).any() and float(gap.max()) > 1e-2
