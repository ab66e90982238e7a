"""The gated grouped-query policy of :mod:`sheeprl_tpu.models.decoder_lm` (the
``afmoe`` layer) against the benchmark's plain reference
(chipbench/reference/ppo_lm_afmoe_ref.py), at a small size on the CPU: the full
forward, the loss and every gradient; prefill and then decode through the
window ring and the full buffer against the reference's full forward; the
expert shares with the shared expert and the dense layer counted once; that
the per-layer remat keeps each sublayer output norm's input, so the gradient
runs the routed share no more often than without remat; what
``from_config`` refuses; the cut's parameter count; and that leaving out each
element this layer adds (the output gate, the q/k norms, the sandwich norms,
the embedding's scale, the full layer without rotary encoding) fails the
tolerance the program passes."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder_lm as lm
from sheeprl_tpu.ops import kernels as K
from sheeprl_tpu.ops.kernels.attn import RESIDUALS
from tests.test_models.test_decoder_lm import (
    assert_the_kernel_tiers_remat_keeps_its_residuals,
    assert_the_reference_tiers_remat_is_a_plain_checkpoint,
    primitive_counts,
    remat_programs,
    update_loss,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "trinity_mini_26b_a3b_ep8.json")


def load_reference():
    path = os.path.join(ROOT, "chipbench", "reference", "ppo_lm_afmoe_ref.py")
    spec = importlib.util.spec_from_file_location("ppo_lm_afmoe_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
LOCAL = (1, 1, 1, 0, 1)  # the published period's first five layers: three window layers, a full one, a window one
# a dense layer, then four routed ones with a shared expert; every expert held; 32 positions are four windows
SMALL = lm.DecoderConfig(
    hidden=64, heads=4, kv_heads=2, head_dim=16, layers=5, experts=8, top_k=2, expert_width=32, experts_held=8,
    expert_offset=0, vocab_held=48, window=8, rope_theta=1e4, eps=1e-5, rope_layout=LOCAL, window_layout=LOCAL,
    remat=True, router="sigmoid_bias", activation="silu", dense_layers=1, dense_width=96, shared_width=32,
    routed_scale=2.826, attn_gate=True, qk_norm=True, post_norms=True, embed_scale=8.0,
)
P, R = 24, 8
# float32 against float32 at `highest`: the gap is rounding, 1e-5 at these sizes; leaving out any element of the
# layer, or bfloat16 weights, moves the logits by 1e-2 or more (the last test)
TOL = dict(rtol=2e-4, atol=2e-4)


def ref_hyper(cfg: lm.DecoderConfig, bias_scale=0.05):
    return {
        "hidden": cfg.hidden, "heads": cfg.heads, "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "layers": cfg.layers, "dense_layers": cfg.dense_layers, "dense_width": cfg.dense_width, "experts": cfg.experts,
        "top_k": cfg.top_k, "expert_width": cfg.expert_width, "shared_width": cfg.shared_width,
        "routed_scale": cfg.routed_scale, "experts_held": cfg.experts_held, "expert_offset": cfg.expert_offset,
        "vocab": cfg.vocab_held, "theta": cfg.rope_theta, "eps": cfg.eps, "window": cfg.window,
        "local": [bool(x) for x in cfg.window_layout], "embed_scale": cfg.embed_scale, "bias_scale": bias_scale,
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
    three = {jax.tree_util.keystr(p).rsplit("'", 2)[-2] for p, x in jax.tree_util.tree_leaves_with_path(ours) if x.ndim == 3}
    assert three == {"w_gate", "w_up", "w_down"}
    assert "dense_gate" in ours["layers"][0] and "router" not in ours["layers"][0]
    assert {"wg", "ln_q", "ln_k", "ln_attn_out", "ln_ffn_out", "shared_gate", "router_bias"} <= set(ours["layers"][1])
    assert ours["layers"][1]["ln_q"].shape == (SMALL.head_dim,) and ours["layers"][1]["wg"].shape == (64, 4 * 16)


def _cut(conf):
    from sheeprl_tpu.config import compose

    cfg = compose(["exp=ppo_anakin_lm", *conf["overrides"]])
    return lm.DecoderConfig.from_config(cfg.algo.lm)


def test_the_cut_has_the_parameters_its_configuration_file_states():
    with open(CONFIG) as f:
        conf = json.load(f)
    model = _cut(conf)
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim, model.window) == (2048, 32, 4, 128, 2048)
    assert (model.experts, model.experts_held, model.top_k, model.expert_width, model.vocab_held) == (128, 16, 8, 1024, 25024)
    assert (model.dense_layers, model.dense_width, model.shared_width, model.routed_scale) == (1, 6144, 1024, 2.826)
    assert model.window_layout == model.rope_layout == (1, 1, 1, 0, 1) and model.embed_scale == pytest.approx(2048**0.5)
    assert (model.attn_gate, model.qk_norm, model.post_norms, model.router) == (True, True, True, "sigmoid_bias")
    shapes = jax.eval_shape(lambda key: lm.init_params(model, key), jax.random.PRNGKey(0))
    assert lm.parameter_count(shapes) == conf["parameters"] == 705_476_353
    # the count part by part: attention a layer, the dense layer, a routed layer, embedding and head, the rest
    layers = [sum(int(x.size) for x in jax.tree.leaves(layer)) for layer in shapes["layers"]]
    attention = sum(int(shapes["layers"][1][k].size) for k in ("wq", "wk", "wv", "wg", "wo", "ln_q", "ln_k"))
    assert attention == 27_263_232 and layers == [65_020_160] + [134_488_448] * 4
    assert sum(int(shapes["layers"][1][k].size) for k in ("w_gate", "w_up", "w_down")) == 100_663_296
    assert shapes["embed"].size + shapes["head"].size == 102_498_304


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
    assert counters.shape == (5, 6) and not np.asarray(counters[0]).any()
    np.testing.assert_array_equal(counters[1:, 0], np.full(4, 2 * (P + R) * SMALL.top_k))
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
            assert scale > 0, jax.tree_util.keystr(path)


def test_the_remat_keeps_the_splash_kernels_residuals_gated_attention(monkeypatch):
    """A dense window layer and a routed full layer, 256 positions on blocks of 128."""
    cfg = dataclasses.replace(SMALL, layers=2, window=128, rope_layout=(1, 0), window_layout=(1, 0))
    assert_the_kernel_tiers_remat_keeps_its_residuals(cfg, small_params(cfg), tokens_of(cfg, 1, 256), monkeypatch)


def test_the_reference_tiers_remat_is_the_plain_checkpoints_program_gated_attention(monkeypatch):
    assert_the_reference_tiers_remat_is_a_plain_checkpoint(SMALL, small_params(), tokens_of(SMALL, 2, P + R), monkeypatch)


def test_the_remat_keeps_each_output_norms_input_so_the_routed_share_runs_forward_as_often_as_without_remat(monkeypatch):
    """A dense layer and a routed one whose call compacts (2 of 8 experts held, 512 tokens: 512 of 1024 rows
    moved, so the share is a branch of two), on the reference attention tier. With the input of each sublayer output
    norm kept, the backward does not run the sublayer again to rebuild it: the gradient program holds as many
    grouped products as the program without remat (24; 30 under the policy that kept the attention kernel's
    residuals alone, whose recompute ran the routed share's forward once more), and its gradients are that
    policy's."""
    cfg = dataclasses.replace(SMALL, layers=2, rope_layout=(1, 0), window_layout=(1, 0), experts_held=2, expert_offset=2)
    full = small_params(dataclasses.replace(cfg, experts_held=8, expert_offset=0))
    params = {**full, "layers": [{k: (v[2:4] if v.ndim == 3 else v) for k, v in layer.items()} for layer in full["layers"]]}
    tokens = tokens_of(cfg, 2, 256)
    assert lm.compact_rows(cfg, 512) < 512 * cfg.top_k
    residuals_alone = jax.checkpoint_policies.save_only_these_names(RESIDUALS)
    with K.use_backend("lax"):
        (kept_jaxpr, kept), (parent_jaxpr, parent) = remat_programs(cfg, params, tokens, monkeypatch, residuals_alone)
        unremat = dataclasses.replace(cfg, remat=False)
        unremat_jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: update_loss(unremat, p, t)))(params, tokens)
    products = [primitive_counts(j)["ragged_dot_general"] for j in (kept_jaxpr, unremat_jaxpr, parent_jaxpr)]
    assert products == [24, 24, 30]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept), jax.tree.leaves(parent)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7, err_msg=jax.tree_util.keystr(path))


def test_prefill_then_decode_through_the_window_ring_and_the_full_buffer_equals_the_references_full_forward():
    """Position by position, 14 steps past a 12-token prompt, the window of 8
    wrapping its ring: keys cached normed and rotated (window layers) or
    normed alone (the full layer) against the reference's full forward, on
    logits and values."""
    params, tokens = small_params(), tokens_of(SMALL, 3, 26)
    prompt = 12
    h = ref_hyper(SMALL)
    want = [REF.heads(h, params, REF.forward(h, params, tokens[b])[0]) for b in range(3)]
    x, cache, _ = lm.prefill(SMALL, params, tokens[:, :prompt], 26)
    ring, buffer = ((3, 8, 2, 16),) * 2, ((3, 26, 2, 16),) * 2
    assert [(k.shape, v.shape) for k, v in cache] == [ring, ring, ring, buffer, ring]
    step = jax.jit(lambda cache, tok, pos: lm.decode_step(SMALL, params, cache, tok, pos))
    for pos in range(prompt - 1, 26):
        if pos >= prompt:
            x, cache, counters = step(cache, tokens[:, pos], jnp.int32(pos))
            assert counters.shape == (5, 6) and not np.asarray(counters[:, 5]).any()  # decode counts no moved selection
        logits, values = lm.heads(SMALL, params, x)
        for b in range(3):
            np.testing.assert_allclose(logits[b], want[b][0][pos], **TOL, err_msg=f"position {pos}")
            np.testing.assert_allclose(values[b], want[b][1][pos], **TOL, err_msg=f"position {pos}")


@pytest.mark.parametrize("shares", [1, 8])
def test_the_routed_shares_with_the_shared_expert_and_the_dense_layer_once_add_up_to_the_uncut_layer(shares):
    """Each share's routed part (its layer less the shared expert, before the output norm, which a deployment
    applies after the exchange) summed over the shares, the shared expert added once and the norm applied,
    against the uncut layer token by token in numpy; the dense layer is the same on every share."""
    params = small_params()
    layer, dense = params["layers"][1], params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, SMALL.hidden))
    held = SMALL.experts // shares
    u = lm.rms_norm(x, layer["ln_post"], SMALL.eps)
    shared = lm._gated_mlp(SMALL, u, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    routed_sum, assignments, dense_outs = 0.0, 0, []
    for s in range(shares):
        cfg = dataclasses.replace(SMALL, experts_held=held, expert_offset=s * held, post_norms=False)
        part = {k: (v[s * held : (s + 1) * held] if v.ndim == 3 else v) for k, v in layer.items()}
        out, counters = lm._feed_forward(cfg, 1, part, x)
        routed_sum = routed_sum + (out - x - shared)  # this share's routed part alone
        dense_outs.append(lm._feed_forward(dataclasses.replace(SMALL, experts_held=held, expert_offset=s * held), 0, dense, x)[0])
        assignments += int(counters[0])
        assert int(counters[2]) == 0
    total = x + lm.rms_norm(routed_sum + shared, layer["ln_ffn_out"], SMALL.eps)  # what every chip computes alike, once
    # the uncut layer, token by token, in numpy
    un, xn = np.asarray(u)[0], np.asarray(x)[0]
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    scores = 1 / (1 + np.exp(-(un @ np.asarray(layer["router"]))))
    y = np.zeros_like(xn)
    for n in range(40):
        kept = np.argsort(-(scores[n] + np.asarray(layer["router_bias"])), kind="stable")[: SMALL.top_k]
        for e in kept:
            w = SMALL.routed_scale * scores[n, e] / scores[n, kept].sum()
            y[n] += w * ((silu(un[n] @ np.asarray(layer["w_gate"][e])) * (un[n] @ np.asarray(layer["w_up"][e]))) @ np.asarray(layer["w_down"][e]))
        y[n] += (silu(un[n] @ np.asarray(layer["shared_gate"])) * (un[n] @ np.asarray(layer["shared_up"]))) @ np.asarray(layer["shared_down"])
    y = y / np.sqrt(np.mean(y**2, axis=-1, keepdims=True) + SMALL.eps) * np.asarray(layer["ln_ffn_out"])
    np.testing.assert_allclose(total[0], xn + y, rtol=2e-4, atol=2e-4)
    assert assignments == 40 * SMALL.top_k
    for other in dense_outs[1:]:
        np.testing.assert_array_equal(other, dense_outs[0])


UNWRITTEN = {
    "softmax_scores": "algo.lm.score_func=softmax", "unnormalised_weights": "algo.lm.route_norm=False",
    "selection_groups": "algo.lm.n_group=8", "expert_groups": "algo.lm.num_expert_groups=2",
    "limited_groups": "algo.lm.num_limited_groups=2", "kept_groups": "algo.lm.topk_group=4",
    "rotary_scaling": "algo.lm.rope_scaling={type: yarn, factor: 4}", "another_activation": "algo.lm.hidden_act=gelu",
    "tied_head": "algo.lm.tie_word_embeddings=True", "full_layers_elsewhere": "algo.lm.global_attn_every_n_layers=2",
    "another_layer_kind": "algo.lm.layer_types=[chunked_attention, full_attention]",
    "mup_beyond_the_embedding_scale": "algo.lm.mup_attention_multiplier=2.0",
    "more_experts_held_than_there_are": "algo.lm.experts_held=200",
}


@pytest.mark.parametrize("case", list(UNWRITTEN))
def test_from_config_raises_on_what_is_not_written_down(case):
    from sheeprl_tpu.config import compose

    with pytest.raises(ValueError, match="written down|held of"):
        lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm_trinity", UNWRITTEN[case]]).algo.lm)


def test_mup_off_leaves_the_embedding_unscaled():
    from sheeprl_tpu.config import compose

    assert lm.DecoderConfig.from_config(compose(["exp=ppo_anakin_lm_trinity", "algo.lm.mup_enabled=False"]).algo.lm).embed_scale == 1.0


FAULTS = ("gate_dropped", "qk_norm_dropped", "post_norms_dropped", "embed_scale_dropped", "rope_on_full",
          "bias_dropped", "shared_skipped", "bfloat16_weights")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_element_left_out_fails_the_tolerance_the_program_passes(fault):
    """The comparison is tight enough: the reference with one element of the
    layer left out (or the full layer given rotary encoding), or with its
    weights rounded to bfloat16, is not within the tolerance of the program's
    logits."""
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


@pytest.mark.parametrize("kind", ["attn_gate", "qk_norm", "post_norms", "embed_scale", "rope_layout"])
def test_the_program_without_each_element_fails_the_references_tolerance(kind):
    """The other way round: the program with one of its static kinds turned off (the full layer given rotary
    encoding for ``rope_layout``) is not within the tolerance of the reference."""
    off = {"attn_gate": False, "qk_norm": False, "post_norms": False, "embed_scale": 1.0, "rope_layout": (1,) * 5}[kind]
    cfg = dataclasses.replace(SMALL, **{kind: off})
    params, tokens = small_params(), tokens_of(SMALL, 1, P + R)
    (logits, _), _ = program_logits(cfg, params, tokens)
    h = ref_hyper(SMALL)
    rlogits, _ = REF.heads(h, params, REF.forward(h, params, tokens[0])[0])
    gap = np.abs(np.asarray(logits[0]) - np.asarray(rlogits))
    assert (gap > TOL["atol"] + TOL["rtol"] * np.abs(np.asarray(rlogits))).any() and float(gap.max()) > 1e-2
