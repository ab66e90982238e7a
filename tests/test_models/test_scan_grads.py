"""``RSSM.dynamic_rollout`` over ``scan_dense_grads_after``
(``models/scan_grads.py``) against a plain closed-over ``lax.scan`` of
``RSSM.dynamic``: same values, same gradients, no weight-gradient accumulator
left in the backward loop, and nothing in either loop that feeds no carry (the
transition model, the ``embedded`` half of the representation model). The plain
rollout, which the trainers ran before, lives on here only."""

import functools

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3.agent import RSSM, RecurrentModel, _StochHead
from sheeprl_tpu.analysis.hlo import while_body_shapes, while_carried_shapes

T, B = 6, 3
ACTIONS, EMBED = 3, 10
RECURRENT, DENSE, HIDDEN, TRANSITION_HIDDEN = 24, 8, 12, 14
STOCH, DISCRETE = 4, 4
#: the width of ``concat([recurrent_state, embedded])``: no other array of the rollout has a dimension of it
CONCAT = RECURRENT + EMBED
#: the GRU's fused projection: no other array of the rollout has this shape
FUSED = ("f32", (RECURRENT + DENSE, 3 * RECURRENT))
#: every other kernel a step of the coupled rollout applies, each shape its own too: the recurrent MLP's, the ``rec``
#: half of the representation model's first layer (sliced off the ``(CONCAT, HIDDEN)`` leaf), its output layer's
STEP_KERNELS = {
    FUSED,
    ("f32", (STOCH * DISCRETE + ACTIONS, DENSE)),
    ("f32", (RECURRENT, HIDDEN)),
    ("f32", (HIDDEN, STOCH * DISCRETE)),
}
#: the transition model's two kernels: no other array of the rollout has either shape
TRANSITION = {("f32", (RECURRENT, TRANSITION_HIDDEN)), ("f32", (TRANSITION_HIDDEN, STOCH * DISCRETE))}

IS_FIRST = {
    "start_only": np.zeros((T, B, 1), np.float32),
    "mid_sequence_resets": np.zeros((T, B, 1), np.float32),
}
IS_FIRST["start_only"][0] = 1.0
IS_FIRST["mid_sequence_resets"][0] = 1.0
IS_FIRST["mid_sequence_resets"][2, 1] = 1.0
IS_FIRST["mid_sequence_resets"][4, 0] = 1.0
IS_FIRST["mid_sequence_resets"][4, 2] = 1.0


def plain_rollout(rssm, wmp, embedded, actions, is_first, key):
    """The rollout as a scan that closes over the parameters and evaluates the
    initial states inside every step."""
    rec0 = jnp.zeros((actions.shape[1], rssm.recurrent_model.recurrent_state_size), embedded.dtype)
    if rssm.decoupled:
        k_repr, key = jax.random.split(key)
        post_logits, posts = rssm._representation(wmp, None, embedded, k_repr)
        posts_prev = jnp.concatenate([jnp.zeros_like(posts[:1]), posts[:-1]], axis=0)

        def step_dec(rec, xs):
            rec, prior_logits = rssm.dynamic_decoupled(wmp, xs[0], rec, xs[1], xs[2])
            return rec, (rec, prior_logits)

        _, (recs, prior_logits) = jax.lax.scan(step_dec, rec0, (posts_prev, actions, is_first))
        return recs, posts, post_logits, prior_logits

    def step(carry, xs):
        rec, post, post_logits, prior_logits = rssm.dynamic(wmp, carry[1], carry[0], xs[1], xs[0], xs[2], xs[3])
        return (rec, post), (rec, post, post_logits, prior_logits)

    post0 = jnp.zeros((actions.shape[1], rssm.transition_model.stoch_state_size), embedded.dtype)
    keys = jax.random.split(key, actions.shape[0])
    return jax.lax.scan(step, (rec0, post0), (embedded, actions, is_first, keys))[1]


def _rssm_and_inputs(decoupled, seed=0):
    rssm = RSSM(
        recurrent_model=RecurrentModel(recurrent_state_size=RECURRENT, dense_units=DENSE),
        representation_model=_StochHead(hidden_size=HIDDEN, stoch_state_size=STOCH * DISCRETE),
        transition_model=_StochHead(hidden_size=TRANSITION_HIDDEN, stoch_state_size=STOCH * DISCRETE),
        discrete=DISCRETE,
        decoupled=decoupled,
    )
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    stoch = STOCH * DISCRETE
    wmp = {
        "recurrent_model": rssm.recurrent_model.init(k[0], jnp.zeros((B, stoch + ACTIONS)), jnp.zeros((B, RECURRENT))),
        "representation_model": rssm.representation_model.init(
            k[1], jnp.zeros((B, EMBED if decoupled else RECURRENT + EMBED))
        ),
        "transition_model": rssm.transition_model.init(k[2], jnp.zeros((B, RECURRENT))),
        "initial_recurrent_state": 0.5 * jax.random.normal(k[3], (RECURRENT,)),
    }
    embedded = jax.random.normal(k[4], (T, B, EMBED))
    actions = jax.nn.one_hot(jax.random.randint(k[5], (T, B), 0, ACTIONS), ACTIONS)
    # a fixed random read-out of the four outputs, so every output's cotangent differs
    widths = (RECURRENT, stoch, stoch, stoch)
    weights = [jax.random.normal(kk, (T, B, n)) for kk, n in zip(jax.random.split(k[6], 4), widths)]
    return rssm, wmp, embedded, actions, weights, k[7]


def _loss(rollout, rssm, is_first, weights, key):
    def loss(wmp, embedded, actions):
        outs = rollout(rssm, wmp, embedded, actions, is_first, key)
        return sum(jnp.sum(w * o) for w, o in zip(weights, outs)), outs

    return loss


def _assert_close(got, want, what, rel=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want)))
    assert scale > 0, f"{what}: the plain gradient is zero, the case tests nothing"
    assert float(np.max(np.abs(got - want))) <= rel * scale, what


@pytest.mark.parametrize("pattern", sorted(IS_FIRST))
@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_rollout_gradients_match_plain_scan(decoupled, pattern):
    rssm, wmp, embedded, actions, weights, key = _rssm_and_inputs(decoupled)
    is_first = jnp.asarray(IS_FIRST[pattern])
    ((_, outs), (g_wmp, g_emb, g_act)), ((_, outs_plain), (g_wmp_plain, g_emb_plain, g_act_plain)) = [
        jax.jit(jax.value_and_grad(_loss(rollout, rssm, is_first, weights, key), argnums=(0, 1, 2), has_aux=True))(
            wmp, embedded, actions
        )
        for rollout in (RSSM.dynamic_rollout, plain_rollout)
    ]
    names = ("recurrent_states", "posteriors", "posterior_logits", "prior_logits")
    for name, got, want in zip(names, outs, outs_plain):
        _assert_close(got, want, name)
    _assert_close(g_emb, g_emb_plain, "embedded")
    _assert_close(g_act, g_act_plain, "actions")
    paths = jax.tree_util.tree_flatten_with_path(g_wmp_plain)[0]
    assert jax.tree.structure(g_wmp) == jax.tree.structure(g_wmp_plain)
    for (path, want), got in zip(paths, jax.tree.leaves(g_wmp)):
        _assert_close(got, want, jax.tree_util.keystr(path))


def test_rollout_values_match_without_differentiation():
    rssm, wmp, embedded, actions, _, key = _rssm_and_inputs(False)
    is_first = jnp.asarray(IS_FIRST["mid_sequence_resets"])
    for got, want in zip(
        rssm.dynamic_rollout(wmp, embedded, actions, is_first, key),
        plain_rollout(rssm, wmp, embedded, actions, is_first, key),
    ):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _gradient_hlo(decoupled, hoisted):
    """The optimized executable of the rollout's gradient (``RSSM.dynamic_rollout`` if ``hoisted``, else the plain
    scan) with resets in mid-sequence, as text; both structural tests read the same four programs."""
    rssm, wmp, embedded, actions, weights, key = _rssm_and_inputs(decoupled)
    rollout = RSSM.dynamic_rollout if hoisted else plain_rollout
    loss = _loss(rollout, rssm, jnp.asarray(IS_FIRST["mid_sequence_resets"]), weights, key)
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    return grad.lower(wmp, embedded, actions).compile().as_text()


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_backward_loop_carries_no_weight_gradient(decoupled):
    """The guard against the accumulation coming back: in the optimized
    executable of the rollout's gradient, no ``while`` carries a kernel's
    shape more than once (the weight itself). The plain scan carries the fused
    kernel's twice in its backward loop: weight and accumulator."""
    most = {}
    for hoisted in (True, False):
        loops = while_carried_shapes(_gradient_hlo(decoupled, hoisted))
        assert len(loops) >= 2, "expected a forward and a backward loop"
        most[hoisted] = {kernel: max(shapes.count(kernel) for shapes in loops) for kernel in STEP_KERNELS}
    assert most[False][FUSED] == 2, "the plain scan no longer shows the accumulator: the test has lost its teeth"
    assert max(most[True].values()) <= 1, most[True]


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_loops_hold_only_what_feeds_the_carry(decoupled):
    """In the optimized executable of the rollout's gradient, no loop's body
    (its carry is the body's parameter) names an array with a transition
    kernel's shape or a dimension as wide as ``concat([rec, embedded])``: the
    transition model runs after the loop, the ``embedded`` half of the
    representation model's first layer before it. The plain scan holds both."""

    def found(hoisted):
        bodies = while_body_shapes(_gradient_hlo(decoupled, hoisted))
        assert len(bodies) >= 2, "expected a forward and a backward loop"
        shapes = set().union(*bodies)
        return bool(shapes & TRANSITION), any(CONCAT in dims for _, dims in shapes)

    # the decoupled posterior never was in the loop: only its transition model shows there
    assert found(False) == (True, not decoupled), "the plain scan no longer shows them: the test has lost its teeth"
    assert found(True) == (False, False)


def test_train_step_matches_plain_rollout(tmp_path, monkeypatch):
    """One ``make_train_step`` gradient step with the hoisted rollout against
    the same step with the plain one: the losses, and every parameter's change."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.optim.builders import build_optimizer
    from sheeprl_tpu.parallel.fabric import Fabric

    cfg = compose(
        [
            "exp=dreamer_v3",
            "algo=dreamer_v3_XS",
            "env=dummy",
            "algo.per_rank_batch_size=2",
            "algo.per_rank_sequence_length=6",
            "algo.horizon=3",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.world_model.reward_model.bins=17",
            "algo.critic.bins=17",
            "algo.cnn_keys.encoder=[]",
            "algo.cnn_keys.decoder=[]",
            "algo.mlp_keys.encoder=[state]",
            "algo.mlp_keys.decoder=[state]",
            f"log_root={tmp_path}",
        ]
    )
    fabric = Fabric(devices=1)
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-20, 20, (10,), np.float32)})
    world_model, actor, critic, params, _ = build_agent(fabric, (3,), False, cfg, obs_space)
    txs = {
        name: build_optimizer(cfg.algo[key].optimizer, max_grad_norm=cfg.algo[key].clip_gradients)
        for name, key in (("world", "world_model"), ("actor", "actor"), ("critic", "critic"))
    }
    opts = {
        "world": txs["world"].init(params["world_model"]),
        "actor": txs["actor"].init(params["actor"]),
        "critic": txs["critic"].init(params["critic"]),
    }
    rng = np.random.default_rng(0)
    G, T_, B_ = 1, 6, 2
    data = {
        "state": rng.normal(size=(G, T_, B_, 10)).astype(np.float32),
        "actions": np.eye(3, dtype=np.float32)[rng.integers(0, 3, (G, T_, B_))],
        "rewards": rng.normal(size=(G, T_, B_, 1)).astype(np.float32),
        "terminated": np.zeros((G, T_, B_, 1), np.float32),
        "truncated": np.zeros((G, T_, B_, 1), np.float32),
        "is_first": np.zeros((G, T_, B_, 1), np.float32),
    }
    data["is_first"][:, 3, 0] = 1.0
    data["terminated"][:, 2, 0] = 1.0

    before = jax.device_get((params, opts))

    def one_step():
        train_fn = make_train_step(world_model, actor, critic, cfg, fabric.mesh, (3,), False, txs)
        p, o = jax.tree.map(jnp.asarray, before)  # the step donates its state
        new_params, _, _, metrics = train_fn(p, o, init_moments(), data, jax.random.PRNGKey(0), jnp.int32(0))
        return jax.device_get(new_params), jax.device_get(metrics)

    hoisted_params, hoisted_metrics = one_step()
    monkeypatch.setattr(RSSM, "dynamic_rollout", plain_rollout)
    plain_params, plain_metrics = one_step()

    # world_model_loss, policy_loss, value_loss
    for i in (0, 8, 9):
        np.testing.assert_allclose(hoisted_metrics[i], plain_metrics[i], rtol=1e-5)
    paths_and_old = jax.tree_util.tree_flatten_with_path(before[0])[0]
    for (path, old), new, want in zip(paths_and_old, jax.tree.leaves(hoisted_params), jax.tree.leaves(plain_params)):
        change = np.linalg.norm(want - old)
        assert np.linalg.norm(new - want) <= 1e-3 * change + 1e-9, jax.tree_util.keystr(path)

