"""Plain reference of token-level PPO on a decoder language model whose layers
mix positions by a gated short convolution or by grouped-query attention with
per-head q/k norms, with a sigmoid router with a selection bias after the
mixer and no shared expert, leading dense layers and a head tied to the
embedding: float32 `jax.numpy`, matrix products at `highest` precision, no
kernels, no cache, no optax, nothing of the program.

The model is the published `lfm2_moe` layer (LiquidAI, config.json of
LFM2-24B-A2B; the mixers and the decoder layer from the dense LFM2's modeling
code in Hugging Face `transformers`, `models/lfm2/modeling_lfm2.py`, whose
layers are the same; the routed block from LiquidAI's `lfm2_moe` modeling
code), cut to one chip's share of a stated deployment: `experts_held` experts
from `expert_offset` of the `experts` the router scores, `vocab` rows of the
embedding (which is also the head), `layers` layers of which the first
`dense_layers` are dense. For layer input `x` (T x hidden), RMSNorm without
bias:

    h        = norm_in(x)
    conv layer:       [b | c | v] = h W_in;  z_t = b_t * v_t
                      s_t = sum_{i=0..K-1} w_i * z_{t-K+1+i}      z before position 0 is 0, K = conv_kernel
                      x' = x + (c * s) W_out
    attention layer:  q, k, v = norm_q(h Wq), norm_k(h Wk), h Wv  per head of head_dim = hidden / heads
                      q, k = rope(q), rope(k)                       rotate-half, theta, every attention layer
                      o = attention(q, k, v)                        full causal, kv_heads shared by heads / kv_heads
                      x' = x + o Wo
    u        = norm_post(x')
    dense layer:   y = x' + Wdown (silu(Wgate u) * (Wup u))
    routed layer:  g = sigmoid(u Wr);  kept = top_k of (g + b);  w_e = scale * g_e / (sum_kept g + 1e-6)
                   y = x' + sum_{e kept and held} w_e E_e(u)                E_e SwiGLU
    logits   = norm_f(x) embed^T                                  the head tied to the embedding

What an expert that is not held would have added is left out, here as in the
program; the mixers and the dense layer are whole. `b` is no trained weight:
it reaches the result through `top_k`'s indices alone, so its gradient is
zero and Adam leaves it as it was. Departures from the published model, each
also in the configuration's `assumed`: the bias is drawn from the seed
(`bias_scale`) and frozen, where the published model updates it from the
experts' loads; the critic is one linear layer hidden -> 1 (with bias) on the
final normed hidden state; no reference-policy KL term.

GAE, the PPO losses, the clip, Adam, the sketches and `follow` /
`rollout_readings` are `ppo_lm_ref.py`'s, loaded by path as this module's own
instance whose `response_outputs` is the one below, and so is the masked
attention in query blocks: one PPO arithmetic for the four families.
`compute="bfloat16"` is the control. `fault` plants a fault: `conv_gate_dropped`
(the convolution's output gate `c` left out), `taps_reversed` (the taps read
the positions ahead of the query and not behind it: anti-causal),
`conv_state_dropped` (in the rollout only: the convolution at a response
position reads zeros where the prompt's gated inputs were, as a decode that
did not take prefill's state would), `qk_norm_dropped`, `head_untied` (in the
gradient steps only: the head's product reads the embedding under a stopped
gradient, so the tied matrix learns from its input end alone), `bias_dropped`
(selection by the unbiased scores), `half_batch` (`ppo_lm_ref.follow`'s: the
losses over the first half of the response only).
"""

from __future__ import annotations

import importlib.util
import os
import sys
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _own_instance_of(name: str):
    """`reference/<name>.py` as a module of this file's own (not the one `run.py` may have loaded)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_reference_{name}_for_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_ppo = _own_instance_of("ppo_lm_ref")
HIGHEST, QUERY_BLOCK, SKETCH = _ppo.HIGHEST, _ppo.QUERY_BLOCK, _ppo.SKETCH
leaf_names, entry_names, gae = _ppo.leaf_names, _ppo.entry_names, _ppo.gae
_dot, _rmsnorm, _rope, _attention = _ppo._dot, _ppo._rmsnorm, _ppo._rope, _ppo._attention
BIAS = "router_bias"
CONV = "conv"
ROUTE_EPS = 1e-6  # lfm2_moe's: the kept scores over their sum plus this
ROLLOUT_ONLY = ("conv_state_dropped",)  # faults of the rollout's decode: the gradient steps are the reference's


def hyper(as_run: Dict[str, Any], assumed: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """Widths from the configuration file (the published key names); the recipe's scalars from the composed config."""
    a = lambda k: as_run["algo.lm." + k]  # noqa: E731
    opt = cfg.algo.optimizer
    layers = int(a("num_hidden_layers"))
    return {
        "hidden": a("hidden_size"), "heads": a("num_attention_heads"), "kv_heads": a("num_key_value_heads"),
        "head_dim": a("hidden_size") // a("num_attention_heads"), "layers": layers,
        "dense_layers": min(int(a("num_dense_layers")), layers), "dense_width": a("intermediate_size"),
        "experts": a("num_experts"), "top_k": a("num_experts_per_tok"), "expert_width": a("moe_intermediate_size"),
        "routed_scale": float(a("routed_scaling_factor")), "experts_held": a("experts_held"),
        "expert_offset": a("expert_offset"), "vocab": a("vocab_held"), "theta": float(a("rope_parameters")["rope_theta"]),
        "eps": float(a("norm_eps")), "conv_kernel": int(a("conv_L_cache")),
        "conv": [t == CONV for t in list(a("layer_types"))[:layers]], "bias_scale": float(assumed["router_bias_scale"]),
        "prompt_len": int(cfg.env.prompt_len), "response_len": int(cfg.algo.rollout_steps),
        "num_envs": int(cfg.env.num_envs), "minibatch": int(cfg.algo.per_rank_batch_size),
        "update_epochs": int(cfg.algo.update_epochs), "gamma": float(cfg.algo.gamma),
        "gae_lambda": float(cfg.algo.gae_lambda), "clip_coef": float(cfg.algo.clip_coef),
        "vf_coef": float(cfg.algo.vf_coef), "ent_coef": float(cfg.algo.ent_coef),
        "normalize_advantages": bool(cfg.algo.normalize_advantages), "clip_vloss": bool(cfg.algo.clip_vloss),
        "max_grad_norm": float(cfg.algo.max_grad_norm), "lr": float(opt.lr), "adam_eps": float(opt.eps),
        "b1": float(opt.betas[0]), "b2": float(opt.betas[1]), "init_std": 0.02,
    }


_MAKERS: Dict[Any, Any] = {}  # one jitted maker a layout: `follow` calls `make_params` after every step


def init_params(h: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Normal(0, init_std) matrices and taps, unit norms, zero value bias, the
    selection bias normal(0, bias_scale); one jitted call, traced once a
    layout. Every leaf but the routed experts' three is of one or two
    dimensions (the harness reads a leaf of three as one entry an expert)."""
    H, V, D, F, E = h["hidden"], h["vocab"], h["head_dim"], h["expert_width"], h["experts_held"]
    Q, KV = h["heads"] * D, h["kv_heads"] * D
    conv = {"ln_in": (H,), "conv_in": (H, 3 * H), "conv_taps": (h["conv_kernel"], H), "conv_out": (H, H), "ln_post": (H,)}
    attention = {"ln_in": (H,), "wq": (H, Q), "wk": (H, KV), "wv": (H, KV), "ln_q": (D,), "ln_k": (D,), "wo": (Q, H),
                 "ln_post": (H,)}
    W = h["dense_width"]
    dense = {"dense_gate": (H, W), "dense_up": (H, W), "dense_down": (W, H)}
    routed = {"router": (H, h["experts"]), BIAS: (h["experts"],), "w_gate": (E, H, F), "w_up": (E, H, F),
              "w_down": (E, F, H)}
    shapes = {
        "embed": (V, H), "ln_f": (H,), "value_b": (1,), "value_w": (H, 1),
        "layers": [{**(conv if h["conv"][i] else attention), **(dense if i < h["dense_layers"] else routed)}
                   for i in range(h["layers"])],
    }
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)]

    def make(key):
        leaves = []
        for path, shape, k in zip(paths, jax.tree.leaves(shapes, is_leaf=is_shape), jax.random.split(key, len(paths))):
            if "ln_" in path:
                leaves.append(jnp.ones(shape, jnp.float32))
            elif "value_b" in path:
                leaves.append(jnp.zeros(shape, jnp.float32))
            else:
                std = h["bias_scale"] if BIAS in path else h["init_std"]
                leaves.append(std * jax.random.normal(k, shape, jnp.float32))
        return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), leaves)

    layout = (tuple(paths), tuple(jax.tree.leaves(shapes, is_leaf=is_shape)), h["init_std"], h["bias_scale"])
    if layout not in _MAKERS:
        _MAKERS[layout] = jax.jit(make)
    return _MAKERS[layout](jax.random.PRNGKey(seed))


# -- the model ----------------------------------------------------------------
def _swiglu(u, w_gate, w_up, w_down):
    return _dot(jax.nn.silu(_dot(u, w_gate)) * _dot(u, w_up), w_down)


def _taps(z, taps, reversed_: bool = False):
    """`sum_i taps[i] * z[t - K + 1 + i]` over zero-padded `z` (T, hidden); reversed, `z[t + K - 1 - i]`."""
    T, K = z.shape[0], taps.shape[0]
    if reversed_:
        padded = jnp.pad(z, ((0, K - 1), (0, 0)))
        return sum(taps[i] * padded[K - 1 - i : K - 1 - i + T] for i in range(K))
    padded = jnp.pad(z, ((K - 1, 0), (0, 0)))
    return sum(taps[i] * padded[i : i + T] for i in range(K))


def _mixer(h, index, layer, z, fault):
    T, D = z.shape[0], h["head_dim"]
    if h["conv"][index]:
        b, c, v = jnp.split(_dot(z, layer["conv_in"]), 3, axis=-1)
        g = b * v
        s = _taps(g, layer["conv_taps"], fault == "taps_reversed")
        if fault == "conv_state_dropped":  # a response position's convolution without the prompt's gated inputs
            after = jnp.arange(T)[:, None] >= h["prompt_len"]
            s = jnp.where(after, _taps(jnp.where(after, g, 0.0), layer["conv_taps"]), s)
        return _dot(s if fault == "conv_gate_dropped" else c * s, layer["conv_out"])
    q = _dot(z, layer["wq"]).reshape(T, h["heads"], D)
    k = _dot(z, layer["wk"]).reshape(T, h["kv_heads"], D)
    v = _dot(z, layer["wv"]).reshape(T, h["kv_heads"], D)
    if fault != "qk_norm_dropped":
        q, k = _rmsnorm(q, layer["ln_q"], h["eps"]), _rmsnorm(k, layer["ln_k"], h["eps"])
    q, k = _rope(q, h["theta"]), _rope(k, h["theta"])
    return _dot(_attention(h, q, k, v, 0, ""), layer["wo"])


def _layer(h, index, layer, x, fault):
    T = x.shape[0]
    x = x + _mixer(h, index, layer, _rmsnorm(x, layer["ln_in"], h["eps"]), fault)
    u = _rmsnorm(x, layer["ln_post"], h["eps"])
    if index < h["dense_layers"]:
        return x + _swiglu(u, layer["dense_gate"], layer["dense_up"], layer["dense_down"]), jnp.zeros((), jnp.int32)
    scores = jax.nn.sigmoid(_dot(u, layer["router"]).astype(jnp.float32))
    selection = scores if fault == "bias_dropped" else scores + layer[BIAS].astype(jnp.float32)
    _, chosen = jax.lax.top_k(selection, h["top_k"])
    kept = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = h["routed_scale"] * kept / (jnp.sum(kept, axis=-1, keepdims=True) + ROUTE_EPS)

    # each held expert as a dense product over all tokens, times the weight the router gave it (0 where not chosen)
    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e + h["expert_offset"], weights, 0.0), axis=-1)  # (T,)
        return out + w_e[:, None] * _swiglu(u, w_gate, w_up, w_down).astype(jnp.float32), None

    experts = (jnp.arange(h["experts_held"]), layer["w_gate"], layer["w_up"], layer["w_down"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros((T, h["hidden"]), jnp.float32), experts)
    local = chosen - h["expert_offset"]
    return x + y.astype(x.dtype), jnp.sum((local >= 0) & (local < h["experts_held"]), dtype=jnp.int32)


def forward(h, params, tokens, fault: str = ""):
    """One sequence `tokens` (T,): hidden states before the final norm (T, hidden),
    and per layer the assignments that landed on held experts (0 for a dense layer)."""
    x = params["embed"][tokens]
    counts = []
    for i, layer in enumerate(params["layers"]):
        x, c = jax.checkpoint(lambda layer, x, _i=i: _layer(h, _i, layer, x, fault))(layer, x)
        counts.append(c)
    return x, jnp.stack(counts)


def heads(h, params, x, fault: str = ""):
    """The logits against the embedding's rows (the tied head) and the value."""
    z = _rmsnorm(x, params["ln_f"], h["eps"])
    table = jax.lax.stop_gradient(params["embed"]) if fault == "head_untied" else params["embed"]
    logits = _dot(z, table.T).astype(jnp.float32)
    value = (_dot(z, params["value_w"])[..., 0] + params["value_b"][0]).astype(jnp.float32)
    return logits, value


def response_outputs(h, params, tokens, fault: str = ""):
    """As `ppo_lm_ref.response_outputs`, on this module's `forward` and `heads`."""
    P, R = h["prompt_len"], h["response_len"]
    x, counts = forward(h, params, tokens, fault)
    logits, values = heads(h, params, x[P - 1 : P + R], fault)
    logp_all = jax.nn.log_softmax(logits[:R], axis=-1)
    logp = jnp.take_along_axis(logp_all, tokens[P:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    return logp, entropy, values[:R], values[R], counts


def follow(h, make_params, traj, order, steps: int = 3, compute: str = "float32", fault: str = ""):
    """`ppo_lm_ref.follow`; a fault of the rollout alone leaves the gradient steps as they are."""
    return _ppo.follow(h, make_params, traj, order, steps, compute, "" if fault in ROLLOUT_ONLY else fault)


# this file's own instance of the PPO arithmetic follows this file's model
_ppo.response_outputs = response_outputs
rollout_readings = _ppo.rollout_readings
