"""Plain reference of token-level PPO on a decoder language model with gated
grouped-query attention, per-head q/k norms, window and full layers, sandwich
norms, a sigmoid router with a selection bias after attention, a shared expert
and leading dense layers: float32 `jax.numpy`, matrix products at `highest`
precision, no kernels, no cache, no optax, nothing of the program.

The model is the published `afmoe` layer (arcee-ai, config.json of
Trinity-Mini; the layer equations from the `afmoe` modeling code published
with it in Hugging Face `transformers`, `models/afmoe/modeling_afmoe.py`), cut
to one chip's share of a stated deployment: `experts_held` experts from
`expert_offset` of the `experts` the router scores, `vocab` rows of embedding
and head, `layers` layers of which the first `dense_layers` are dense. For
layer input `x` (T x hidden), RMSNorm without bias:

    h        = norm_in(x)
    q, k, v  = norm_q(h Wq), norm_k(h Wk), h Wv      per head of head_dim: (T, heads | kv_heads, head_dim)
    q, k     = rope(q), rope(k)                       rotate-half, theta; on sliding layers only
    o        = attention(q, k, v)                     kv_heads shared by heads / kv_heads query heads each;
                                                      query i sees keys j <= i, and i - window < j on sliding layers
    x'       = x + norm_attn_out((o * sigmoid(h Wg)) Wo)
    u        = norm_post(x')
    dense layer:   y = x' + norm_ffn_out(Wdown (silu(Wgate u) * (Wup u)))
    routed layer:  g = sigmoid(u Wr);  kept = top_k of (g + b);  w_e = scale * g_e / (sum_kept g + 1e-20)
                   y = x' + norm_ffn_out(sum_{e kept and held} w_e E_e(u) + S(u))      E_e, S SwiGLU
    the embedding's rows times sqrt(hidden) (mup_enabled)

What an expert that is not held would have added is left out, here as in the
program; `S` (the shared expert) and the dense layer are whole. `b` is no
trained weight: it reaches the result through `top_k`'s indices alone, so its
gradient is zero and Adam leaves it as it was. Departures from the published
model, each also in the configuration's `assumed`: the bias is drawn from the
seed (`bias_scale`) and frozen, where the published model updates it from the
experts' loads (`load_balance_coeff`); the critic is one linear layer hidden ->
1 (with bias) on the final normed hidden state; no reference-policy KL term.

GAE, the PPO losses, the clip, Adam, the sketches and `follow` /
`rollout_readings` are `ppo_lm_ref.py`'s, loaded by path as this module's own
instance whose `response_outputs` is the one below, and so is the masked
attention in query blocks: one PPO arithmetic for the three families.
`compute="bfloat16"` is the control. `fault` plants a fault: `gate_dropped`
(the attention's output ungated), `qk_norm_dropped`, `post_norms_dropped` (the
two sublayer output norms left out), `embed_scale_dropped`, `rope_on_full` (the
rotary encoding on the full layer too), `bias_dropped` (selection by the
unbiased scores), `shared_skipped` (the shared expert adds nothing),
`expert_skipped_in_update` (the first held expert adds nothing in the gradient
steps: a backward pass that loses an expert), `half_batch` (the losses over the
first half of the response only).
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
    spec = importlib.util.spec_from_file_location(f"chipbench_reference_{name}_for_afmoe", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_ppo = _own_instance_of("ppo_lm_ref")
HIGHEST, QUERY_BLOCK, SKETCH = _ppo.HIGHEST, _ppo.QUERY_BLOCK, _ppo.SKETCH
leaf_names, entry_names, heads, gae = _ppo.leaf_names, _ppo.entry_names, _ppo.heads, _ppo.gae
_dot, _rmsnorm, _rope, _attention = _ppo._dot, _ppo._rmsnorm, _ppo._rope, _ppo._attention
BIAS = "router_bias"
LOCAL = "sliding_attention"


def hyper(as_run: Dict[str, Any], assumed: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """Widths from the configuration file (the published key names); the recipe's scalars from the composed config."""
    a = lambda k: as_run["algo.lm." + k]  # noqa: E731
    opt = cfg.algo.optimizer
    layers = int(a("num_hidden_layers"))
    return {
        "hidden": a("hidden_size"), "heads": a("num_attention_heads"), "kv_heads": a("num_key_value_heads"),
        "head_dim": a("head_dim"), "layers": layers, "dense_layers": min(int(a("num_dense_layers")), layers),
        "dense_width": a("intermediate_size"), "experts": a("num_experts"), "top_k": a("num_experts_per_tok"),
        "expert_width": a("moe_intermediate_size"), "shared_width": a("num_shared_experts") * a("moe_intermediate_size"),
        "routed_scale": float(a("route_scale")), "experts_held": a("experts_held"), "expert_offset": a("expert_offset"),
        "vocab": a("vocab_held"), "theta": float(a("rope_theta")), "eps": float(a("rms_norm_eps")),
        "window": a("sliding_window"), "local": [t == LOCAL for t in list(a("layer_types"))[:layers]],
        "embed_scale": float(a("hidden_size")) ** 0.5 if a("mup_enabled") else 1.0,
        "bias_scale": float(assumed["router_bias_scale"]),
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
    """Normal(0, init_std) matrices, unit norms, zero value bias, the selection
    bias normal(0, bias_scale); one jitted call, traced once a layout. Every
    leaf but the routed experts' three is of one or two dimensions (the harness
    reads a leaf of three as one entry an expert)."""
    H, V, D, F, E = h["hidden"], h["vocab"], h["head_dim"], h["expert_width"], h["experts_held"]
    Q, KV = h["heads"] * D, h["kv_heads"] * D
    attention = {"ln_in": (H,), "wq": (H, Q), "wk": (H, KV), "wv": (H, KV), "ln_q": (D,), "ln_k": (D,), "wg": (H, Q),
                 "wo": (Q, H), "ln_attn_out": (H,), "ln_post": (H,), "ln_ffn_out": (H,)}
    W, S = h["dense_width"], h["shared_width"]
    dense = {"dense_gate": (H, W), "dense_up": (H, W), "dense_down": (W, H)}
    routed = {"router": (H, h["experts"]), BIAS: (h["experts"],), "shared_gate": (H, S), "shared_up": (H, S),
              "shared_down": (S, H), "w_gate": (E, H, F), "w_up": (E, H, F), "w_down": (E, F, H)}
    shapes = {
        "embed": (V, H), "head": (H, V), "ln_f": (H,), "value_b": (1,), "value_w": (H, 1),
        "layers": [{**attention, **(dense if i < h["dense_layers"] else routed)} for i in range(h["layers"])],
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


def _layer(h, index, layer, x, fault):
    T, D = x.shape[0], h["head_dim"]
    local = h["local"][index]
    z = _rmsnorm(x, layer["ln_in"], h["eps"])
    q = _dot(z, layer["wq"]).reshape(T, h["heads"], D)
    k = _dot(z, layer["wk"]).reshape(T, h["kv_heads"], D)
    v = _dot(z, layer["wv"]).reshape(T, h["kv_heads"], D)
    if fault != "qk_norm_dropped":
        q, k = _rmsnorm(q, layer["ln_q"], h["eps"]), _rmsnorm(k, layer["ln_k"], h["eps"])
    if local or fault == "rope_on_full":  # the published model encodes positions on its sliding layers alone
        q, k = _rope(q, h["theta"]), _rope(k, h["theta"])
    o = _attention(h, q, k, v, h["window"] if local else 0, "")
    if fault != "gate_dropped":
        o = o * jax.nn.sigmoid(_dot(z, layer["wg"]))
    a = _dot(o, layer["wo"])
    if fault != "post_norms_dropped":
        a = _rmsnorm(a, layer["ln_attn_out"], h["eps"])
    x = x + a
    u = _rmsnorm(x, layer["ln_post"], h["eps"])
    if index < h["dense_layers"]:
        y, held = _swiglu(u, layer["dense_gate"], layer["dense_up"], layer["dense_down"]), jnp.zeros((), jnp.int32)
    else:
        scores = jax.nn.sigmoid(_dot(u, layer["router"]).astype(jnp.float32))
        selection = scores if fault == "bias_dropped" else scores + layer[BIAS].astype(jnp.float32)
        _, chosen = jax.lax.top_k(selection, h["top_k"])
        kept = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = h["routed_scale"] * kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)

        # each held expert as a dense product over all tokens, times the weight the router gave it (0 where not chosen)
        def one_expert(out, expert):
            e, w_gate, w_up, w_down = expert
            w_e = jnp.sum(jnp.where(chosen == e + h["expert_offset"], weights, 0.0), axis=-1)  # (T,)
            if fault == "expert_skipped_in_update":
                w_e = jnp.where(e == 0, 0.0, w_e)
            return out + w_e[:, None] * _swiglu(u, w_gate, w_up, w_down).astype(jnp.float32), None

        experts = (jnp.arange(h["experts_held"]), layer["w_gate"], layer["w_up"], layer["w_down"])
        y, _ = jax.lax.scan(one_expert, jnp.zeros((T, h["hidden"]), jnp.float32), experts)
        if fault != "shared_skipped":
            y = y + _swiglu(u, layer["shared_gate"], layer["shared_up"], layer["shared_down"]).astype(jnp.float32)
        y = y.astype(x.dtype)
        local_ids = chosen - h["expert_offset"]
        held = jnp.sum((local_ids >= 0) & (local_ids < h["experts_held"]), dtype=jnp.int32)
    if fault != "post_norms_dropped":
        y = _rmsnorm(y, layer["ln_ffn_out"], h["eps"])
    return x + y, held


def forward(h, params, tokens, fault: str = ""):
    """One sequence `tokens` (T,): hidden states before the final norm (T, hidden),
    and per layer the assignments that landed on held experts (0 for a dense layer)."""
    x = params["embed"][tokens]
    if fault != "embed_scale_dropped":
        x = x * jnp.asarray(h["embed_scale"], x.dtype)
    counts = []
    for i, layer in enumerate(params["layers"]):
        x, c = jax.checkpoint(lambda layer, x, _i=i: _layer(h, _i, layer, x, fault))(layer, x)
        counts.append(c)
    return x, jnp.stack(counts)


def response_outputs(h, params, tokens, fault: str = ""):
    """As `ppo_lm_ref.response_outputs`, on this module's `forward`."""
    P, R = h["prompt_len"], h["response_len"]
    x, counts = forward(h, params, tokens, fault)
    logits, values = heads(h, params, x[P - 1 : P + R])
    logp_all = jax.nn.log_softmax(logits[:R], axis=-1)
    logp = jnp.take_along_axis(logp_all, tokens[P:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    return logp, entropy, values[:R], values[R], counts


# this file's own instance of the PPO arithmetic follows this file's model
_ppo.response_outputs = response_outputs
rollout_readings, follow = _ppo.rollout_readings, _ppo.follow
