"""Plain reference of token-level PPO on a decoder language model with latent
attention (MLA), a sigmoid router with a selection bias, shared experts and a
leading dense layer: float32 `jax.numpy`, matrix products at `highest`
precision, no kernels, no cache, no optax, nothing of the program.

The model is the published `deepseek_v3` layer without low-rank queries
(kakaocorp, config.json of kanana-2-30b-a3b-instruct-2601) as ISSUE 36 writes
it down, cut to one chip's share of a stated deployment: `experts_held` experts
from `expert_offset` of the `experts` the router scores, `vocab` rows of
embedding and head, `layers` layers of which the first `dense_layers` are
dense. For layer input `x` (T x hidden), RMSNorm without bias:

    h        = norm_in(x)
    q        = h Wq                 -> (T, heads, nope + rope) = [q_n | q_r]
    [c | r]  = h Wkva               -> (T, latent) | (T, rope);   c = norm_kv(c)
    q_r, r   = rope(q_r), rope(r)   theta over the rope dims, pairs (2i, 2i+1); r one for all heads
    [k_n | v]= c Wkvb               -> (T, heads, nope) | (T, heads, v)
    s_ij     = (q_n,i . k_n,j + q_r,i . r_j) / sqrt(nope + rope),  j <= i;   o = softmax_j(s) v
    x'       = x + concat(o) Wo;    u = norm_post(x')
    dense layer:   y = x' + Wdown (silu(Wgate u) * (Wup u))
    routed layer:  g = sigmoid(u Wr);  kept = top_k of (g + b);  w_e = scale * g_e / (sum_kept g + 1e-20)
                   y = x' + sum_{e kept and held} w_e E_e(u) + S(u)      E_e, S SwiGLU

This is the **expanded** form only: keys and values of every head are formed
from the latent at every position. The program's decode reads a latent cache
in the absorbed form, and has to agree with this. What an expert that is not
held would have added is left out, here as in the program; `S` (the shared
experts as one MLP) and the dense layer are whole. `b` is no trained weight:
it reaches the result through `top_k`'s indices alone, so its gradient is
zero and Adam leaves it as it was. Departures from the published description,
each also in the configuration's `assumed`: the bias is drawn from the seed
(`bias_scale`) and frozen; the critic is one linear layer hidden -> 1 (with
bias) on the final normed hidden state; no reference-policy KL term.

GAE, the PPO losses, the clip, Adam, the sketches and `follow` /
`rollout_readings` are `ppo_lm_ref.py`'s, loaded by path as this module's own
instance whose `response_outputs` is the one below: one PPO arithmetic for
both families. `compute="bfloat16"` is the control. `fault` plants a fault:
`rope_dropped` (the rotary part of the score left out), `latent_norm_dropped`,
`shared_skipped` (the shared experts add nothing), `bias_dropped` (selection
by the unbiased scores), `expert_skipped_in_update` (the first held expert
adds nothing in the gradient steps: a backward pass that loses an expert),
`half_batch` (the losses over the first half of the response only).
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
    spec = importlib.util.spec_from_file_location(f"chipbench_reference_{name}_for_mla", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_ppo = _own_instance_of("ppo_lm_ref")
HIGHEST, QUERY_BLOCK, SKETCH = _ppo.HIGHEST, _ppo.QUERY_BLOCK, _ppo.SKETCH
leaf_names, entry_names, heads, gae = _ppo.leaf_names, _ppo.entry_names, _ppo.heads, _ppo.gae
_dot, _rmsnorm = _ppo._dot, _ppo._rmsnorm
BIAS = "router_bias"


def hyper(as_run: Dict[str, Any], assumed: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """Widths from the configuration file (the published key names); the recipe's scalars from the composed config."""
    a = lambda k: as_run["algo.lm." + k]  # noqa: E731
    opt = cfg.algo.optimizer
    return {
        "hidden": a("hidden_size"), "heads": a("num_attention_heads"), "nope": a("qk_nope_head_dim"),
        "rope": a("qk_rope_head_dim"), "v": a("v_head_dim"), "latent": a("kv_lora_rank"),
        "layers": int(a("num_hidden_layers")), "dense_layers": min(int(a("first_k_dense_replace")), int(a("num_hidden_layers"))),
        "dense_width": a("intermediate_size"), "experts": a("n_routed_experts"), "top_k": a("num_experts_per_tok"),
        "expert_width": a("moe_intermediate_size"), "shared_width": a("n_shared_experts") * a("moe_intermediate_size"),
        "routed_scale": float(a("routed_scaling_factor")), "experts_held": a("experts_held"),
        "expert_offset": a("expert_offset"), "vocab": a("vocab_held"), "theta": float(a("rope_theta")),
        "eps": float(a("rms_norm_eps")), "bias_scale": float(assumed["router_bias_scale"]),
        "prompt_len": int(cfg.env.prompt_len), "response_len": int(cfg.algo.rollout_steps),
        "num_envs": int(cfg.env.num_envs), "minibatch": int(cfg.algo.per_rank_batch_size),
        "update_epochs": int(cfg.algo.update_epochs), "gamma": float(cfg.algo.gamma),
        "gae_lambda": float(cfg.algo.gae_lambda), "clip_coef": float(cfg.algo.clip_coef),
        "vf_coef": float(cfg.algo.vf_coef), "ent_coef": float(cfg.algo.ent_coef),
        "normalize_advantages": bool(cfg.algo.normalize_advantages), "clip_vloss": bool(cfg.algo.clip_vloss),
        "max_grad_norm": float(cfg.algo.max_grad_norm), "lr": float(opt.lr), "adam_eps": float(opt.eps),
        "b1": float(opt.betas[0]), "b2": float(opt.betas[1]), "init_std": 0.02,
    }


def init_params(h: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Normal(0, init_std) matrices, unit norms, zero value bias, the selection
    bias normal(0, bias_scale); one jitted call. Every leaf but the routed
    experts' three is of one or two dimensions (the harness reads a leaf of
    three as one entry an expert)."""
    H, V, N, F, E = h["hidden"], h["vocab"], h["heads"], h["expert_width"], h["experts_held"]
    attention = {"ln_in": (H,), "wq": (H, N * (h["nope"] + h["rope"])), "wkva": (H, h["latent"] + h["rope"]),
                 "ln_kv": (h["latent"],), "wkvb": (h["latent"], N * (h["nope"] + h["v"])), "wo": (N * h["v"], H),
                 "ln_post": (H,)}
    D, S = h["dense_width"], h["shared_width"]
    dense = {"dense_gate": (H, D), "dense_up": (H, D), "dense_down": (D, H)}
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

    return jax.jit(make)(jax.random.PRNGKey(seed))


# -- the model ----------------------------------------------------------------
def _rope_pairs(x, theta):
    """(T, heads, D): the pair (2i, 2i + 1) turned by position x theta ** (-i / (D / 2))."""
    T, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) / (D // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    pairs = x.reshape(*x.shape[:-1], D // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attention(q_n, q_r, k_n, r, v, fault):
    """(T, heads, .) queries over (T, heads, .) keys and values, `r` (T, rope) for all heads:
    masked softmax in query blocks, the score's two parts added."""
    T, N, _ = q_n.shape
    scale = (q_n.shape[-1] + q_r.shape[-1]) ** -0.5
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(qn_blk, qr_blk, start):
        mask = (start + jnp.arange(block))[:, None] >= cols[None, :]
        s = jnp.einsum("qhd,khd->hqk", qn_blk, k_n, precision=HIGHEST).astype(jnp.float32)
        if fault != "rope_dropped":
            s = s + jnp.einsum("qhd,kd->hqk", qr_blk, r, precision=HIGHEST).astype(jnp.float32)
        p = jax.nn.softmax(jnp.where(mask[None], s * scale, -jnp.inf), axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    blocks = lambda a: a.reshape(T // block, block, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda xs: one(*xs), (blocks(q_n), blocks(q_r), jnp.arange(0, T, block)))
    return out.reshape(T, N * v.shape[-1])


def _swiglu(u, w_gate, w_up, w_down):
    return _dot(jax.nn.silu(_dot(u, w_gate)) * _dot(u, w_up), w_down)


def _layer(h, index, layer, x, fault):
    T, N = x.shape[0], h["heads"]
    z = _rmsnorm(x, layer["ln_in"], h["eps"])
    q = _dot(z, layer["wq"]).reshape(T, N, h["nope"] + h["rope"])
    down = _dot(z, layer["wkva"])
    c, r = down[:, : h["latent"]], down[:, h["latent"] :]
    if fault != "latent_norm_dropped":
        c = _rmsnorm(c, layer["ln_kv"], h["eps"])
    q_n, q_r = q[..., : h["nope"]], _rope_pairs(q[..., h["nope"] :], h["theta"])
    r = _rope_pairs(r[:, None, :], h["theta"])[:, 0]
    kv = _dot(c, layer["wkvb"]).reshape(T, N, h["nope"] + h["v"])
    x = x + _dot(_attention(q_n, q_r, kv[..., : h["nope"]], r, kv[..., h["nope"] :], fault), layer["wo"])
    u = _rmsnorm(x, layer["ln_post"], h["eps"])
    if index < h["dense_layers"]:
        return x + _swiglu(u, layer["dense_gate"], layer["dense_up"], layer["dense_down"]), jnp.zeros((), jnp.int32)
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
    routed, _ = jax.lax.scan(one_expert, jnp.zeros((T, h["hidden"]), jnp.float32), experts)
    if fault != "shared_skipped":
        routed = routed + _swiglu(u, layer["shared_gate"], layer["shared_up"], layer["shared_down"]).astype(jnp.float32)
    local = chosen - h["expert_offset"]
    return x + routed.astype(x.dtype), jnp.sum((local >= 0) & (local < h["experts_held"]), dtype=jnp.int32)


def forward(h, params, tokens, fault: str = ""):
    """One sequence `tokens` (T,): hidden states before the final norm (T, hidden),
    and per layer the assignments that landed on held experts (0 for a dense layer)."""
    x = params["embed"][tokens]
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
