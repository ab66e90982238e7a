"""Plain reference of token-level PPO on a decoder language model with routed
experts and mixed attention: float32 `jax.numpy`, matrix products at `highest`
precision, no kernels, no cache, no optax, nothing of the program.

The model is the published SmallThinker layer (PowerInfer, config.json of
SmallThinker-21BA3B-Instruct) as ISSUE 32 writes it down, cut to one chip's
share of a stated deployment: `experts_held` experts from `expert_offset` of
the `experts` the router scores, `vocab` rows of embedding and head, `layers`
layers of the `[full, window, window, window]` period. For layer input `x`:

    h  = rmsnorm_in(x);  q, k, v = h Wq, h Wk, h Wv  (heads / kv_heads of head_dim)
    rope (rotate-half, theta) on q, k where rope_layout[l]; none where 0
    query i sees keys j <= i, and i - window < j where window_layout[l]
    x' = x + concat(heads) Wo
    r  = h Wr  (all `experts` logits); top_k kept, weights = softmax over the kept
    y  = x' + sum_{e kept and held} w_e Wdown_e (relu(Wgate_e u) * (Wup_e u)),  u = rmsnorm_post(x')

What an expert that is not held would have added is left out, here as in the
program. Departures from the published description, each also in the
configuration's `assumed`: the router reads the normed layer input `h`; RoPE
in the rotate-half convention over the whole head; no q/k norm and no bias;
the window counts the query's own position; the critic is one linear layer
hidden -> 1 (with bias) on the final normed hidden state; no reference-policy
KL term.

Attention is computed as a masked (T x T) product in query blocks, each expert
as a dense product over all tokens times its mask; every layer and query block
is rematerialised in the backward pass (same numbers, less memory).

`follow` takes what the program's first iteration produced (tokens,
log-probabilities, values, rewards) and follows its first gradient steps: GAE,
the clipped policy loss, the value loss and the entropy bonus on the response
positions, the global-norm clip, Adam; after each step it reads, per leaf and
per expert of the experts' leaves, the norm and a sketch (sums over contiguous
chunks) of Adam's first moment and of the parameters' change.
`compute="bfloat16"` is the control: parameters, Adam's moments and the
network's arithmetic in bfloat16. `fault` plants a fault: `window_dropped`,
`expert_skipped` (the first held expert adds nothing, anywhere),
`expert_skipped_in_update` (the same in the gradient steps only: a backward
pass that loses an expert), `half_batch` (the losses over the first half of
the response only).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
SKETCH = 32  # numbers in the sketch of a leaf or of one expert of an experts' leaf


def hyper(as_run: Dict[str, Any], assumed: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """Widths from the configuration file; the recipe's scalars from the composed config."""
    a = lambda k: as_run["algo.lm." + k]  # noqa: E731
    layers = int(a("num_hidden_layers"))
    opt = cfg.algo.optimizer
    return {
        "hidden": a("hidden_size"), "heads": a("num_attention_heads"), "kv_heads": a("num_key_value_heads"),
        "head_dim": a("head_dim"), "layers": layers, "experts": a("moe_num_primary_experts"),
        "top_k": a("moe_num_active_primary_experts"), "expert_width": a("moe_ffn_hidden_size"),
        "experts_held": a("experts_held"), "expert_offset": a("expert_offset"), "vocab": a("vocab_held"),
        "window": a("sliding_window_size"), "theta": float(a("rope_theta")), "eps": float(a("rms_norm_eps")),
        "rope_layout": list(a("rope_layout"))[:layers], "window_layout": list(a("sliding_window_layout"))[:layers],
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
    """Normal(0, 0.02) matrices, unit norms, zero value bias; one jitted call."""
    H, Q, KV = h["hidden"], h["heads"] * h["head_dim"], h["kv_heads"] * h["head_dim"]
    F, E, V = h["expert_width"], h["experts_held"], h["vocab"]
    shapes = {
        "embed": (V, H), "head": (H, V), "ln_f": (H,), "value_b": (1,), "value_w": (H, 1),
        "layers": [
            {"ln_in": (H,), "ln_post": (H,), "router": (H, h["experts"]), "w_down": (E, F, H), "w_gate": (E, H, F),
             "w_up": (E, H, F), "wk": (H, KV), "wo": (Q, H), "wq": (H, Q), "wv": (H, KV)}
            for _ in range(h["layers"])
        ],
    }
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)]

    def make(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for path, shape, k in zip(paths, jax.tree.leaves(shapes, is_leaf=is_shape), keys):
            if "ln_" in path:
                leaves.append(jnp.ones(shape, jnp.float32))
            elif "value_b" in path:
                leaves.append(jnp.zeros(shape, jnp.float32))
            else:
                leaves.append(h["init_std"] * jax.random.normal(k, shape, jnp.float32))
        return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), leaves)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def leaf_names(params: Dict[str, Any]) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)]


# -- the model ----------------------------------------------------------------
def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)).astype(x.dtype) * scale


def _rope(x, theta):
    T, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) / (D // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    a, b = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, q, k, v, window, fault):
    """(T, heads, D) queries over (T, kv_heads, D) keys: masked softmax in query blocks."""
    T, Hq, D = q.shape
    g = Hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(q_blk, start):
        rows = start + jnp.arange(block)
        mask = rows[:, None] >= cols[None, :]
        if window and fault != "window_dropped":
            mask &= rows[:, None] - cols[None, :] < window
        s = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HIGHEST).astype(jnp.float32) * D**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(lambda xs: one(*xs), (q.reshape(T // block, block, Hq, D), jnp.arange(0, T, block)))
    return out.reshape(T, Hq * D)


def _layer(h, index, layer, x, fault):
    H = h["hidden"]
    T = x.shape[0]
    u = _rmsnorm(x, layer["ln_in"], h["eps"])
    q = _dot(u, layer["wq"]).reshape(T, h["heads"], h["head_dim"])
    k = _dot(u, layer["wk"]).reshape(T, h["kv_heads"], h["head_dim"])
    v = _dot(u, layer["wv"]).reshape(T, h["kv_heads"], h["head_dim"])
    if h["rope_layout"][index]:
        q, k = _rope(q, h["theta"]), _rope(k, h["theta"])
    logits = _dot(u, layer["router"]).astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, h["top_k"])
    weights = jax.nn.softmax(top, axis=-1)
    x = x + _dot(_attention(h, q, k, v, h["window"] if h["window_layout"][index] else 0, fault), layer["wo"])
    z = _rmsnorm(x, layer["ln_post"], h["eps"])
    # each held expert as a dense product over all tokens, times the weight the router gave it (0 where not chosen);
    # a scan over the experts, so that the compiler sees one expert's body and not all of them
    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e + h["expert_offset"], weights, 0.0), axis=-1)  # (T,)
        if fault in ("expert_skipped", "expert_skipped_in_update"):
            w_e = jnp.where(e == 0, 0.0, w_e)
        y = _dot(jax.nn.relu(_dot(z, w_gate)) * _dot(z, w_up), w_down)
        return out + w_e[:, None] * y.astype(jnp.float32), None

    experts = (jnp.arange(h["experts_held"]), layer["w_gate"], layer["w_up"], layer["w_down"])
    out, _ = jax.lax.scan(one_expert, jnp.zeros((T, H), jnp.float32), experts)
    local = chosen - h["expert_offset"]
    held = jnp.sum((local >= 0) & (local < h["experts_held"]))
    return x + out.astype(x.dtype), held


def forward(h, params, tokens, fault: str = ""):
    """One sequence `tokens` (T,): hidden states before the final norm (T, hidden),
    and per layer the assignments that landed on held experts."""
    x = params["embed"][tokens]
    counts = []
    for i, layer in enumerate(params["layers"]):
        x, c = jax.checkpoint(lambda layer, x, _i=i: _layer(h, _i, layer, x, fault))(layer, x)
        counts.append(c)
    return x, jnp.stack(counts)


def heads(h, params, x):
    z = _rmsnorm(x, params["ln_f"], h["eps"])
    logits = _dot(z, params["head"]).astype(jnp.float32)
    value = (_dot(z, params["value_w"])[..., 0] + params["value_b"][0]).astype(jnp.float32)
    return logits, value


def response_outputs(h, params, tokens, fault: str = ""):
    """Log-probabilities of the response tokens, the entropies and the values at the
    states they were sampled in (positions P-1 .. P+R-2), the value after the
    last token (position P+R-1) and the held-assignment counts, for one sequence."""
    P, R = h["prompt_len"], h["response_len"]
    x, counts = forward(h, params, tokens, fault)
    logits, values = heads(h, params, x[P - 1 : P + R])
    logp_all = jax.nn.log_softmax(logits[:R], axis=-1)
    logp = jnp.take_along_axis(logp_all, tokens[P:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    return logp, entropy, values[:R], values[R], counts


def rollout_readings(h, params, tokens, compute: str = "float32", fault: str = ""):
    """What the rollout should have recorded for sequences `tokens` (E, P+R), by the full forward."""
    if compute == "bfloat16":
        params = _cast(params, jnp.bfloat16)
    fn = jax.jit(lambda p, t: response_outputs(h, p, t, fault))
    outs = [fn(params, jnp.asarray(t)) for t in tokens]
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    return {"logprobs": np.stack([f32(o[0]) for o in outs]), "values": np.stack([f32(o[2]) for o in outs]),
            "last_value": np.stack([f32(o[3]) for o in outs])}


def gae(h, rewards, values, last_value):
    """(R,) rewards and values of one episode that the time limit cuts at its
    last step: the last reward is bootstrapped with the value of the state it
    was cut at, and nothing flows back from beyond."""
    adv, out = 0.0, []
    R = rewards.shape[0]
    for t in reversed(range(R)):
        nxt = values[t + 1] if t + 1 < R else last_value
        not_done = 0.0 if t == R - 1 else 1.0
        delta = rewards[t] + h["gamma"] * nxt - values[t]
        adv = delta + h["gamma"] * h["gae_lambda"] * not_done * adv
        out.append(adv)
    adv = np.asarray(out[::-1], np.float32)
    return adv + values, adv


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def entry_names(params: Dict[str, Any]) -> List[str]:
    """One name a leaf, and one an expert for the experts' `(E, ., .)` leaves: the rows of `follow`'s readings."""
    names = []
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        names += [f"{name}[{e}]" for e in range(x.shape[0])] if x.ndim == 3 else [name]
    return names


def _sketch(rows):
    """(entries, n) -> (entries, SKETCH): sums over contiguous chunks, the last taking the rest."""
    n = rows.shape[1]
    if n < SKETCH:
        return jnp.pad(rows, ((0, 0), (0, SKETCH - n)))
    chunk = n // SKETCH
    head = rows[:, : chunk * SKETCH].reshape(rows.shape[0], SKETCH, chunk).sum(axis=2)
    return head.at[:, -1].add(rows[:, chunk * SKETCH :].sum(axis=1))


def _read(tree):
    rows = [x.astype(jnp.float32) for x in jax.tree.leaves(tree)]
    rows = [x.reshape(x.shape[0], -1) if x.ndim == 3 else x.reshape(1, -1) for x in rows]
    return (jnp.concatenate([jnp.sqrt(jnp.sum(r * r, axis=1)) for r in rows]), jnp.concatenate([_sketch(r) for r in rows]))


def follow(h, make_params, traj: Dict[str, np.ndarray], order: np.ndarray, steps: int = 3, compute: str = "float32",
           fault: str = ""):
    """The first `steps` gradient steps of one iteration on given trajectories,
    minibatches of whole sequences in `order` (steps x minibatch), from
    `make_params()` (called again after each step for the parameters' change,
    so that no second copy is held through a step). Returns per step the three
    losses and the gradient's global norm and, after each step, per entry
    (`entry_names`) the norm and the sketch of Adam's first moment and of the
    parameters' change; and the counts of assignments to held experts."""
    dtype = jnp.bfloat16 if compute == "bfloat16" else jnp.float32
    P, R = h["prompt_len"], h["response_len"]
    returns, advantages = zip(*(gae(h, r, v, lv) for r, v, lv in zip(traj["rewards"], traj["values"], traj["last_value"])))
    returns, advantages = np.stack(returns), np.stack(advantages)
    kept = R // 2 if fault == "half_batch" else R  # response positions the losses are taken over

    def loss_fn(p, tokens, old_logp, old_values, rets, adv):
        def one(tokens):
            logp, ent, values, _, counts = response_outputs(h, p, tokens, fault)
            return logp.astype(jnp.float32), ent.astype(jnp.float32), values, counts

        logp, ent, values, counts = jax.lax.map(one, tokens)
        if h["normalize_advantages"]:
            adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        logp, ent, values, old_logp, old_values, rets, adv = (
            x[:, :kept] for x in (logp, ent, values, old_logp, old_values, rets, adv))
        ratio = jnp.exp(logp - old_logp)
        pg = jnp.mean(jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1 - h["clip_coef"], 1 + h["clip_coef"])))
        if h["clip_vloss"]:
            clipped = old_values + jnp.clip(values - old_values, -h["clip_coef"], h["clip_coef"])
            v = jnp.mean((clipped - rets) ** 2)
        else:
            v = jnp.mean((values - rets) ** 2)
        e = -jnp.mean(ent)
        return pg + h["vf_coef"] * v + h["ent_coef"] * e, (pg, v, e, counts.sum(axis=0))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mu, nu, t, batch):
        (_, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, *batch)
        g32 = _cast(g, jnp.float32)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g32)))
        if h["max_grad_norm"] > 0:
            scale = jnp.where(norm < h["max_grad_norm"], 1.0, h["max_grad_norm"] / norm)
            g = jax.tree.map(lambda x: (x * scale.astype(x.dtype)), g)
        mu = jax.tree.map(lambda m, x: (h["b1"] * m + (1 - h["b1"]) * x).astype(dtype), mu, g)
        nu = jax.tree.map(lambda n, x: (h["b2"] * n + (1 - h["b2"]) * x * x).astype(dtype), nu, g)
        c1, c2 = 1 - h["b1"] ** t, 1 - h["b2"] ** t
        p = jax.tree.map(
            lambda w, m, n: (w - h["lr"] * (m / c1.astype(dtype)) / (jnp.sqrt(n / c2.astype(dtype)) + h["adam_eps"])).astype(dtype),
            p, mu, nu)
        return p, mu, nu, aux + (norm,)

    @jax.jit
    def readings(p0, p, mu):
        return _read(mu), _read(jax.tree.map(lambda a, b: b.astype(jnp.float32) - a.astype(jnp.float32), p0, p))

    p = _cast(make_params(), dtype)
    mu, nu = jax.tree.map(jnp.zeros_like, p), jax.tree.map(jnp.zeros_like, p)
    losses, counts, read = [], 0, {"mu_norm": [], "mu_sketch": [], "dp_norm": [], "dp_sketch": []}
    for t, rows in enumerate(np.asarray(order)[:steps], start=1):
        batch = tuple(jnp.asarray(a[rows]) for a in (traj["tokens"], traj["logprobs"], traj["values"], returns, advantages))
        p, mu, nu, (pg, v, e, c, norm) = step(p, mu, nu, jnp.float32(t), batch)
        losses.append([float(pg), float(v), float(e), float(norm)])
        counts = counts + np.asarray(c)
        (mu_norm, mu_sketch), (dp_norm, dp_sketch) = readings(_cast(make_params(), dtype), p, mu)
        for k, x in (("mu_norm", mu_norm), ("mu_sketch", mu_sketch), ("dp_norm", dp_norm), ("dp_sketch", dp_sketch)):
            read[k].append(np.asarray(x, np.float64))
    return {"losses": np.asarray(losses, np.float64), **{k: np.stack(x) for k, x in read.items()},
            "held_assignments": np.asarray(counts, np.float64), "returns": returns, "advantages": advantages}
