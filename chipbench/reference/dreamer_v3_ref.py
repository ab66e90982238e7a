"""Plain reference of DreamerV3's gradient step (Hafner et al., arXiv:2301.04104,
as sheeprl v0.5.7 implements it): float32 `jax.numpy`, matrix products at
`highest` precision, no kernels, no flax, no optax, nothing of the program.
Parametrised by the configuration's widths, so every size shares it.

It makes its own weights from the seed (`init_params`: Hafner's truncated
normal, scaled-uniform output layers, zero reward/critic outputs), rebuilds
the replay ring from the rows the harness saw staged, draws the same windows
from the same keys with the sequential-buffer rule, and follows the program's
first gradient steps: world model (CNN encoder, RSSM with the LayerNorm GRU,
decoder, two-hot reward and Bernoulli continue heads), imagination with actor,
critic, lambda-returns and the percentile moments, and the three
clip-then-Adam updates.

`compute="bfloat16"` is the control, the nearest precision below float32 and
what `fabric.precision=bf16-true` would run: parameters, Adam's moments and
every network's arithmetic in bfloat16 (losses and the return statistics in
float32).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-3
DN = ("NHWC", "HWIO", "NHWC")


# -- hyper-parameters and shapes ---------------------------------------------
def hyper(as_run: Dict[str, Any], assumed: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """Widths from the configuration file; the recipe's scalars (learning
    rates, discount, KL weights) from the composed config."""
    a, wm = as_run, cfg.algo.world_model
    return {
        "dense": a["algo.dense_units"], "layers": a["algo.mlp_layers"],
        "mult": a["algo.world_model.encoder.cnn_channels_multiplier"],
        "rec": a["algo.world_model.recurrent_model.recurrent_state_size"],
        "rec_dense": a["algo.world_model.recurrent_model.dense_units"],
        "trans_hidden": a["algo.world_model.transition_model.hidden_size"],
        "repr_hidden": a["algo.world_model.representation_model.hidden_size"],
        "stochastic": a["algo.world_model.stochastic_size"], "discrete": a["algo.world_model.discrete_size"],
        "bins": a["algo.world_model.reward_model.bins"], "critic_bins": a["algo.critic.bins"],
        "horizon": a["algo.horizon"], "batch": a["algo.per_rank_batch_size"],
        "seq": a["algo.per_rank_sequence_length"], "screen": a["env.screen_size"],
        "actions": assumed["actions"], "channels": assumed["image"][2], "unimix": a["algo.unimix"],
        "gamma": float(cfg.algo.gamma), "lmbda": float(cfg.algo.lmbda), "ent_coef": float(cfg.algo.actor.ent_coef),
        "kl_dynamic": float(wm.kl_dynamic), "kl_representation": float(wm.kl_representation),
        "kl_free_nats": float(wm.kl_free_nats), "kl_regularizer": float(wm.kl_regularizer),
        "continue_scale": float(wm.continue_scale_factor),
        "tau": float(cfg.algo.critic.tau), "target_freq": int(cfg.algo.critic.per_rank_target_network_update_freq),
        "moments_decay": float(cfg.algo.actor.moments.decay), "moments_max": float(cfg.algo.actor.moments.max),
        "moments_low": float(cfg.algo.actor.moments.percentile.low),
        "moments_high": float(cfg.algo.actor.moments.percentile.high),
        "optim": {
            name: {"lr": float(node.optimizer.lr), "eps": float(node.optimizer.eps),
                   "b1": float(node.optimizer.betas[0]), "b2": float(node.optimizer.betas[1]),
                   "clip": float(node.clip_gradients)}
            for name, node in (("world_model", wm), ("actor", cfg.algo.actor), ("critic", cfg.algo.critic))
        },
    }


def _mlp_shapes(fan_in: int, units: int, layers: int) -> Dict[str, Any]:
    out, width = {}, fan_in
    for i in range(layers):
        out[f"dense_{i}"] = {"bias": (units,), "kernel": (width, units)}
        out[f"ln_{i}"] = {"bias": (units,), "scale": (units,)}
        width = units
    return out


def _head_shapes(fan_in: int, units: int, layers: int, out: int, out_name: str = "out") -> Dict[str, Any]:
    return {"params": {"model": _mlp_shapes(fan_in, units, layers), out_name: {"bias": (out,), "kernel": (units, out)}}}


def param_shapes(h: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree by name, shapes at the leaves (the names are the
    interface through which the harness hands the weights to the program)."""
    stoch = h["stochastic"] * h["discrete"]
    latent = stoch + h["rec"]
    m, stages = h["mult"], 4
    enc, c_in = {}, h["channels"]
    for i in range(stages):
        enc[f"conv_{i}"] = {"kernel": (4, 4, c_in, (2**i) * m)}
        enc[f"ln_{i}"] = {"bias": ((2**i) * m,), "scale": ((2**i) * m,)}
        c_in = (2**i) * m
    embed = c_in * 16
    dec = {"fc": {"bias": (embed,), "kernel": (latent, embed)}}
    c_in = 8 * m
    for i, c_out in enumerate((4 * m, 2 * m, m)):
        dec[f"deconv_{i}"] = {"ConvTranspose_0": {"kernel": (4, 4, c_in, c_out)}}
        dec[f"ln_{i}"] = {"bias": (c_out,), "scale": (c_out,)}
        c_in = c_out
    dec["out"] = {"ConvTranspose_0": {"bias": (h["channels"],), "kernel": (4, 4, c_in, h["channels"])}}
    world = {
        "cnn_decoder": {"params": dec},
        "continue_model": _head_shapes(latent, h["dense"], h["layers"], 1),
        "encoder": {"params": {"cnn_encoder": enc}},
        "initial_recurrent_state": (h["rec"],),
        "recurrent_model": {"params": {
            "mlp": _mlp_shapes(stoch + h["actions"], h["rec_dense"], 1),
            "rnn": {"fused": {"kernel": (h["rec"] + h["rec_dense"], 3 * h["rec"])},
                    "ln": {"bias": (3 * h["rec"],), "scale": (3 * h["rec"],)}},
        }},
        "representation_model": _head_shapes(h["rec"] + embed, h["repr_hidden"], 1, stoch),
        "reward_model": _head_shapes(latent, h["dense"], h["layers"], h["bins"]),
        "transition_model": _head_shapes(h["rec"], h["trans_hidden"], 1, stoch),
    }
    return {
        "world_model": world,
        "actor": _head_shapes(latent, h["dense"], h["layers"], h["actions"], out_name="head_0"),
        "critic": _head_shapes(latent, h["dense"], h["layers"], h["critic_bins"]),
    }


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _fans(shape: Tuple[int, ...]) -> Tuple[float, float]:
    space = float(np.prod(shape[:-2])) if len(shape) > 2 else 1.0
    return space * shape[-2], space * shape[-1]


# output layers that Hafner's initialisation draws from a scaled uniform
_UNIFORM_OUT = {
    "['world_model']['transition_model']['params']['out']": 1.0,
    "['world_model']['representation_model']['params']['out']": 1.0,
    "['world_model']['reward_model']['params']['out']": 0.0,
    "['world_model']['continue_model']['params']['out']": 1.0,
    "['world_model']['cnn_decoder']['params']['out']": 1.0,
    "['critic']['params']['out']": 0.0,
    "['actor']['params']['head_0']": 1.0,
}


def init_params(h: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in one jitted call from the seed, in float32."""
    shapes = param_shapes(h)
    leaves = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=_is_shape)

    def make(key):
        out = {}
        for i, (path, shape) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            last = path[-1].key
            if last == "kernel":
                fan_in, fan_out = _fans(shape)
                scale = next((s for prefix, s in _UNIFORM_OUT.items() if name.startswith(prefix)), None)
                if scale is None:
                    std = np.sqrt(1.0 / ((fan_in + fan_out) / 2.0)) / 0.87962566103423978
                    out[name] = std * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                else:
                    limit = np.sqrt(3.0 * scale / ((fan_in + fan_out) / 2.0))
                    out[name] = jax.random.uniform(k, shape, jnp.float32, -limit, limit)
            elif last == "scale":
                out[name] = jnp.ones(shape, jnp.float32)
            else:  # biases and the learnable initial recurrent state
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    flat = jax.jit(make)(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map_with_path(lambda p, _s: flat[jax.tree_util.keystr(p)], shapes, is_leaf=_is_shape)


# -- networks ------------------------------------------------------------------
COMPUTES = ("float32_highest", "bfloat16")


class Net:
    """Evaluates the networks in float32 with exact products, or in bfloat16."""

    def __init__(self, h: Dict[str, Any], compute: str = "float32_highest"):
        if compute not in COMPUTES:
            raise ValueError(f"compute must be one of {COMPUTES}, got {compute!r}")
        self.h = h
        self.cd = jnp.bfloat16 if compute == "bfloat16" else jnp.float32
        self.prec = None if compute == "bfloat16" else HIGHEST

    def c(self, x):
        return x.astype(self.cd)

    def dense(self, p, x):
        y = jnp.dot(self.c(x), self.c(p["kernel"]), precision=self.prec)
        return y + self.c(p["bias"]) if "bias" in p else y

    def ln(self, p, x):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * self.c(p["scale"]) + self.c(p["bias"])

    def mlp(self, p, x, layers):
        for i in range(layers):
            x = jax.nn.silu(self.ln(p[f"ln_{i}"], self.dense(p[f"dense_{i}"], x)))
        return x

    def head(self, p, x, layers, out="out"):
        p = p["params"]
        return self.dense(p[out], self.mlp(p["model"], x, layers)).astype(jnp.float32)

    def encoder(self, p, obs):
        p = p["params"]["cnn_encoder"]
        lead = obs.shape[:-3]
        x = self.c(obs.reshape((-1,) + obs.shape[-3:]))
        for i in range(4):
            x = jax.lax.conv_general_dilated(x, self.c(p[f"conv_{i}"]["kernel"]), (2, 2), ((1, 1), (1, 1)),
                                             dimension_numbers=DN, precision=self.prec)
            x = jax.nn.silu(self.ln(p[f"ln_{i}"], x))
        return x.reshape(lead + (-1,))

    def deconv(self, p, x):
        p = p["ConvTranspose_0"]
        y = jax.lax.conv_transpose(x, self.c(p["kernel"]), (2, 2), "VALID", dimension_numbers=DN, precision=self.prec)
        y = y[:, 1:-1, 1:-1, :]
        return y + self.c(p["bias"]) if "bias" in p else y

    def decoder(self, p, latent):
        p = p["params"]
        lead = latent.shape[:-1]
        x = self.dense(p["fc"], latent)
        x = x.reshape(-1, 4, 4, x.shape[-1] // 16)
        for i in range(3):
            x = jax.nn.silu(self.ln(p[f"ln_{i}"], self.deconv(p[f"deconv_{i}"], x)))
        x = self.deconv(p["out"], x)
        return x.reshape(lead + x.shape[1:]).astype(jnp.float32)

    def recurrent(self, p, x, rec):
        p = p["params"]
        feat = self.mlp(p["mlp"], x, 1)
        fused = self.ln(p["rnn"]["ln"], self.dense(p["rnn"]["fused"], jnp.concatenate([self.c(rec), feat], -1)))
        reset, cand, update = jnp.split(fused, 3, -1)
        reset = jax.nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = jax.nn.sigmoid(update - 1)
        return update * cand + (1 - update) * self.c(rec)

    def stoch_logits(self, p, x):
        """Logits of the grouped categoricals with 1% uniform mixing, flat."""
        logits = self.head(p, x, 1)
        g = logits.reshape(logits.shape[:-1] + (-1, self.h["discrete"]))
        probs = (1 - self.h["unimix"]) * jax.nn.softmax(g, -1) + self.h["unimix"] / self.h["discrete"]
        return jnp.log(probs).reshape(logits.shape)


def log_normalise(logits):
    return logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)


def straight_through(logits_grouped, key):
    """A hard one-hot sample whose gradient is that of the probabilities."""
    logp = log_normalise(logits_grouped)
    idx = jax.random.categorical(key, logp, axis=-1, shape=logp.shape[:-1])
    hard = jax.lax.stop_gradient(jax.nn.one_hot(idx, logp.shape[-1], dtype=logp.dtype))
    probs = jax.nn.softmax(logp, -1)
    return hard + probs - jax.lax.stop_gradient(probs)


def sample_stoch(h, logits_flat, key):
    g = logits_flat.reshape(logits_flat.shape[:-1] + (-1, h["discrete"]))
    return straight_through(g, key).reshape(logits_flat.shape)


def mode_stoch(h, logits_flat):
    g = logits_flat.reshape(logits_flat.shape[:-1] + (-1, h["discrete"]))
    return jax.nn.one_hot(jnp.argmax(g, -1), h["discrete"], dtype=g.dtype).reshape(logits_flat.shape)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1)


def twohot_mean(logits):
    bins = jnp.linspace(-20.0, 20.0, logits.shape[-1], dtype=jnp.float32)
    return symexp(jnp.sum(jax.nn.softmax(logits, -1) * bins, -1, keepdims=True))


def twohot_log_prob(logits, value):
    """log-probability of `value` (..., 1) under two-hot targets on a symlog
    support of `logits.shape[-1]` bins in [-20, 20]."""
    logp = log_normalise(logits)
    k = logits.shape[-1]
    bins = jnp.linspace(-20.0, 20.0, k, dtype=jnp.float32)
    x = symlog(value)
    below = jnp.clip(jnp.sum((bins <= x).astype(jnp.int32), -1, keepdims=True) - 1, 0, k - 1)
    above = jnp.clip(k - jnp.sum((bins > x).astype(jnp.int32), -1, keepdims=True), 0, k - 1)
    equal = below == above
    d_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x))
    d_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x))
    total = d_below + d_above
    target = jax.nn.one_hot(below[..., 0], k) * (d_above / total) + jax.nn.one_hot(above[..., 0], k) * (d_below / total)
    return jnp.sum(target * logp, -1)


def bernoulli_log_prob(logits, target):
    return -(jnp.maximum(logits, 0) - logits * target + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def categorical_kl(p_logits, q_logits, h):
    """KL(p || q) of the grouped categoricals, summed over the groups."""
    shape = p_logits.shape[:-1] + (h["stochastic"], h["discrete"])
    lp, lq = log_normalise(p_logits.reshape(shape)), log_normalise(q_logits.reshape(shape))
    return jnp.sum(jnp.sum(jnp.exp(lp) * (lp - lq), -1), -1)


# -- the ring and its windows --------------------------------------------------
RING_BLOCK = 512  # the kept part of the ring is a whole number of these rows


def build_ring(rows: List[Tuple[Dict[str, np.ndarray], np.ndarray]], capacity: int):
    """Ragged per-env append of the staged rows: row i goes to env e's write
    head iff its mask says so. Only the written part is kept, rounded up to
    whole blocks: the number of rows staged before the first training burst
    moves by a few with the seed (episode ends), and the shape must not, or
    every new count compiles the reference anew."""
    n_envs = rows[0][1].shape[0]
    n = min(-(-len(rows) // RING_BLOCK) * RING_BLOCK, capacity)
    ring = {k: np.zeros((n, n_envs) + v.shape[1:], v.dtype) for k, v in rows[0][0].items()}
    pos = np.zeros(n_envs, np.int64)
    valid = np.zeros(n_envs, np.int64)
    for data, mask in rows:
        for e in np.nonzero(mask)[0]:
            if pos[e] >= n:
                raise ValueError("the reference ring holds no wrap-around; check before the ring is full")
            for k in ring:
                ring[k][pos[e], e] = data[k][e]
            pos[e] = (pos[e] + 1) % capacity
            valid[e] = min(valid[e] + 1, capacity)
    return ring, pos, valid


def sample_windows(key, pos, valid, capacity: int, seq: int, batch: int, n_envs: int):
    """Uniform window starts that never cross an env's write head."""
    k_env, k_start, k_grad = jax.random.split(key, 3)
    env_idx = jax.random.randint(k_env, (batch,), 0, n_envs)
    vn = valid[env_idx]
    full = vn >= capacity
    n_starts = jnp.where(full, capacity - seq + 1, jnp.maximum(vn - seq + 1, 1))
    base = jnp.where(full, pos[env_idx], 0)
    u = jax.random.uniform(k_start, env_idx.shape)
    start = (base + (u * n_starts).astype(jnp.int32)) % capacity
    t_idx = (start[None, :] + jnp.arange(seq)[:, None]) % capacity
    return t_idx, env_idx, k_grad


# -- losses ---------------------------------------------------------------------
def world_model_loss(net: Net, wmp, batch, key):
    h = net.h
    obs = batch["rgb"].astype(jnp.float32) / 255.0 - 0.5
    is_first = batch["is_first"].at[0].set(1.0)
    actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    embedded = net.encoder(wmp["encoder"], obs)
    T, B = actions.shape[:2]
    stoch = h["stochastic"] * h["discrete"]

    def step(carry, xs):
        rec, post = carry
        emb, act, first, k = xs
        first = net.c(first)
        act = (1 - first) * net.c(act)
        init_rec = jnp.broadcast_to(jnp.tanh(net.c(wmp["initial_recurrent_state"])), rec.shape)
        init_post = mode_stoch(h, net.stoch_logits(wmp["transition_model"], init_rec))
        rec = (1 - first) * rec + first * init_rec
        post = (1 - first) * post + first * net.c(init_post)
        rec = net.recurrent(wmp["recurrent_model"], jnp.concatenate([post, act], -1), rec)
        prior_logits = net.stoch_logits(wmp["transition_model"], rec)
        post_logits = net.stoch_logits(wmp["representation_model"], jnp.concatenate([rec, emb], -1))
        post = net.c(sample_stoch(h, post_logits, k))
        return (rec, post), (rec, post, post_logits, prior_logits)

    carry0 = (jnp.zeros((B, h["rec"]), net.cd), jnp.zeros((B, stoch), net.cd))
    _, (recs, posts, post_logits, prior_logits) = jax.lax.scan(
        step, carry0, (embedded, actions, is_first, jax.random.split(key, T)))
    latents = jnp.concatenate([posts, recs], -1)
    recon = net.decoder(wmp["cnn_decoder"], latents)
    observation_loss = jnp.sum(jnp.square(recon - obs), (-3, -2, -1))
    reward_loss = -twohot_log_prob(net.head(wmp["reward_model"], latents, h["layers"]), batch["rewards"])
    continue_logits = net.head(wmp["continue_model"], latents, h["layers"])
    continue_loss = -h["continue_scale"] * jnp.sum(bernoulli_log_prob(continue_logits, 1 - batch["terminated"]), -1)
    sg = jax.lax.stop_gradient
    dyn = h["kl_dynamic"] * jnp.maximum(categorical_kl(sg(post_logits), prior_logits, h), h["kl_free_nats"])
    rep = h["kl_representation"] * jnp.maximum(categorical_kl(post_logits, sg(prior_logits), h), h["kl_free_nats"])
    loss = jnp.mean(h["kl_regularizer"] * (dyn + rep) + observation_loss + reward_loss + continue_loss)
    return loss, (recs.astype(jnp.float32), posts.astype(jnp.float32))


def actor_logits(net: Net, ap, state):
    logits = net.head(ap, state, net.h["layers"], out="head_0")
    probs = (1 - net.h["unimix"]) * jax.nn.softmax(logits, -1) + net.h["unimix"] / logits.shape[-1]
    return jnp.log(probs)


def actor_loss(net: Net, ap, params, moments, recs, posts, terminated, key):
    h, sg = net.h, jax.lax.stop_gradient
    wmp = params["world_model"]
    T, B = recs.shape[:2]
    prior0 = sg(posts).reshape(T * B, -1)
    rec0 = sg(recs).reshape(T * B, -1)
    true_continue = (1 - terminated).reshape(1, T * B, 1)
    latent0 = jnp.concatenate([prior0, rec0], -1)
    k0, k_scan = jax.random.split(key)

    def act(latent, k):
        return straight_through(actor_logits(net, ap, sg(latent)), jax.random.split(k, 1)[0])

    a0 = act(latent0, k0)

    def img_step(carry, k):
        prior, rec, action = carry
        k_prior, k_act = jax.random.split(k)
        rec = net.recurrent(wmp["recurrent_model"], jnp.concatenate([net.c(prior), net.c(action)], -1), rec)
        prior = sample_stoch(h, net.stoch_logits(wmp["transition_model"], rec), k_prior)
        latent = jnp.concatenate([prior, rec.astype(jnp.float32)], -1)
        new_action = act(latent, k_act)
        return (prior, rec, new_action), (latent, new_action)

    _, (latents, acts) = jax.lax.scan(img_step, (prior0, net.c(rec0), a0), jax.random.split(k_scan, h["horizon"]))
    traj = jnp.concatenate([latent0[None], latents], 0)
    actions = jnp.concatenate([a0[None], acts], 0)
    values = twohot_mean(net.head(params["critic"], traj, h["layers"]))
    rewards = twohot_mean(net.head(wmp["reward_model"], traj, h["layers"]))
    continues = (jax.nn.sigmoid(net.head(wmp["continue_model"], traj, h["layers"])) > 0.5).astype(jnp.float32)
    continues = jnp.concatenate([true_continue, continues[1:]], 0)

    # TD(lambda) returns, backwards from the last value
    r, v, c = rewards[1:], values[1:], continues[1:] * h["gamma"]
    interm = r + c * v * (1 - h["lmbda"])

    def back(nxt, xs):
        val = xs[0] + xs[1] * h["lmbda"] * nxt
        return val, val

    _, lambda_values = jax.lax.scan(back, v[-1], (interm, c), reverse=True)
    discount = sg(jnp.cumprod(continues * h["gamma"], 0) / h["gamma"])
    flat = sg(lambda_values).reshape(-1)
    low = h["moments_decay"] * moments["low"] + (1 - h["moments_decay"]) * jnp.quantile(flat, h["moments_low"])
    high = h["moments_decay"] * moments["high"] + (1 - h["moments_decay"]) * jnp.quantile(flat, h["moments_high"])
    invscale = jnp.maximum(1.0 / h["moments_max"], high - low)
    advantage = (lambda_values - low) / invscale - (values[:-1] - low) / invscale
    logp = log_normalise(actor_logits(net, ap, sg(traj)))
    logprob = jnp.sum(sg(actions) * logp, -1)[..., None][:-1]
    entropy = h["ent_coef"] * -jnp.sum(jnp.exp(logp) * logp, -1)
    loss = -jnp.mean(discount[:-1] * (logprob * sg(advantage) + entropy[..., None][:-1]))
    return loss, (sg(traj), sg(lambda_values), discount, {"low": low, "high": high})


def critic_loss(net: Net, cp, target, traj, lambda_values, discount):
    h = net.h
    logits = net.head(cp, traj[:-1], h["layers"])
    target_values = twohot_mean(net.head(target, traj[:-1], h["layers"]))
    loss = -twohot_log_prob(logits, lambda_values) - twohot_log_prob(logits, jax.lax.stop_gradient(target_values))
    return jnp.mean(loss * discount[:-1, ..., 0])


# -- optimiser --------------------------------------------------------------------
def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params)}


def clip_then_adam(grads, state, params, o):
    """Clip by the global norm, then Adam; returns what Adam was given too."""
    dt = jax.tree.leaves(params)[0].dtype  # float32, or bfloat16 in the control: Adam runs in the parameters' type
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: jnp.where(norm < o["clip"], g, g / norm * o["clip"]), grads)
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, state["mu"], grads)
    nu = jax.tree.map(lambda n, g: o["b2"] * n + (1 - o["b2"]) * jnp.square(g), state["nu"], grads)
    c1 = (1 - o["b1"] ** count.astype(jnp.float32)).astype(dt)
    c2 = (1 - o["b2"] ** count.astype(jnp.float32)).astype(dt)
    new = jax.tree.map(lambda p, m, n: p - o["lr"] * (m / c1) / (jnp.sqrt(n / c2) + o["eps"]), params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}, grads


# -- one gradient step ----------------------------------------------------------------
def gradient_step(net: Net, state, batch, key):
    h = net.h
    params, opts, moments, cum = state["params"], state["opts"], state["moments"], state["cum"]
    k_dyn, k_img = jax.random.split(key)
    mix = jnp.where(cum % h["target_freq"] == 0, jnp.where(cum == 0, 1.0, h["tau"]), 0.0)
    target = jax.tree.map(lambda c, t: mix * c + (1 - mix) * t, params["critic"], params["target_critic"])

    (wm_loss, (recs, posts)), wm_grads = jax.value_and_grad(
        lambda p: world_model_loss(net, p, batch, k_dyn), has_aux=True)(params["world_model"])
    world, opt_w, wm_given = clip_then_adam(wm_grads, opts["world_model"], params["world_model"], h["optim"]["world_model"])
    params = {**params, "world_model": world, "target_critic": target}

    (a_loss, (traj, lambda_values, discount, moments)), a_grads = jax.value_and_grad(
        lambda p: actor_loss(net, p, params, moments, recs, posts, batch["terminated"], k_img), has_aux=True
    )(params["actor"])
    actor, opt_a, a_given = clip_then_adam(a_grads, opts["actor"], params["actor"], h["optim"]["actor"])

    c_loss, c_grads = jax.value_and_grad(
        lambda p: critic_loss(net, p, target, traj, lambda_values, discount))(params["critic"])
    critic, opt_c, c_given = clip_then_adam(c_grads, opts["critic"], params["critic"], h["optim"]["critic"])

    params = {**params, "actor": actor, "critic": critic}
    new_state = {"params": params, "opts": {"world_model": opt_w, "actor": opt_a, "critic": opt_c},
                 "moments": moments, "cum": cum + 1}
    given = {"world_model": wm_given, "actor": a_given, "critic": c_given}
    return new_state, jnp.stack([wm_loss, a_loss, c_loss]), given


MODULES = ("world_model", "actor", "critic")


def leaf_names(params) -> List[str]:
    return [m + jax.tree_util.keystr(p) for m in MODULES for p, _ in jax.tree_util.tree_leaves_with_path(params[m])]


def _leaf_norms(tree_by_module) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for m in MODULES for x in jax.tree.leaves(tree_by_module[m])])


def follow(h, params, rows, first_flush, capacity: int, steps: int, compute: str = "float32_highest", fault: str = ""):
    """The first `steps` gradient steps of the first training burst. Returns
    the three losses of each step, the per-leaf norm of the first gradient as
    Adam gets it, and the per-leaf norm of the parameters' change after the
    last step. `fault="half_batch"` plants a fault for the readings that set
    the limits: half of the batch left out, the mean taken over the rest."""
    net = Net(h, compute)
    if compute == "bfloat16":
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    ring, pos, valid = build_ring(rows, capacity)
    n_envs = pos.shape[0]
    ring_dev = {k: jnp.asarray(v) for k, v in ring.items()}
    pos_d, valid_d = jnp.asarray(pos, jnp.int32), jnp.asarray(valid, jnp.int32)
    key = jax.random.fold_in(jnp.asarray(first_flush["key"], jnp.uint32), 0)
    keys = jax.random.split(key, first_flush["grad_chunk"])[:steps]

    def one_step(state, key, ring_dev, pos_d, valid_d):  # the ring is an argument: as a constant it would be compiled in
        t_idx, env_idx, k_grad = sample_windows(key, pos_d, valid_d, capacity, h["seq"], h["batch"], n_envs)
        batch = {k: v[t_idx, env_idx[None, :]] for k, v in ring_dev.items()}
        if fault == "half_batch":
            batch = {k: v[:, : h["batch"] // 2] for k, v in batch.items()}
        state, loss, given = gradient_step(net, state, batch, k_grad)
        return state, loss, _leaf_norms(given)

    one_step = jax.jit(one_step, donate_argnums=0)
    change_norms = jax.jit(
        lambda p0, p1: _leaf_norms({m: jax.tree.map(lambda a, b: b - a, p0[m], p1[m]) for m in MODULES}))
    state = {
        "params": {**jax.tree.map(jnp.copy, params), "target_critic": jax.tree.map(jnp.copy, params["critic"])},
        "opts": {m: adam_init(params[m]) for m in MODULES},
        "moments": {"low": jnp.zeros((), jnp.float32), "high": jnp.zeros((), jnp.float32)},
        "cum": jnp.zeros((), jnp.int32),
    }
    losses, first_given = [], None
    for i in range(steps):
        state, loss, given = one_step(state, keys[i], ring_dev, pos_d, valid_d)
        losses.append(np.asarray(loss))
        if i == 0:
            first_given = np.asarray(given)
    change = change_norms(params, state["params"])
    losses = np.stack(losses)
    return {"losses": np.asarray(losses), "grad_norm_step1": np.asarray(first_given), "dp_norm": np.asarray(change),
            "ring_pos": pos, "ring_valid": valid}
