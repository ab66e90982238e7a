"""Model FLOPs of one gradient step of token-level PPO on the decoder language
model, from the configuration's widths and the traffic's lengths, and the
operations and bytes of its two kernels.

Matrix products only (2 x multiply-adds). An iteration is the prefill of
`num_envs` prompts, `response_len` decode steps and the update's forward and
backward over every sequence (a backward pass is twice the forward; what the
update recomputes under `jax.checkpoint` is not counted: recomputed operations
are not model FLOPs). A gradient step is an iteration over the gradient steps
it holds. Routed work is counted at the expected `top_k * experts_held /
experts` experts a token, attention's score work by the mask's true area.

The kernels' functions take what was counted, not what was expected: the
assignments that landed on held experts, from the block's counters.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def widths(config: Dict[str, Any]) -> Dict[str, Any]:
    a = config["as_run"]
    lm = lambda k: a["algo.lm." + k]  # noqa: E731
    layers = int(lm("num_hidden_layers"))
    return {
        "hidden": lm("hidden_size"), "q": lm("num_attention_heads") * lm("head_dim"),
        "kv": lm("num_key_value_heads") * lm("head_dim"), "heads": lm("num_attention_heads"), "head_dim": lm("head_dim"),
        "layers": layers, "experts": lm("moe_num_primary_experts"), "top_k": lm("moe_num_active_primary_experts"),
        "expert_width": lm("moe_ffn_hidden_size"), "experts_held": lm("experts_held"), "vocab": lm("vocab_held"),
        "window": lm("sliding_window_size"), "window_layout": list(lm("sliding_window_layout"))[:layers],
        "prompt": a["env.prompt_len"], "response": a["algo.rollout_steps"], "envs": a["env.num_envs"],
        "minibatch": a["algo.per_rank_batch_size"], "epochs": a["algo.update_epochs"],
    }


def visible_pairs(seq: int, window: int, first: int = 0) -> int:
    """(query, key) pairs a causal mask lets through for queries `first .. seq - 1`
    of one head: query i sees min(i + 1, window) keys (every key where `window` is 0)."""
    def upto(n: int) -> int:  # queries 0 .. n - 1
        ramp = min(n, window) if window else n
        return ramp * (ramp + 1) // 2 + (n - ramp) * window

    return upto(seq) - upto(first)


def token_flops(w: Dict[str, Any]) -> Dict[str, float]:
    """Per token and layer, outside attention's scores and the head."""
    return {
        "projections": 2.0 * w["hidden"] * (2 * w["q"] + 2 * w["kv"]),
        "router": 2.0 * w["hidden"] * w["experts"],
        "experts": w["top_k"] * w["experts_held"] / w["experts"] * 3 * 2.0 * w["hidden"] * w["expert_width"],
    }


def pair_flops(w: Dict[str, Any]) -> float:
    """Per visible (query, key) pair, all heads: the score and the weighted sum."""
    return 4.0 * w["head_dim"] * w["heads"]


def head_flops(w: Dict[str, Any]) -> float:
    """Per position the head is applied to: logits and the value."""
    return 2.0 * w["hidden"] * (w["vocab"] + 1)


def parts(config: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one iteration by phase."""
    w = widths(config)
    P, R, T, L = w["prompt"], w["response"], w["prompt"] + w["response"], w["layers"]
    per_token = sum(token_flops(w).values()) * L
    windows = [w["window"] if flag else 0 for flag in w["window_layout"]]
    prefill = P * per_token + sum(visible_pairs(P, win) for win in windows) * pair_flops(w) + head_flops(w)
    decode = R * (per_token + head_flops(w)) + sum(visible_pairs(T, win, first=P) for win in windows) * pair_flops(w)
    forward = T * per_token + sum(visible_pairs(T, win) for win in windows) * pair_flops(w) + R * head_flops(w)
    return {"prefill": w["envs"] * prefill, "decode": w["envs"] * decode, "update": w["envs"] * w["epochs"] * 3.0 * forward}


def grad_steps_per_iteration(config: Dict[str, Any]) -> int:
    w = widths(config)
    return w["epochs"] * (w["envs"] // w["minibatch"])


def flops_per_grad_step(config: Dict[str, Any]) -> float:
    return float(sum(parts(config).values())) / grad_steps_per_iteration(config)


def flops_as_xla_counts(config: Dict[str, Any]) -> float:
    """With every loop's body counted once, which is how XLA's `cost_analysis()` counts a while loop:
    one prompt's prefill, one decode step, one minibatch's update (a gradient step's worth, not an iteration's)."""
    w, p = widths(config), parts(config)
    return p["prefill"] / w["envs"] + p["decode"] / (w["envs"] * w["response"]) + p["update"] / grad_steps_per_iteration(config)


# -- the two kernels: operations and bytes of what ran, per iteration ----------
def moe_kernel_work(config: Dict[str, Any], update_assignments: float, rollout_assignments: float) -> Dict[str, Tuple[float, float]]:
    """`{phase: (flops, bytes)}` of `kernel.moe_grouped_ffn` in one iteration,
    from the assignments that landed on held experts (summed over layers) in
    the update's forwards and in the rollout. An assignment is three products
    of hidden x expert_width. The update runs the kernel forward twice (once
    again under `jax.checkpoint`) and backward once, the backward being two
    products for each forward one. Bytes: the rows in and out in float32, the
    hidden activations, and the held experts' weights once per call in
    bfloat16 (the decode steps are bound by this last term)."""
    w = widths(config)
    per_assignment = 3 * 2.0 * w["hidden"] * w["expert_width"]
    rows = 4.0 * (2 * w["hidden"] + 3 * w["expert_width"])  # a row in, a row out, gate, up, their product
    weights = 2.0 * w["experts_held"] * 3 * w["hidden"] * w["expert_width"]
    L, E, R = w["layers"], w["envs"], w["response"]
    update_calls = L * w["epochs"] * (E // w["minibatch"])
    return {
        "update": (4.0 * update_assignments * per_assignment,
                   4.0 * update_assignments * rows + update_calls * (3 * weights + 2 * weights)),  # + float32 gradients out
        "rollout": (rollout_assignments * per_assignment, rollout_assignments * rows + L * (E + R) * weights),
    }


def attention_kernel_work(config: Dict[str, Any]) -> Dict[str, Tuple[float, float]]:
    """`{phase: (flops, bytes)}` of `kernel.window_attention` in one iteration:
    the prefill's forward, and the update's forward (twice, once again under
    `jax.checkpoint`) and backward (five products for the forward's two), each
    over the mask's true area. Bytes: q, k, v and the output (and their
    gradients) once per pass in float32; decode does not run this kernel."""
    w = widths(config)
    P, T, E = w["prompt"], w["prompt"] + w["response"], w["envs"]
    windows = [w["window"] if flag else 0 for flag in w["window_layout"]]
    io = lambda seq: 4.0 * seq * (2 * w["q"] + 2 * w["kv"])  # noqa: E731
    return {
        "prefill": (E * sum(visible_pairs(P, win) for win in windows) * pair_flops(w), E * len(windows) * io(P)),
        "update": (E * w["epochs"] * sum(visible_pairs(T, win) for win in windows) * pair_flops(w) * (2 + 2 + 5) / 2.0,
                   E * w["epochs"] * len(windows) * 4 * io(T)),
    }


def roofline_seconds(work: Dict[str, Tuple[float, float]], peaks: Dict[str, float]) -> float:
    """The least time the work can take: per phase the larger of operations
    over the bf16 peak and bytes over the memory bandwidth."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"]) for f, b in work.values())
