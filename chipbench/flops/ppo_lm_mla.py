"""Model FLOPs of one gradient step of token-level PPO on the latent-attention
decoder (MLA, a leading dense layer, routed experts beside shared ones), from
the configuration's widths and the traffic's lengths, and the operations and
bytes of its two kernels.

Matrix products only (2 x multiply-adds), as flops/ppo_lm.py counts them: an
iteration is the prefill of `num_envs` prompts, `response_len` decode steps
and the update's forward and backward over every sequence (a backward pass is
twice the forward; what `jax.checkpoint` recomputes is not counted). **Every
phase is counted in the expanded form** (the latent up-projected to each
head's key and value, a score of `nope + rope` and a weighted sum of `v` a
visible pair and head): a decode that does more arithmetic over the latent
cache to read fewer bytes does not raise `train_mfu_pct`. Routed work is
counted at the expected `top_k * experts_held / experts` experts a token; the
shared experts and the dense layer whole.

The kernels' functions take what was counted (the assignments that landed on
held experts, from the block's counters) and are **lower bounds on the
kernel's work whatever implements it**, so that no share of a roofline can
read over 100%: scores over the mask's true area at `nope + rope + v` a pair
and head (a kernel that pads 192 to 256 pays for it), operands at the width
the kernel is handed them.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def widths(config: Dict[str, Any]) -> Dict[str, Any]:
    a = config["as_run"]
    lm = lambda k: a["algo.lm." + k]  # noqa: E731
    layers = int(lm("num_hidden_layers"))
    dense = min(int(lm("first_k_dense_replace")), layers)
    return {
        "hidden": lm("hidden_size"), "heads": lm("num_attention_heads"), "nope": lm("qk_nope_head_dim"),
        "rope": lm("qk_rope_head_dim"), "v": lm("v_head_dim"), "latent": lm("kv_lora_rank"), "layers": layers,
        "dense_layers": dense, "routed_layers": layers - dense, "dense_width": lm("intermediate_size"),
        "experts": lm("n_routed_experts"), "top_k": lm("num_experts_per_tok"), "expert_width": lm("moe_intermediate_size"),
        "shared_width": lm("n_shared_experts") * lm("moe_intermediate_size"), "experts_held": lm("experts_held"),
        "vocab": lm("vocab_held"), "prompt": a["env.prompt_len"], "response": a["algo.rollout_steps"],
        "envs": a["env.num_envs"], "minibatch": a["algo.per_rank_batch_size"], "epochs": a["algo.update_epochs"],
    }


def visible_pairs(seq: int, first: int = 0) -> int:
    """(query, key) pairs a causal mask lets through for queries `first .. seq - 1` of one head."""
    return seq * (seq + 1) // 2 - first * (first + 1) // 2


def token_flops(w: Dict[str, Any]) -> Dict[str, float]:
    """Per token, summed over the layers, outside attention's scores and the head."""
    H, N = w["hidden"], w["heads"]
    attention = H * N * (w["nope"] + w["rope"]) + H * (w["latent"] + w["rope"]) + w["latent"] * N * (w["nope"] + w["v"]) + N * w["v"] * H
    return {
        "projections": 2.0 * attention * w["layers"],
        "dense": 3 * 2.0 * H * w["dense_width"] * w["dense_layers"],
        "router": 2.0 * H * w["experts"] * w["routed_layers"],
        "shared": 3 * 2.0 * H * w["shared_width"] * w["routed_layers"],
        "experts": w["top_k"] * w["experts_held"] / w["experts"] * 3 * 2.0 * H * w["expert_width"] * w["routed_layers"],
    }


def pair_flops(w: Dict[str, Any]) -> float:
    """Per visible (query, key) pair, all heads: the score over `nope + rope` and the weighted sum over `v`."""
    return 2.0 * (w["nope"] + w["rope"] + w["v"]) * w["heads"]


def head_flops(w: Dict[str, Any]) -> float:
    return 2.0 * w["hidden"] * (w["vocab"] + 1)


def parts(config: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one iteration by phase."""
    w = widths(config)
    P, R, T, L = w["prompt"], w["response"], w["prompt"] + w["response"], w["layers"]
    per_token = sum(token_flops(w).values())
    prefill = P * per_token + L * visible_pairs(P) * pair_flops(w) + head_flops(w)
    decode = R * (per_token + head_flops(w)) + L * visible_pairs(T, first=P) * pair_flops(w)
    forward = T * per_token + L * visible_pairs(T) * pair_flops(w) + R * head_flops(w)
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
    from the assignments that landed on held experts (summed over the routed
    layers) in the update's forwards and in the rollout. An assignment is
    three products of hidden x expert_width. The update runs the kernel
    forward twice (once again under `jax.checkpoint`) and backward once, the
    backward being two products for each forward one. Bytes: the rows in and
    out in float32, the hidden activations, and the experts' weights in
    bfloat16: all the held experts' once per call of thousands of rows
    (prefill, the update: every expert has rows), **one expert's per decode
    call**, whose handful of assignments reach at least one expert and may
    reach no more."""
    w = widths(config)
    per_assignment = 3 * 2.0 * w["hidden"] * w["expert_width"]
    rows = 4.0 * (2 * w["hidden"] + 3 * w["expert_width"])  # a row in, a row out, gate, up, their product
    one_expert = 2.0 * 3 * w["hidden"] * w["expert_width"]
    weights = w["experts_held"] * one_expert
    L, E, R = w["routed_layers"], w["envs"], w["response"]
    update_calls = L * w["epochs"] * (E // w["minibatch"])
    return {
        "update": (4.0 * update_assignments * per_assignment,
                   4.0 * update_assignments * rows + update_calls * (3 * weights + 2 * weights)),  # + float32 gradients out
        "rollout": (rollout_assignments * per_assignment, rollout_assignments * rows + L * E * weights + L * R * one_expert),
    }


def attention_kernel_work(config: Dict[str, Any]) -> Dict[str, Tuple[float, float]]:
    """`{phase: (flops, bytes)}` of `kernel.window_attention` in one iteration:
    the prefill's forward, and the update's forward (twice, once again under
    `jax.checkpoint`) and backward (five products for the forward's two), each
    over the causal mask's true area at `nope + rope` a score and `v` a
    weighted sum. Bytes: q and k of `nope + rope`, v and the output of `v` a
    head (and their gradients) once per pass in float32, as the kernel is
    handed them; decode does not run this kernel."""
    w = widths(config)
    P, T, E, L = w["prompt"], w["prompt"] + w["response"], w["envs"], w["layers"]
    io = lambda seq: 4.0 * seq * w["heads"] * (2 * (w["nope"] + w["rope"]) + 2 * w["v"])  # noqa: E731
    return {
        "prefill": (E * L * visible_pairs(P) * pair_flops(w), E * L * io(P)),
        "update": (E * w["epochs"] * L * visible_pairs(T) * pair_flops(w) * (2 + 2 + 5) / 2.0, E * w["epochs"] * L * 4 * io(T)),
    }


def roofline_seconds(work: Dict[str, Tuple[float, float]], peaks: Dict[str, float]) -> float:
    """The least time the work can take: per phase the larger of operations
    over the bf16 peak and bytes over the memory bandwidth."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"]) for f, b in work.values())
