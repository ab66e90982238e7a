"""Model FLOPs of one gradient step of token-level PPO on the convolution
decoder (`lfm2_moe`: gated short-convolution layers and grouped-query
attention layers with q/k norms, a leading dense layer, routed experts without
a shared one, a head tied to the embedding), from the configuration's widths
and the traffic's lengths, and the operations and bytes of its two kernels.

Matrix products (2 x multiply-adds) as flops/ppo_lm.py counts them, and
besides them the elementwise work of the convolution layer (a gate's product
an element of `hidden`, `2 x conv_kernel - 1` operations an element for the
taps, the output gate's product) and the RMSNorms (four operations an element:
square, sum, scale by the inverse root, scale by the weight; two a layer over
`hidden`, the q/k norms over every query and key head of an attention layer).
An iteration is the prefill of `num_envs` prompts, `response_len` decode steps
and the update's forward and backward over every sequence (a backward pass is
twice the forward; what `jax.checkpoint` recomputes is not counted). Routed
work is counted at the expected `top_k * experts_held / experts` experts a
token, the dense layer whole; attention's score work by the mask's true area
on the attention layers alone (`ppo_lm.py`'s `visible_pairs`, loaded by path:
full causal, query i sees i + 1 keys). The tied head is one product of
`hidden x (vocab + 1)` a position, as an untied one.

The kernels' functions take what was counted (the assignments that landed on
held experts, from the block's counters) and are **lower bounds on the
kernel's work whatever implements it**, so that no share of a roofline can
read over 100%.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, Tuple


def _ppo_lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ppo_lm.py")
    spec = importlib.util.spec_from_file_location("chipbench_flops_ppo_lm_for_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


visible_pairs = _ppo_lm().visible_pairs
NORM_OPS = 4.0  # operations an element of an RMSNorm


def widths(config: Dict[str, Any]) -> Dict[str, Any]:
    a = config["as_run"]
    lm = lambda k: a["algo.lm." + k]  # noqa: E731
    layers = int(lm("num_hidden_layers"))
    dense = min(int(lm("num_dense_layers")), layers)
    heads, hidden = lm("num_attention_heads"), lm("hidden_size")
    head_dim = hidden // heads
    conv = [t == "conv" for t in list(lm("layer_types"))[:layers]]
    return {
        "hidden": hidden, "heads": heads, "kv_heads": lm("num_key_value_heads"), "head_dim": head_dim,
        "q": heads * head_dim, "kv": lm("num_key_value_heads") * head_dim, "layers": layers, "dense_layers": dense,
        "routed_layers": layers - dense, "conv_layers": sum(conv), "attention_layers": layers - sum(conv),
        "conv_kernel": lm("conv_L_cache"), "dense_width": lm("intermediate_size"), "experts": lm("num_experts"),
        "top_k": lm("num_experts_per_tok"), "expert_width": lm("moe_intermediate_size"),
        "experts_held": lm("experts_held"), "vocab": lm("vocab_held"),
        "prompt": a["env.prompt_len"], "response": a["algo.rollout_steps"], "envs": a["env.num_envs"],
        "minibatch": a["algo.per_rank_batch_size"], "epochs": a["algo.update_epochs"],
    }


def token_flops(w: Dict[str, Any]) -> Dict[str, float]:
    """Per token, summed over the layers, outside attention's scores and the head."""
    H, Q, KV, C, A = w["hidden"], w["q"], w["kv"], w["conv_layers"], w["attention_layers"]
    return {
        "conv": 2.0 * H * (3 * H + H) * C,  # the input projection to [b | c | v], the output projection
        "conv_mix": (1 + (2 * w["conv_kernel"] - 1) + 1) * H * C,  # b * v, the taps, c * s
        "attention": 2.0 * H * (2 * Q + 2 * KV) * A,  # q, the output; k, v
        "norms": NORM_OPS * (2 * H * w["layers"] + (Q + KV) * A),
        "dense": 3 * 2.0 * H * w["dense_width"] * w["dense_layers"],
        "router": 2.0 * H * w["experts"] * w["routed_layers"],
        "experts": w["top_k"] * w["experts_held"] / w["experts"] * 3 * 2.0 * H * w["expert_width"] * w["routed_layers"],
    }


def pair_flops(w: Dict[str, Any]) -> float:
    """Per visible (query, key) pair, all heads: the score and the weighted sum."""
    return 4.0 * w["head_dim"] * w["heads"]


def head_flops(w: Dict[str, Any]) -> float:
    return 2.0 * w["hidden"] * (w["vocab"] + 1)


def pairs(w: Dict[str, Any], seq: int, first: int = 0) -> int:
    """Visible pairs of queries `first .. seq - 1`, summed over the attention layers."""
    return w["attention_layers"] * visible_pairs(seq, 0, first)


def parts(config: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one iteration by phase."""
    w = widths(config)
    P, R, T = w["prompt"], w["response"], w["prompt"] + w["response"]
    per_token = sum(token_flops(w).values())
    prefill = P * per_token + pairs(w, P) * pair_flops(w) + head_flops(w)
    decode = R * (per_token + head_flops(w)) + pairs(w, T, first=P) * pair_flops(w)
    forward = T * per_token + pairs(w, T) * pair_flops(w) + R * head_flops(w)
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
    forward twice (the routed layer is recomputed under `jax.checkpoint`) and
    backward once, the backward being two products for each forward one.
    Bytes: the rows in and out in float32, the hidden activations, and the
    experts' weights in bfloat16: all the held experts' once per call of
    thousands of rows (prefill, the update: every expert has rows), **one
    expert's per decode call**, whose handful of assignments reach at least
    one expert and may reach no more."""
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
    the prefill's forward, and the update's forward and backward (five
    products for the forward's two; the update's remat keeps the kernel's
    output and log-sum-exp, so its forward runs once), each over the causal
    mask's true area on the attention layers. Bytes: q, k, v and the output
    (and their gradients) once per pass in float32; decode does not run this
    kernel."""
    w = widths(config)
    P, T, E = w["prompt"], w["prompt"] + w["response"], w["envs"]
    io = lambda seq: 4.0 * seq * (2 * w["q"] + 2 * w["kv"])  # noqa: E731
    A = w["attention_layers"]
    return {
        "prefill": (E * pairs(w, P) * pair_flops(w), E * A * io(P)),
        "update": (E * w["epochs"] * pairs(w, T) * pair_flops(w) * (2 + 5) / 2.0, E * w["epochs"] * A * 3 * io(T)),
    }


def roofline_seconds(work: Dict[str, Tuple[float, float]], peaks: Dict[str, float]) -> float:
    """The least time the work can take: per phase the larger of operations
    over the bf16 peak and bytes over the memory bandwidth."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"]) for f, b in work.values())
