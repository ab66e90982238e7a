"""What decides `correct` for the token-level PPO cells: the first iteration
of the timed block (the same compiled object, the timed sizes) dispatched with
1, 2 and 3 of its gradient steps granted, against the plain reference from its
own weights.

(a) The rollout: the reference recomputes, by its full forward over all
positions, the log-probabilities of the tokens the program sampled and the
values of the states it sampled them in. That is decode through the two-kind
cache against the full forward.
- `rollout_logprob_mean`, `rollout_value_mean`: the mean absolute gap over all
  envs and response positions (log-probabilities are near -log(vocabulary),
  values near +-1: absolute gaps mean the same everywhere). The mean and not
  the largest: the router's near-ties flip with the operands' rounding, a
  flipped expert moves that token's hidden state by a visible amount, and the
  largest gap over 2048 positions reads those few tokens and nothing else
  (`rollout_logprob`, `rollout_value` are the largest; read, not compared).
- `rollout_repeats_differ`: sampled tokens that differ between the dispatches
  (the same inputs have to give the same rollout); exactly 0.
(b) The update: the reference follows the three gradient steps on the
program's trajectories (its tokens, log-probabilities, values and rewards),
minibatches in the program's order (from the block's key).
- `loss_policy`, `loss_value`, `loss_entropy`: each step's loss; the worst
  step's gap relative to the reference's loss (with a floor, below).
- `grad_norm`: each step's global gradient norm before the clip; worst step.
- `loss_value_first`: the value loss at the first step alone, before any
  update has fed a difference back.
Per entry, where an entry is a parameter leaf or, for the routed experts'
leaves, one expert of it, after 1, 2 and 3 steps granted:
- `adam_moment`, `param_change`, `param_change_experts`: the gap between the
  program's and the reference's norm of Adam's first moment (the gradients as
  Adam got them) and of the parameters' change, over the reference's norm of
  that entry or of the median entry, whichever is larger; the worst entry of
  the worst grant (`param_change_experts`: over the experts' entries alone).
  A norm says how far something moved, not where to.
- `grad_direction`, `param_direction_first`, `param_direction`: the norm of
  the difference between the program's and the reference's sketch (32 sums
  over contiguous chunks: a linear map, so it reads the difference of the two
  arrays and not of their lengths) over the reference's sketch's norm, or the
  median entry's; of Adam's first moment after one step (the first gradient),
  of the parameters' change after one step, and after each of the three.
- `held_assignments`: per layer, the assignments that landed on held experts
  in the update's forwards, program against reference, relative (the router's
  near-ties flip with the operands' rounding, so it is not exact).
- `moe_dropped`: what the program counted as dropped (assignments to held
  experts less the rows its grouped products were handed); exactly 0.
A number whose limit in the configuration's file is `null` is read and kept in
the record but not compared.

With `--control 1` (not a benchmark run) the reference is also run as the
bfloat16 control and with each planted fault, and each is put through the same
limits as the program: `controls_passing` counts those that fail none, and a
run with any is not `correct`.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

LOSS_NAMES = ("policy", "value", "entropy")
# the policy loss is a mean of advantages times ratios near 1 and can come near 0: a gap is read against this scale
LOSS_FLOOR = {"policy": 1e-2, "value": 1e-6, "entropy": 1e-6}
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# what the reference is also run as with --control 1; the last two leave the rollout as it is
CONTROLS = (("control_bfloat16", {"compute": "bfloat16"}), ("fault_window_dropped", {"fault": "window_dropped"}),
            ("fault_expert_skipped", {"fault": "expert_skipped"}),
            ("fault_expert_skipped_in_update", {"fault": "expert_skipped_in_update"}),
            ("fault_half_batch", {"fault": "half_batch"}))
UPDATE_ONLY = ("expert_skipped_in_update", "half_batch")


def minibatch_order(train_key: np.ndarray, h: Dict[str, Any]) -> np.ndarray:
    """The program's minibatches of its first iteration: `(steps, minibatch)`
    sequence indices, from the key the block was given (device 0 of `dp`)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.split(jnp.asarray(train_key, jnp.uint32), 1)[0]  # iters_per_block = 1
    key = jax.random.fold_in(key, 0)
    envs, mb = h["num_envs"], h["minibatch"]
    return np.concatenate([
        np.asarray(jax.random.permutation(k, envs)).reshape(envs // mb, mb) for k in jax.random.split(key, h["update_epochs"])
    ])


def worst_entry(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    """`got`, `want`: `(grants, entries)` norms or `(grants, entries, sketch)`
    sketches. Per grant and entry the length of the difference over the
    reference's length, or the median kept entry's of that grant where that is
    larger; the worst kept entry of the worst grant."""
    if got.ndim == 2:
        got, want = got[..., None], want[..., None]
    gap, size = np.linalg.norm(got - want, axis=-1)[:, keep], np.linalg.norm(want, axis=-1)[:, keep]
    denom = np.maximum(size, np.median(size, axis=1, keepdims=True))
    return float(np.max(np.where(denom > 0, gap / np.where(denom > 0, denom, 1.0), 0.0)))


def compare(got: Dict[str, Any], want: Dict[str, Any], entries) -> Dict[str, float]:
    """The numbers, `got` in the program's place and `want` the reference."""
    logprob_gap = np.abs(got["rollout"]["logprobs"] - want["rollout"]["logprobs"])
    value_gap = np.concatenate([np.abs(got["rollout"]["values"] - want["rollout"]["values"]),
                                np.abs(got["rollout"]["last_value"] - want["rollout"]["last_value"])[:, None]], axis=1)
    out = {"rollout_logprob": float(logprob_gap.max()), "rollout_logprob_mean": float(logprob_gap.mean()),
           "rollout_value": float(value_gap.max()), "rollout_value_mean": float(value_gap.mean())}
    for j, name in enumerate(LOSS_NAMES):
        g, w = got["losses"][:, j], want["losses"][:, j]
        out["loss_" + name] = float(np.max(np.abs(g - w) / np.maximum(np.abs(w), LOSS_FLOOR[name])))
    g, w = got["losses"][:, 3], want["losses"][:, 3]
    out["grad_norm"] = float(np.max(np.abs(g - w) / w))
    g, w = got["losses"][:, 1], want["losses"][:, 1]
    out["loss_value_first"] = float(abs(g[0] - w[0]) / max(abs(w[0]), LOSS_FLOOR["value"]))
    everything = np.ones(len(entries), bool)
    experts = np.asarray([any(e in n for e in EXPERT_LEAVES) for n in entries])
    out["adam_moment"] = worst_entry(got["mu_norm"], want["mu_norm"], everything)
    out["param_change"] = worst_entry(got["dp_norm"], want["dp_norm"], everything)
    out["param_change_experts"] = worst_entry(got["dp_norm"], want["dp_norm"], experts)
    out["grad_direction"] = worst_entry(got["mu_sketch"][:1], want["mu_sketch"][:1], everything)
    out["param_direction_first"] = worst_entry(got["dp_sketch"][:1], want["dp_sketch"][:1], everything)
    out["param_direction"] = worst_entry(got["dp_sketch"], want["dp_sketch"], everything)
    out["held_assignments"] = float(np.max(np.abs(got["held_assignments"] - want["held_assignments"])
                                           / np.maximum(want["held_assignments"], 1.0)))
    return out


def through_limits(values: Dict[str, float], limits: Dict[str, Any]):
    """`(numbers, read_only)`: the values that have a limit, each beside it, and the others."""
    numbers = {k: {"value": v, "limit": float(limits[k])} for k, v in values.items() if limits.get(k) is not None}
    return numbers, {k: v for k, v in values.items() if limits.get(k) is None}


def failing(numbers: Dict[str, Dict[str, float]]):
    return [k for k, v in numbers.items() if not (np.isfinite(v["value"]) and v["value"] <= v["limit"])]


def check(adapter, cfg, config, ref, control: bool = False) -> Dict[str, Any]:
    """`ref` is the family's plain reference (reference/<family>_ref.py)."""
    import jax

    limits = config["correct_limits"]
    numbers: Dict[str, Dict[str, Any]] = {}
    first = adapter.first
    if not first or "error" in first:
        numbers["first_block_readings"] = {"value": 1.0, "limit": 0.0, "note": (first or {}).get("error", "none taken")}
        return {"correct": False, "numbers": numbers}
    h = ref.hyper(config["as_run"], config["assumed"], cfg)
    t0 = time.perf_counter()
    make_params = lambda: ref.init_params(h, int(cfg.seed))  # noqa: E731
    shapes = jax.eval_shape(make_params)
    entries = ref.entry_names(shapes)
    if ref.leaf_names(shapes) != first["names"] or entries != first["entries"]:
        numbers["leaf_names_differ"] = {"value": 1.0, "limit": 0.0}
        return {"correct": False, "numbers": numbers}
    traj, steps = first["rollout"], len(first["grants"])
    order = minibatch_order(first["train_key"], h)

    def readings(rollout=None, **kwargs):
        out = ref.follow(h, make_params, traj, order, steps, **kwargs)
        out["rollout"] = rollout or ref.rollout_readings(h, make_params(), traj["tokens"], **kwargs)
        return out

    want = readings()
    reference_seconds = time.perf_counter() - t0
    got = {"rollout": traj, **{k: first[k] for k in ("losses", "mu_norm", "mu_sketch", "dp_norm", "dp_sketch",
                                                       "held_assignments")}}
    values = compare(got, want, entries)
    values["moe_dropped"] = first["moe_dropped"]
    values["rollout_repeats_differ"] = first["rollout_repeats_differ"]
    numbers, read_only = through_limits(values, limits)
    verdict = {"correct": not failing(numbers), "numbers": numbers, "read_only": read_only,
               "reference_seconds": reference_seconds,
               "detail": {"program_losses": got["losses"].tolist(), "reference_losses": want["losses"].tolist(),
                          "entries": entries, "program_moment": got["mu_norm"].tolist(),
                          "reference_moment": want["mu_norm"].tolist(), "program_change": got["dp_norm"].tolist(),
                          "reference_change": want["dp_norm"].tolist(),
                          "program_held": got["held_assignments"].tolist(),
                          "reference_held": want["held_assignments"].tolist(), "order": order.tolist(),
                          "rewards": np.asarray(traj["rewards"]).sum(axis=-1).tolist()}}
    if control:
        # not part of a benchmark run: the readings that the limits are set from, each put through the limits
        passing = []
        for label, kwargs in CONTROLS:
            rollout = want["rollout"] if kwargs.get("fault") in UPDATE_ONLY else None
            other = compare(readings(rollout, **kwargs), want, entries)
            fails = failing(through_limits(other, limits)[0])
            verdict[label] = {**other, "fails": fails}
            if not fails:
                passing.append(label)
        numbers["controls_passing"] = {"value": float(len(passing)), "limit": 0.0, "note": ", ".join(passing)}
        verdict["correct"] = not failing(numbers)
    jax.clear_caches()
    return verdict
