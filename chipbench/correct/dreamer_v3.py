"""What decides `correct` for the DreamerV3 cells: the first three gradient
steps of the first training burst, as the timed burst program returned them
(same compiled object, same state, same blob, 1, 2 and 3 steps granted),
against the plain reference following the same steps from its own weights,
its own ring and the same keys.

Compared, each with a limit from the configuration's file:
- `loss_*`: each step's world-model, actor and critic loss; the worst step's
  gap relative to the reference's loss.
- `grad_norm_world_model`, `grad_norm_behaviour`: per leaf, the norm of the
  first gradient as Adam gets it (its first moment after one step over
  1 - b1); the gap between the program's and the reference's norm over the
  reference's norm of that leaf or of the median leaf of the group, whichever
  is larger; the worst leaf of the world model, and of actor and critic.
- `param_change_world_model`, `param_change_behaviour`: the same measure on the
  parameters' change after the three steps, over the leaves whose reference
  gradient is at least a thousandth of the median leaf's (the others move by
  round-off under Adam).
- `ring_heads_mismatch`: the ring's write heads after the burst's append, the
  program's against the reference's; exact.
A number whose limit in the configuration's file is `null` is read and kept in
the record but not compared.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

LOSS_NAMES = ("world_model", "actor", "critic")
LOSS_INDEX = {"world_model": 0, "actor": 8, "critic": 9}  # places in the program's metrics tuple
ZERO_GRADIENT_RULE = 1e-3  # of the median leaf's reference gradient norm
# The actor's loss is the sum of an advantage term and an entropy term of about
# ent_coef * log(actions) = 9e-4 that can cancel; a gap is read against that scale.
LOSS_FLOOR = {"world_model": 1e-6, "actor": 1e-3, "critic": 1e-6}


def program_readings(first: Dict[str, Any], b1: Dict[str, float], ref_names) -> Dict[str, Any]:
    """Per-step losses from the means over 1, 2, 3 granted steps; the first
    gradient's norms from Adam's first moment."""
    means = np.asarray(first["metrics"], np.float64)  # (steps, 10): row n-1 is the mean over the first n steps
    steps = means.shape[0]
    per_step = np.stack([(n + 1) * means[n] - n * means[n - 1] if n else means[0] for n in range(steps)])
    losses = np.stack([per_step[:, LOSS_INDEX[name]] for name in LOSS_NAMES], axis=1)
    scale = np.asarray([1.0 - b1[name.split("[", 1)[0]] for name in ref_names])
    return {"losses": losses, "grad_norm_step1": np.asarray(first["mu_norm_step1"], np.float64) / scale,
            "dp_norm": np.asarray(first["dp_norm"], np.float64)}


def worst_leaf(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    floor = float(np.median(want[keep])) if keep.any() else 0.0
    denom = np.maximum(want, floor)
    gap = np.where(denom > 0, np.abs(got - want) / np.where(denom > 0, denom, 1.0), 0.0)
    return float(np.max(gap[keep])) if keep.any() else 0.0


BEHAVIOUR = ("actor", "critic")


def compare(got: Dict[str, Any], want: Dict[str, Any], names) -> Dict[str, float]:
    """The numbers, `got` in the program's place and `want` the reference. The
    world model's leaves and the behaviour's (actor, critic) are read apart:
    the behaviour's losses and gradients hang on thresholded continue
    predictions of a fresh head, which any rounding flips."""
    out = {}
    for j, name in enumerate(LOSS_NAMES):
        g, w = got["losses"][:, j], want["losses"][:, j]
        out["loss_" + name] = float(np.max(np.abs(g - w) / np.maximum(np.abs(w), LOSS_FLOOR[name])))
    g_ref = np.asarray(want["grad_norm_step1"], np.float64)
    moved = g_ref >= ZERO_GRADIENT_RULE * np.median(g_ref)
    behaviour = np.asarray([n.split("[", 1)[0] in BEHAVIOUR for n in names])
    for label, part in (("world_model", ~behaviour), ("behaviour", behaviour)):
        out["grad_norm_" + label] = worst_leaf(np.asarray(got["grad_norm_step1"], np.float64), g_ref, part)
        out["param_change_" + label] = worst_leaf(
            np.asarray(got["dp_norm"], np.float64), np.asarray(want["dp_norm"], np.float64), moved & part)
    return out


def check(adapter, cfg, config, ref, control: bool = False) -> Dict[str, Any]:
    """`ref` is the family's plain reference (reference/<family>_ref.py)."""
    import jax

    limits = config["correct_limits"]
    numbers: Dict[str, Dict[str, Any]] = {}
    first = adapter.first
    if not first or "error" in first or adapter.first_flush is None:
        numbers["first_burst_readings"] = {"value": 1.0, "limit": 0.0, "note": (first or {}).get("error", "none taken")}
        return {"correct": False, "numbers": numbers}
    h = ref.hyper(config["as_run"], config["assumed"], cfg)
    t0 = time.perf_counter()
    params = ref.init_params(h, int(cfg.seed))
    names = ref.leaf_names(params)
    if names != first["names"]:
        numbers["leaf_names_differ"] = {"value": 1.0, "limit": 0.0}
        return {"correct": False, "numbers": numbers}
    capacity = int(cfg.buffer.size) // int(cfg.env.num_envs)
    steps = len(first["metrics"])
    want = ref.follow(h, params, adapter.staged_rows, adapter.first_flush, capacity, steps)
    reference_seconds = time.perf_counter() - t0
    b1 = {name: h["optim"][name]["b1"] for name in ref.MODULES}
    got = program_readings(first, b1, names)
    values = compare(got, want, names)
    heads = int(np.sum(want["ring_pos"] != adapter.first_flush["pos_after"])
                + np.sum(want["ring_valid"] != adapter.first_flush["valid_after"]))
    values["ring_heads_mismatch"] = float(heads)
    read_only = {}
    for name, value in values.items():
        if limits.get(name) is None:  # read and kept in the record, not compared (PERF.md section 2 says why)
            read_only[name] = value
        else:
            numbers[name] = {"value": value, "limit": float(limits[name])}
    correct = all(np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in numbers.values())
    verdict = {"correct": bool(correct), "numbers": numbers, "read_only": read_only,
               "reference_seconds": reference_seconds,
               "detail": {"program_losses": got["losses"].tolist(), "reference_losses": want["losses"].tolist(),
                          "names": names, "program_grad": got["grad_norm_step1"].tolist(),
                          "reference_grad": np.asarray(want["grad_norm_step1"]).tolist(),
                          "program_change": got["dp_norm"].tolist(),
                          "reference_change": np.asarray(want["dp_norm"]).tolist()}}
    if control:
        # not part of a benchmark run: the readings that the limits are set from
        for label, kwargs in (("control_bfloat16", {"compute": "bfloat16"}), ("fault_half_batch", {"fault": "half_batch"})):
            other = ref.follow(h, params, adapter.staged_rows, adapter.first_flush, capacity, steps, **kwargs)
            verdict[label] = compare(other, want, names)
            verdict["detail"][label + "_grad"] = np.asarray(other["grad_norm_step1"]).tolist()
            verdict["detail"][label + "_change"] = np.asarray(other["dp_norm"]).tolist()
    jax.clear_caches()
    return verdict
