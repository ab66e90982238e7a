"""What decides `correct` for the token-level PPO cell of the latent-attention
policy: `correct/ppo_lm.py`'s procedure and, but for these, its numbers (the
first iteration of the timed block dispatched with 1, 2 and 3 gradient steps
granted, against the plain reference from its own weights; that file's
docstring has every definition), with this family's reference, its own
controls, two exact counts of entries that never moved, and the router's
selection bias held apart.

(a) The rollout is decode in the **absorbed** form through the latent cache
(the key up-projection folded into the query, the value up-projection after
the weighted sum of latents); the reference recomputes the sampled tokens'
log-probabilities and the values by its **expanded** full forward over all
positions. The two paths through one attention have to agree
(`rollout_logprob_mean`, `rollout_value_mean`).
(b) The update, as there, with these numbers read otherwise, each because of
what this cell's readings on the chip are (PERF.md, section 2 and finding 36):
- `loss_policy`: the worst step's gap over the **largest of the three steps'
  reference losses** (or 0.03). The advantages are normalised over one
  sequence, so a step's policy loss is `-mean(adv * (ratio - 1))`, a
  covariance that comes as near 0 as the seed likes (0.003 on record), while
  the gap that rounding leaves is additive (0.0006-0.003 at every step): read
  against that step's own loss with a floor of 0.01 it spans 0.006-0.23.
- `adam_moment`, `param_change`: the worst entry **of the trained leaves that
  are no routed expert's** (attention, dense and shared feed-forward, router,
  norms, embedding, heads: 0.003-0.033 on record). An expert here is reached
  by about 12 of a sequence's 256 response tokens (6 of 128 experts a token),
  so one near-tie of the router that falls the other way under bfloat16
  operand rounding moves an expert's entry by a twelfth: the per-expert
  entries read 0.04-0.38 for a sound program, and would hide every fault
  under 0.7 if they shared a limit with the rest.
- `param_change_experts`: the per-expert entries alone, as there; **read, not
  compared** (limit `null`): 0.04-0.39 on 23 seeds and 0.86 on the 24th, where
  one response token fewer left an expert's first gradient under Adam's
  epsilon and its first step a twentieth of the reference's.
- `param_change_expert_leaves`: the same gap over each routed leaf's sixteen
  experts taken together (the norm over a leaf's experts: flips between
  experts of one leaf cancel in it; 0.002-0.023 on record), which sees the
  bias dropped and bfloat16 state among the experts.
- `moments_unmoved`, `entries_unmoved`: how many trained entries' first moment,
  and how many entries' change, is after the third grant under a thousandth of
  the reference's (the least on record for a sound program is 0.23 and 0.58
  of it); exactly 0. "An entry the program never moved reads 1.0" made exact:
  it is what sees one expert skipped in the update (twelve entries, both
  counts), whatever that expert's load, and a norm scale that bfloat16 state
  cannot move.
The selection bias (`router_bias`, one entry a routed layer) is no trained
weight: its entries are kept out of every relative number (an entry that
never moves would read against the median entry's scale and hide there) and
held to a number of their own:
- `router_bias_change`: the largest norm, over the bias's entries and the
  three grants, of Adam's first moment of it and of its change; exactly 0.

Loaded by path as this file's own instance of `correct/ppo_lm.py`, whose
`update_numbers` and `CONTROLS` are the ones below: `check`, `compare`'s
rollout, sketch and assignment numbers, `through_limits`, `failing` are that
file's.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from typing import Any, Dict

import numpy as np


def _own_instance_of(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_correct_{name}_for_mla", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_base = _own_instance_of("ppo_lm")
_base_compare, _base_update_numbers = _base.compare, _base.update_numbers
through_limits, failing, minibatch_order, worst_entry = _base.through_limits, _base.failing, _base.minibatch_order, _base.worst_entry

BIAS = "router_bias"
UNMOVED = 1e-3  # of the reference's norm after the last grant: under it an entry counts as never moved
POLICY_FLOOR = 0.03  # the smallest largest policy loss of three steps on record is 0.031; a seed may go under it
PER_ENTRY = ("mu_norm", "mu_sketch", "dp_norm", "dp_sketch")
# what the reference is also run as with --control 1; the last two leave the rollout as it is
CONTROLS = (("control_bfloat16", {"compute": "bfloat16"}), ("fault_rope_dropped", {"fault": "rope_dropped"}),
            ("fault_latent_norm_dropped", {"fault": "latent_norm_dropped"}),
            ("fault_shared_skipped", {"fault": "shared_skipped"}), ("fault_bias_dropped", {"fault": "bias_dropped"}),
            ("fault_expert_skipped_in_update", {"fault": "expert_skipped_in_update"}),
            ("fault_half_batch", {"fault": "half_batch"}))


def _trained(readings: Dict[str, Any], bias: np.ndarray) -> Dict[str, Any]:
    """The readings without the bias's entries."""
    return {**readings, **{k: np.asarray(readings[k])[:, ~bias] for k in PER_ENTRY if k in readings}}


def _bias_change(got: Dict[str, Any], bias: np.ndarray) -> float:
    if not bias.any():
        return 0.0
    return float(max(np.max(np.abs(np.asarray(got[k])[:, bias])) for k in ("mu_norm", "dp_norm")))


def _split(entries):
    bias = np.asarray([BIAS in name for name in entries])
    return bias, [name for name, b in zip(entries, bias) if not b]


def _expert_leaves(norms: np.ndarray, names) -> np.ndarray:
    """`(grants, entries)` norms of the routed experts' entries -> `(grants, leaves)`: the norm over each leaf's experts."""
    leaves = sorted({name.rsplit("[", 1)[0] for name in names})
    return np.stack([np.sqrt(sum(norms[:, i] ** 2 for i, name in enumerate(names) if name.rsplit("[", 1)[0] == leaf))
                     for leaf in leaves], axis=1)


def _numbers_over_trained(got, want, entries, max_grad_norm: float = 1.0) -> Dict[str, float]:
    """`correct/ppo_lm.py:update_numbers` over entries that hold no bias, with the four numbers this family reads otherwise."""
    out = _base_update_numbers(got, want, entries, max_grad_norm)
    policy = (got["losses"][:, 0], want["losses"][:, 0])
    out["loss_policy"] = float(np.max(np.abs(policy[0] - policy[1])) / max(np.max(np.abs(policy[1])), POLICY_FLOOR))
    ratio = float(np.clip(_base.clip_ratio(got["losses"], want["losses"], max_grad_norm), *_base.CLIP_RATIO_RANGE))
    experts = np.asarray([any(leaf in name for leaf in _base.EXPERT_LEAVES) for name in entries])
    out["adam_moment"] = worst_entry(got["mu_norm"], want["mu_norm"], ~experts, ratio)
    out["param_change"] = worst_entry(got["dp_norm"], want["dp_norm"], ~experts, ratio)
    names = [name for name, e in zip(entries, experts) if e]
    out["param_change_expert_leaves"] = worst_entry(_expert_leaves(np.asarray(got["dp_norm"])[:, experts], names),
                                                    _expert_leaves(np.asarray(want["dp_norm"])[:, experts], names), rescale=ratio)
    for name, key in (("moments_unmoved", "mu_norm"), ("entries_unmoved", "dp_norm")):
        out[name] = float(np.sum(np.asarray(got[key])[-1] <= UNMOVED * np.asarray(want[key])[-1]))
    return out


def update_numbers(got, want, entries, max_grad_norm: float = 1.0) -> Dict[str, float]:
    """(b) from the steps' `losses` and the entries' `mu_norm` and `dp_norm` alone, and `router_bias_change`: what
    a record of those (tests/ppo_lm_mla_readings.jsonl) can be put through again."""
    bias, trained = _split(entries)
    out = _numbers_over_trained(_trained(got, bias), _trained(want, bias), trained, max_grad_norm)
    out["router_bias_change"] = _bias_change(got, bias)
    return out


def compare(got, want, entries, max_grad_norm: float = 1.0) -> Dict[str, float]:
    bias, trained = _split(entries)
    out = _base_compare(_trained(got, bias), _trained(want, bias), trained, max_grad_norm)
    out["router_bias_change"] = _bias_change(got, bias)
    return out


_base.update_numbers, _base.compare, _base.CONTROLS = _numbers_over_trained, compare, CONTROLS
check = _base.check
