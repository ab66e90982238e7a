"""What decides `correct` for the token-level PPO cell of the convolution
policy (`lfm2_moe`): `correct/ppo_lm_mla.py`'s procedure and numbers (the
first iteration of the timed block dispatched with 1, 2 and 3 gradient steps
granted, against the plain reference from its own weights; the rollout
compared as means, the update's numbers over the trained entries, the routed
experts' leaves taken together, the router's selection bias held apart and to
exactly 0; that file's docstring and `correct/ppo_lm.py`'s have every
definition), with the two counts of entries that never moved taken over the
entries the reference moved (as `correct/ppo_lm_afmoe.py` takes them: an
expert no token reaches in three steps moves on neither side), this family's
reference, its own controls and three numbers of its own.

(a) The rollout is decode through **four convolution states and one full
buffer**: each decoded token's gated input is shifted into its layer's state
and the taps read the state; the reference recomputes the sampled tokens'
log-probabilities and the values by its full forward over all positions
(`rollout_logprob_mean`, `rollout_value_mean`). Only the first few decoded
positions read what prefill left in the states (position `P + j` reads the
prompt's gated inputs for `j < conv_kernel - 1`, and through the stack of
convolution layers up to `(conv_kernel - 1) x` their number), so a decode
that did not take prefill's state moves a few of the 256 positions and the
means over all of them hide it:
- `rollout_logprob_head_mean`, `rollout_value_head_mean`: the same mean gaps
  over the response's first `1 + (conv_kernel - 1) x conv layers` positions
  alone (9 at the cut: the first is prefill's own).
(b) The update, as there, and:
- `param_change_tied`: the embedding's entry alone, which is also the head's
  and carries both ends' gradients: the gap of its change's norm after the
  worst grant, read as `param_change` reads an entry.

The controls (`--control 1`): the reference in bfloat16, and with each of the
elements this layer adds left out or changed: the convolution's output gate,
the taps reversed (anti-causal), the prompt's convolution state dropped at
the first decoded positions (the rollout alone), the q/k norms; and, leaving
the rollout as it is, the head untied in the update (the tied matrix learns
from its input end alone). The router with its selection bias, the routed
share and the PPO arithmetic are the other sigmoid-router families' code,
which their controls cover (a control costs a compile of the reference at the
cell's size: ~100 s on the chip's host).

Loaded by path as this file's own instance of `correct/ppo_lm_mla.py` (and so
of `correct/ppo_lm.py`), whose `CONTROLS`, `UPDATE_ONLY`, `compare` and
numbers over the trained entries are the ones below.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from typing import Any, Dict

import numpy as np


def _own_instance_of(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_correct_{name}_for_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_mla = _own_instance_of("ppo_lm_mla")
_base = _mla._base
through_limits, failing, worst_entry = _mla.through_limits, _mla.failing, _mla.worst_entry
TIED = "['embed']"
# what the reference is also run as with --control 1: the elements this layer adds (the router, the routed share
# and the PPO arithmetic are the other three families' and their controls'); the last leaves the rollout as it is
CONTROLS = (("control_bfloat16", {"compute": "bfloat16"}), ("fault_conv_gate_dropped", {"fault": "conv_gate_dropped"}),
            ("fault_taps_reversed", {"fault": "taps_reversed"}),
            ("fault_conv_state_dropped", {"fault": "conv_state_dropped"}),
            ("fault_qk_norm_dropped", {"fault": "qk_norm_dropped"}), ("fault_head_untied", {"fault": "head_untied"}))

_over_trained, _mla_compare = _mla._numbers_over_trained, _mla.compare


def _numbers_over_trained(got, want, entries, max_grad_norm: float = 1.0) -> Dict[str, float]:
    """`correct/ppo_lm_mla.py`'s numbers over the trained entries, with the two counts taken over the entries the
    reference moved, and the tied matrix's change."""
    out = _over_trained(got, want, entries, max_grad_norm)
    for name, key in (("moments_unmoved", "mu_norm"), ("entries_unmoved", "dp_norm")):
        mine, theirs = np.asarray(got[key])[-1], np.asarray(want[key])[-1]
        out[name] = float(np.sum((mine <= _mla.UNMOVED * theirs) & (theirs > 0)))
    ratio = float(np.clip(_base.clip_ratio(got["losses"], want["losses"], max_grad_norm), *_base.CLIP_RATIO_RANGE))
    tied = np.asarray([name == TIED for name in entries])
    out["param_change_tied"] = worst_entry(np.asarray(got["dp_norm"])[:, tied], np.asarray(want["dp_norm"])[:, tied],
                                           rescale=ratio) if tied.any() else 0.0
    return out


def head_positions(conv_kernel: int, conv_layers: int) -> int:
    """The response positions whose convolution reads, directly or through the stack, what prefill left."""
    return 1 + (conv_kernel - 1) * conv_layers


HEAD: Dict[str, int] = {}  # `head_positions` of the run being checked, set by `check`


def compare(got, want, entries, max_grad_norm: float = 1.0) -> Dict[str, float]:
    out = _mla_compare(got, want, entries, max_grad_norm)
    n = HEAD.get("positions", 0)
    if n:
        g, w = got["rollout"], want["rollout"]
        out["rollout_logprob_head_mean"] = float(np.mean(np.abs(g["logprobs"][:, :n] - w["logprobs"][:, :n])))
        out["rollout_value_head_mean"] = float(np.mean(np.abs(g["values"][:, :n] - w["values"][:, :n])))
    return out


def check(adapter, cfg, config, ref, control: bool = False) -> Dict[str, Any]:
    h = ref.hyper(config["as_run"], config["assumed"], cfg)
    HEAD["positions"] = min(head_positions(h["conv_kernel"], sum(h["conv"])), h["response_len"])
    return _base.check(adapter, cfg, config, ref, control)


_mla._numbers_over_trained = _mla._base.update_numbers = _numbers_over_trained
_base.compare, _base.CONTROLS = compare, CONTROLS
_base.UPDATE_ONLY = (*_base.UPDATE_ONLY, "head_untied")
update_numbers = _mla.update_numbers
