"""What decides `correct` for the token-level PPO cell of the gated
grouped-query policy (`afmoe`): `correct/ppo_lm_mla.py`'s procedure and
numbers (the first iteration of the timed block dispatched with 1, 2 and 3
gradient steps granted, against the plain reference from its own weights; the
rollout compared as means, the update's numbers over the trained entries, the
routed experts' leaves taken together and their entries that never moved
counted, the router's selection bias held apart and to exactly 0; that file's
docstring and `correct/ppo_lm.py`'s have every definition), with this family's
reference and its own controls.

(a) The rollout is decode through the **window ring and the full buffer**
(keys cached normed and, on window layers, rotated); the reference recomputes
the sampled tokens' log-probabilities and the values by its full forward over
all positions (`rollout_logprob_mean`, `rollout_value_mean`).
(b) The update, as there, but for the two counts of entries that never
moved (`moments_unmoved`, `entries_unmoved`): an entry counts where the
reference moved it and the program did not (under a thousandth of the
reference's after the third grant). An expert that no token reaches in the
three granted steps moves on neither side and is no fault: here 8 of 128
experts a token with 16 held leave one such expert now and then in the last
routed layer (three entries on one seed of four, PERF.md section 2), which
`correct/ppo_lm_mla.py`'s count, `got <= 1e-3 x want` with both at 0, reads
as never moved.

The controls (`--control 1`): the reference in bfloat16, and with each of the
layer's elements left out or changed: the output gate, the q/k norms, the
sandwich norms, the embedding's scale, rotary encoding put on the full layer,
the selection bias, the shared expert; and, leaving the rollout as it is, one
expert skipped in the update and half a batch.

Loaded by path as this file's own instance of `correct/ppo_lm_mla.py` (and so
of `correct/ppo_lm.py`), whose `CONTROLS` are the ones below and whose
numbers over the trained entries are `_numbers_over_trained` below.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np


def _own_instance_of(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_correct_{name}_for_afmoe", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_mla = _own_instance_of("ppo_lm_mla")
through_limits, failing, update_numbers, compare = _mla.through_limits, _mla.failing, _mla.update_numbers, _mla.compare
# what the reference is also run as with --control 1; the last two leave the rollout as it is
CONTROLS = (("control_bfloat16", {"compute": "bfloat16"}), ("fault_gate_dropped", {"fault": "gate_dropped"}),
            ("fault_qk_norm_dropped", {"fault": "qk_norm_dropped"}),
            ("fault_post_norms_dropped", {"fault": "post_norms_dropped"}),
            ("fault_embed_scale_dropped", {"fault": "embed_scale_dropped"}),
            ("fault_rope_on_full", {"fault": "rope_on_full"}), ("fault_bias_dropped", {"fault": "bias_dropped"}),
            ("fault_shared_skipped", {"fault": "shared_skipped"}),
            ("fault_expert_skipped_in_update", {"fault": "expert_skipped_in_update"}),
            ("fault_half_batch", {"fault": "half_batch"}))

_over_trained = _mla._numbers_over_trained


def _numbers_over_trained(got, want, entries, max_grad_norm: float = 1.0):
    """`correct/ppo_lm_mla.py`'s numbers over the trained entries, with the two counts taken over the entries the
    reference moved."""
    out = _over_trained(got, want, entries, max_grad_norm)
    for name, key in (("moments_unmoved", "mu_norm"), ("entries_unmoved", "dp_norm")):
        mine, theirs = np.asarray(got[key])[-1], np.asarray(want[key])[-1]
        out[name] = float(np.sum((mine <= _mla.UNMOVED * theirs) & (theirs > 0)))
    return out


_mla._numbers_over_trained = _mla._base.update_numbers = _numbers_over_trained
_mla._base.CONTROLS = CONTROLS
check = _mla.check
