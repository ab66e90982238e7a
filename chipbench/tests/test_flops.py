"""The FLOP function against XLA's own count (ISSUE 25: 673 GFLOP for one
gradient step at size S, 5.97 TFLOP at XL, from a chipless AOT compile of
`make_train_step`). XLA counts the body of each scan once, so the function's
`flops_as_xla_counts` is what has to come near; tolerance 8%."""

import json
import os

from conftest import ROOT
from run import load_module

XLA_COUNT = {"dreamer_v3_S": 673e9, "dreamer_v3_XL": 5.97e12}
TOLERANCE = 0.08


def test_flops_agree_with_xla_count(bench):
    for c in bench["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        flops = load_module("flops", conf["family"])
        if c["name"] in XLA_COUNT:
            got = flops.flops_as_xla_counts(conf)
            assert abs(got - XLA_COUNT[c["name"]]) / XLA_COUNT[c["name"]] < TOLERANCE, (c["name"], got)
        parts = flops.parts(conf)
        assert all(v > 0 for v in parts.values())
        assert flops.flops_per_grad_step(conf) > flops.flops_as_xla_counts(conf)
