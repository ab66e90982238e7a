"""Device self time per gradient step of the gated short convolution: the instructions whose innermost scope is
`lm.conv` (the layer's norm, input and output projections) or `lm.conv_mix` (its gates and taps, which lie inside
`lm.conv`), forward and backward, in every phase of the traced block (prefill and decode too, where the rollout's
regions are outermost). `None` where the trace holds no instruction of either scope (a policy without the
convolution, or a program that does not name it) or there is no trace or table."""

from layers._program_record import attributed

SCOPES = ("lm.conv", "lm.conv_mix")


def read(run):
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    conv = [r[1] for r in rows if r[3] in SCOPES]
    return 1e3 * sum(conv) / run["trace"]["grants"] if conv else None
