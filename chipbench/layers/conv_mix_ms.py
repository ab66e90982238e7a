"""Device self time per gradient step of the convolution's elementwise mix: the instructions whose innermost scope is
`lm.conv_mix` (the gate `b * v`, the taps' shifted products and sums, the output gate `c * s`), forward and backward,
in every phase of the traced block. They also count to `conv_ms`, since the scope lies inside `lm.conv`. `None` where
the trace holds no instruction of that scope (a policy without the convolution, or a program that does not name it)
or there is no trace or table."""

from layers._program_record import attributed

SCOPE = "lm.conv_mix"


def read(run):
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    mix = [r[1] for r in rows if r[3] == SCOPE]
    return 1e3 * sum(mix) / run["trace"]["grants"] if mix else None
