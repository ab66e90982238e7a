"""Device self time per gradient step of the attention's output gate: the instructions whose innermost scope is
`lm.attn_gate` (the gate's projection, its sigmoid and the product with the attention's output), forward and
backward, in every phase of the traced block; they also count to `lm.attn_window` / `lm.attn_global`, inside which
the scope lies. `None` where the trace holds no instruction of that scope (a policy without the gate, or a program
that does not name it) or there is no trace or table."""

from layers._program_record import attributed

SCOPE = "lm.attn_gate"


def read(run):
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    gate = [r[1] for r in rows if r[3] == SCOPE]
    return 1e3 * sum(gate) / run["trace"]["grants"] if gate else None
