"""Device self time per gradient step of the instructions whose innermost scope is
`kernel.moe_grouped_ffn` (whichever tier ran), rollout and update, forward and backward.
Counted in its region metric too."""

from layers._program_record import kernel_ms


def read(run):
    return kernel_ms(run, "moe_grouped_ffn")
