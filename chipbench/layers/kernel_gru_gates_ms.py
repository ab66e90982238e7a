"""Device self time per gradient step of the instructions whose innermost scope
is `kernel.gru_gates`: the fused GRU gate chain of the RSSM step, forward
(Mosaic or the lax reference, whichever tier ran) and its backward.
Counted in its region's metric too."""

from layers._program_record import kernel_ms


def read(run):
    return kernel_ms(run, "gru_gates")
