"""Device self time per gradient step under `lm.attn_window` in the update: the window layers
projections, RoPE, sliding-window attention and output product, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.attn_window",))
