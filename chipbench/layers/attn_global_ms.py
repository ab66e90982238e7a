"""Device self time per gradient step under `lm.attn_global` in the update: the full-attention
layers projections, their causal attention and output product, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.attn_global",))
