"""Device self time per gradient step under `lm.ffn_shared` in the update: the shared experts' gated MLP that
every token passes through beside the routed part, and the leading dense layer's feed-forward, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.ffn_shared",))
