"""Device self time per gradient step under `lm.embed` in the update: the gather of the
sequence's embedding rows and, backward, the scatter-add of their gradients into the table."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.embed",))
