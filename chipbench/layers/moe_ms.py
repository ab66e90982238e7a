"""Device self time per gradient step under `lm.moe` in the update: router, sort by expert,
gather, grouped products, combine, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.moe",))
