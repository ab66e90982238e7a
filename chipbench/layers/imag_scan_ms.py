"""Device self time per gradient step under `behaviour.imagination`: the
15-step `img_step` scan with the actor inside."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("behaviour.imagination",))
