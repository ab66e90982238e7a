"""Device self time per gradient step of the instructions under `wm.encoder` and
`wm.decoder` (forward and backward), from the traced bursts."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("wm.encoder", "wm.decoder"))
