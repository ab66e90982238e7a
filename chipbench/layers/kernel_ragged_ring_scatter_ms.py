"""Device self time per gradient step of the instructions whose innermost scope
is `kernel.ragged_ring_scatter`: the ragged scatter of a flush's staged rows into
the device ring (once a burst).
Counted in its region's metric too."""

from layers._program_record import kernel_ms


def read(run):
    return kernel_ms(run, "ragged_ring_scatter")
