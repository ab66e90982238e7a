"""Device self time per gradient step of the instructions whose innermost scope
is `kernel.two_hot_symexp_decode`: the two-hot symexp decode of reward and value
predictions over the imagined trajectory.
Counted in its region's metric too."""

from layers._program_record import kernel_ms


def read(run):
    return kernel_ms(run, "two_hot_symexp_decode")
