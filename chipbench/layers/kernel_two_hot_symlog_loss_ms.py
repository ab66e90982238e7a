"""Device self time per gradient step of the instructions whose innermost scope
is `kernel.two_hot_symlog_loss`: the two-hot symlog loss of the reward and critic
heads, forward and backward.
Counted in its region's metric too."""

from layers._program_record import kernel_ms


def read(run):
    return kernel_ms(run, "two_hot_symlog_loss")
