"""Device self time per gradient step under `lm.head_loss`: final norm, head and value on the
response positions, the three PPO losses, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("lm.head_loss",))
