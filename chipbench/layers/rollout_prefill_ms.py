"""Device self time per gradient step under `rollout.prefill`: the full-sequence forward of the
prompts that fills both kinds of cache, its head at the last position."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("rollout.prefill",))
