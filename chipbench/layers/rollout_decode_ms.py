"""Device self time per gradient step under `rollout.decode`: the scan of decode steps (sampling,
the token env, one token per sequence through the two-kind cache, the head)."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("rollout.decode",))
