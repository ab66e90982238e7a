"""Device self time per gradient step under `wm.dynamics`: the 64-step
`dynamic_rollout` scan, forward and backward."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("wm.dynamics",))
