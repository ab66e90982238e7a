"""Device self time per gradient step under `ppo.optim`: the gradient `pmean`, its global norm,
the clip, Adam, `apply_updates`, the divergence guard select."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("ppo.optim",))
