"""Device self time per gradient step under `wm.optim`, `behaviour.optim` and
`target.ema`: clip, Adam, `apply_updates`, the gradient `pmean`, the target EMA."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("wm.optim", "behaviour.optim", "target.ema"))
