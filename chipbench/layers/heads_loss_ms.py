"""Device self time per gradient step under `wm.heads`, `behaviour.returns` and
`behaviour.heads`: reward, continue, actor and critic heads with their losses."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("wm.heads", "behaviour.returns", "behaviour.heads"))
