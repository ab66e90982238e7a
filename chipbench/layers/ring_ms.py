"""Device self time under `ring.append` (blob unpack, ragged scatter: once a
burst) and `ring.sample` (window draw and gather: once a step), per gradient step."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("ring.append", "ring.sample"))
