"""The `transpose(jvp(wm.dynamics))` part of `dyn_scan_ms`: the backward scan
with its weight-gradient accumulation."""

from layers._program_record import region_ms


def read(run):
    return region_ms(run, ("wm.dynamics",), backward=True)
