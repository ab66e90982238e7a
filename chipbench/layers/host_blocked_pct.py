"""Share of the window the main thread spent inside `burst.submit`, blocked on
the trainer thread's queue: the host's slack before the device would wait."""

from layers._program_record import share_of_window_pct


def read(run):
    return share_of_window_pct(run, "burst.submit")
