"""Median `player.act` span of the window: the host policy's forward pass and
the pull of its actions."""

from layers._program_record import median_ms


def read(run):
    return median_ms(run, "player.act")
