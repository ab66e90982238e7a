"""Gradient steps granted to bursts in the window, all of which had run on the
device when the window closed, over the window's seconds (host clock)."""


def read(run):
    w = run["window"]
    return w["grants"] / w["seconds"]
