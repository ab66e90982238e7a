"""Seconds of set-up in which the backend compiled a program the persistent cache did not serve (`jax.compile` with
`cache` `miss`, or `off` where the cache was not asked), as a union over threads."""

from layers._setup_record import intervals, seconds, setup_spans


def read(run):
    found = setup_spans(run, "jax.compile")
    if found is None:
        return None
    return seconds(intervals(found, lambda s: s["counters"].get("cache") != "hit"))
