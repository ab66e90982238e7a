"""Seconds of set-up in which the persistent compile cache served a program (`jax.compile` with `cache` `hit`), as a
union over threads: what a warm cache costs where a cold one compiles."""

from layers._setup_record import intervals, seconds, setup_spans


def read(run):
    found = setup_spans(run, "jax.compile")
    if found is None:
        return None
    return seconds(intervals(found, lambda s: s["counters"].get("cache") == "hit"))
