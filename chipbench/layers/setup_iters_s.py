"""Seconds of the main thread's `iter` spans before the window (Dreamer's prefill, the first dispatch with the
harness's readings, the warm-up bursts or blocks) that no `jax.*` span covers."""

from layers._setup_record import intervals, jax_intervals, less, main_thread, setup_spans


def read(run):
    found, jax = setup_spans(run, "iter"), jax_intervals(run)
    if found is None or jax is None:
        return None
    return less(intervals(found, main_thread), jax)
