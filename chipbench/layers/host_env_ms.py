"""Median `env.step` span of the window: the main thread's wait for the env
workers (`utils.profiler` record, `perf_counter`)."""

from layers._program_record import median_ms


def read(run):
    return median_ms(run, "env.step")
