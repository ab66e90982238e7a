"""Seconds of the program's `setup.build` spans (`cli.run_algorithm` up to the trainer, the trainer up to its first
`iter`: Fabric, agent, parameters, optimizer state, env, ring or rollout cache, block or burst builders) that no
`jax.*` span covers: the eager construction itself, less its tracing and compiling."""

from layers._setup_record import intervals, jax_intervals, less, setup_spans


def read(run):
    found, jax = setup_spans(run, "setup.build"), jax_intervals(run)
    if found is None or jax is None:
        return None
    return less(intervals(found), jax)
