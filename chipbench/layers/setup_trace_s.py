"""Seconds of set-up in which JAX traced or lowered a program (`jax.trace`, `jax.lower`; any thread, as a union,
so that a jit traced inside another's trace counts once). No cache serves this: it is paid on every start."""

from layers._setup_record import JAX, intervals, seconds, setup_spans


def read(run):
    found = [setup_spans(run, name) for name in JAX[:2]]
    if any(f is None for f in found):
        return None
    return seconds([iv for f in found for iv in intervals(f)])
