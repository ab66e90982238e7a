"""What the five `setup_*` readers of ISSUE 38 share: the program's spans of set-up, those that start before the
window opens (`layers._program_record.spans(name, None, t_open)`), cut to `[t_open - setup_s, t_open]`.

- `jax.trace`, `jax.lower`, `jax.compile` (`cache`: `hit`, `miss`, `off`): JAX's compile pipeline, entered by the
  program's `jax.monitoring` listener (`utils/utils.py:CompileStats`), any thread;
- `setup.build`: `cli.run_algorithm` up to the trainer's entry, and the trainer up to its first `iter`;
- `iter`: the main thread's iterations before the window (prefill, the first dispatch with the harness's readings,
  the warm-up).

`None` where the program records no `setup.build` (the parent of the PR that added these spans), and where the ring
has dropped any span recorded since set-up began: a full ring whose oldest span starts after set-up began. Never a
short sum.
"""

import threading

from layers._program_record import _profiler, spans

JAX = ("jax.trace", "jax.lower", "jax.compile")


def setup_spans(run, name):
    """Spans named `name` that start before the window opens, each cut to set-up, or `None` (see above)."""
    if "_setup_complete" not in run:
        run["_setup_complete"] = _complete(run)
    if not run["_setup_complete"]:
        return None
    cache = run.setdefault("_setup_spans", {})
    if name not in cache:
        w = run["window"]
        t0, t1 = w["t_open"] - w["setup_s"], w["t_open"]
        found = spans(name, None, t1) or []
        cache[name] = [dict(s, t_start=max(s["t_start"], t0), t_end=min(s["t_end"], t1)) for s in found if s["t_end"] > t0]
    return cache[name]


def _complete(run):
    prof = _profiler()
    if prof is None or not spans("setup.build", None, run["window"]["t_open"]):
        return False
    snap = prof.snapshot()
    c, held = snap["counters"], snap["spans"]
    t_start = run["window"]["t_open"] - run["window"]["setup_s"]
    dropped = c["held"] >= c["capacity"] and c["recorded"] > c["held"] and min(s["t_start"] for s in held) > t_start
    return not dropped


def intervals(found, keep=lambda s: True):
    return [(s["t_start"], s["t_end"]) for s in found if keep(s)]


def union(ivs):
    """Disjoint, sorted intervals covering `ivs`."""
    out = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def seconds(ivs):
    return sum(b - a for a, b in union(ivs))


def less(ivs, cover):
    """Seconds of the union of `ivs` that no interval of `cover` covers."""
    a, b = union(ivs), union(cover)
    covered, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            covered += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return seconds(ivs) - covered


def jax_intervals(run):
    """Every `jax.*` interval of set-up, or `None`."""
    found = [setup_spans(run, name) for name in JAX]
    return None if any(f is None for f in found) else [iv for f in found for iv in intervals(f)]


def main_thread(s):
    return s["thread"] == threading.main_thread().ident
