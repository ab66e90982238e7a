"""What the per-layer readers of ISSUE 26 share: the program's own record.

Two halves, both read from `sheeprl_tpu.utils.profiler` in the run's own
process after the window has closed:

- host spans (`snapshot(t0, t1)`, on `time.perf_counter()`, the clock of
  `run["window"]` and `run["trace_info"]`);
- the scope table of the burst program the traced bursts dispatched
  (`scope_table(program)`: instruction -> named scope, from the optimized
  executable's `op_name` metadata), joined to the reduced trace's
  `ops_self_s`, whose keys are `"<instruction> <shape>"`.

A program that has no such record (the parent of the PR that added it) gives
`None` everywhere, and the metric is left out of the line.
"""

from collections import Counter
import statistics


def _profiler():
    try:
        from sheeprl_tpu.utils import profiler
    except ImportError:
        return None
    return profiler if hasattr(profiler, "snapshot") and hasattr(profiler, "scope_table") else None


def spans(name, t0, t1):
    """Finished spans named `name` that overlap `[t0, t1]`, oldest first."""
    prof = _profiler()
    if prof is None:
        return None
    return [s for s in prof.snapshot(t0, t1)["spans"] if s["name"] == name]


def window_spans(run, name):
    w = run["window"]
    return spans(name, w["t_open"], w["t_close"])


def median_ms(run, name):
    """Median length of the spans named `name` that lie wholly inside the window."""
    w = run["window"]
    found = window_spans(run, name)
    if found is None:
        return None
    inside = [s["t_end"] - s["t_start"] for s in found if s["t_start"] >= w["t_open"] and s["t_end"] <= w["t_close"]]
    if len(inside) < 10:
        return None
    return 1e3 * statistics.median(inside)


def share_of_window_pct(run, name):
    """Share of the window covered by spans named `name`, each cut to the window."""
    w = run["window"]
    found = window_spans(run, name)
    if not found or w["seconds"] <= 0:
        return None
    covered = sum(min(s["t_end"], w["t_close"]) - max(s["t_start"], w["t_open"]) for s in found)
    return 100.0 * covered / w["seconds"]


def mean_counter(run, name, counter):
    found = window_spans(run, name)
    values = [s["counters"][counter] for s in found or [] if counter in s["counters"]]
    return sum(values) / len(values) if values else None


def attributed(run):
    """`[(instruction, seconds, outer region, innermost scope, backward)]` for
    every entry of the first device's `ops_self_s` and `custom_calls` (an
    instruction the scope table does not know, or that has no `op_name`, has
    `None` for both), or `None` where there is no trace, no record or no
    table. Kept on `run`, so that the table is parsed once for all readers."""
    if "_attributed" in run:
        return run["_attributed"]
    run["_attributed"] = rows = _attribute(run)
    return rows


def _attribute(run):
    trace, info, prof = run.get("trace"), run.get("trace_info") or {}, _profiler()
    if not trace or prof is None or "t_start" not in info:
        return None
    want = run["traffic"].get("burst_program", "")
    dispatched = Counter(
        s["counters"].get("program")
        for s in spans("burst.dispatch", info["t_start"], info.get("t_stop", float("inf"))) or []
    )
    program = next((p for p, _n in dispatched.most_common() if p and want in p), None)
    table = prof.scope_table(program) if program else None
    if not table:
        return None
    device = trace["devices"][0]
    times = dict(device["ops_self_s"])  # the 300 longest instructions
    for label, call in device.get("custom_calls", {}).items():  # and every custom call, however short
        times.setdefault(label, call["seconds"])
    rows = []
    for label, seconds in times.items():
        instruction = label.split(" ", 1)[0]
        entry = table.get(instruction) or {}
        rows.append((instruction, seconds, entry.get("outer"), entry.get("scope"), bool(entry.get("backward"))))
    return rows if any(r[2] for r in rows) else None


def region_ms(run, regions, backward=None):
    """Device self time per gradient step of the instructions whose outermost
    region is one of `regions` (`backward`: only the `transpose(...)` part, or
    only the rest)."""
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    total = sum(r[1] for r in rows if r[2] in regions and (backward is None or r[4] == backward))
    return 1e3 * total / run["trace"]["grants"]


def kernel_ms(run, kernel):
    """The same for instructions whose innermost scope is `kernel.<kernel>`;
    `None` where the trace has no event of it."""
    rows = attributed(run)
    if rows is None or not run["trace"]["grants"]:
        return None
    hits = [r[1] for r in rows if r[3] == "kernel." + kernel]
    return 1e3 * sum(hits) / run["trace"]["grants"] if hits else None


def unscoped_pct(run):
    """100 x (1 - attributed self time / busy time): instructions with no
    `op_name`, those outside every region, those beyond the ones the trace
    reduction keeps, and other programs."""
    rows = attributed(run)
    if rows is None or run["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - sum(r[1] for r in rows if r[2]) / run["trace"]["busy_s"])
