#!/usr/bin/env python
"""Learning-evidence harness: run a REAL training entry point through the CLI
and record every finished-episode return the main logs.

The reference publishes trained-agent quality (``/root/reference/README.md:24-80``:
DreamerV3 Crafter 12.1, MsPacman 1542, ...). This harness is the repo's
equivalent evidence channel at sandbox-feasible scales: it spies on
``MetricAggregator.update`` / ``__contains__`` so every ``Rewards/rew_avg``
update the algorithm main emits (one per finished episode, in time order) is
captured, without requiring the exp config to declare the metric.

Usage::

    python benchmarks/learning_bench.py <tag> <threshold> <window> <override...>

    tag        label for the JSON line / artifact
    threshold  mean return over the last <window> episodes must reach this
    window     trailing-episode window for the final score
    overrides  passed verbatim to the CLI (first one usually ``exp=...``)

Prints one JSON line::

    {"tag", "episodes", "first_window_mean", "last_window_mean", "best_window_mean",
     "threshold", "passed", "elapsed_s", "returns": [...]}

Exit status 0 iff the threshold is met (so shell scripts can gate on it).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    # When run as `python benchmarks/learning_bench.py` the script dir is
    # sys.path[0]; make the package importable without an editable install.
    sys.path.insert(0, _REPO_ROOT)


def capture_returns(overrides: list[str]) -> list[float]:
    """Run the CLI with the given overrides; return finished-episode returns in order."""
    import sheeprl_tpu.utils.metric as metric_mod

    returns: list[float] = []
    orig_update = metric_mod.MetricAggregator.update
    orig_contains = metric_mod.MetricAggregator.__contains__

    def spy_update(self, name, value):
        if name == "Rewards/rew_avg":
            try:
                v = float(value)
            except Exception:
                v = float("nan")
            returns.append(v)
        if name in self.metrics:
            orig_update(self, name, value)

    def spy_contains(self, name):
        if name == "Rewards/rew_avg":
            return True
        return orig_contains(self, name)

    metric_mod.MetricAggregator.update = spy_update
    metric_mod.MetricAggregator.__contains__ = spy_contains
    try:
        from sheeprl_tpu.cli import run

        run(list(overrides))
    finally:
        metric_mod.MetricAggregator.update = orig_update
        metric_mod.MetricAggregator.__contains__ = orig_contains
    return returns


def main() -> None:
    if len(sys.argv) < 4:
        print(__doc__)
        raise SystemExit(2)
    tag = sys.argv[1]
    threshold = float(sys.argv[2])
    window = int(sys.argv[3])
    if window < 1:
        print(f"window must be >= 1, got {window}")
        raise SystemExit(2)
    overrides = sys.argv[4:]

    start = time.perf_counter()
    returns = capture_returns(overrides)
    elapsed = time.perf_counter() - start

    finite = [r for r in returns if math.isfinite(r)]
    w = min(window, max(len(finite), 1))
    first_mean = sum(finite[:w]) / w if finite else float("nan")
    last_mean = sum(finite[-w:]) / w if finite else float("nan")
    best_mean = float("nan")
    if finite:
        best_mean = max(
            sum(finite[i : i + w]) / w for i in range(0, max(len(finite) - w + 1, 1))
        )
    # The contract is "mean over the last <window> episodes" — a run that
    # finished fewer episodes than the window must not pass on a tiny sample.
    passed = len(finite) >= window and last_mean >= threshold

    print(
        json.dumps(
            {
                "tag": tag,
                "episodes": len(finite),
                "first_window_mean": round(first_mean, 2),
                "last_window_mean": round(last_mean, 2),
                "best_window_mean": round(best_mean, 2),
                "threshold": threshold,
                "passed": passed,
                "elapsed_s": round(elapsed, 1),
                "returns": [round(r, 2) for r in finite],
            }
        )
    )
    raise SystemExit(0 if passed else 1)


if __name__ == "__main__":
    main()
