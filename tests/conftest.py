"""Test bootstrap: force an 8-device virtual CPU mesh before JAX initializes
(the TPU-world analogue of the reference's ``LT_DEVICES`` fixture,
``tests/test_algos/test_algos.py:16-53``)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The tests run on the CPU whatever the machine holds (the reference's
# LT_DEVICES analogue needs a local many-device mesh), and share the
# repository's one persistent compile cache: the dreamer/p2e train steps
# take tens of seconds to compile, and caching them across runs keeps the
# suite usable.
from sheeprl_tpu.utils.utils import enable_compile_cache, pin_cpu_platform  # noqa: E402

pin_cpu_platform("cpu")
enable_compile_cache()

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Auto-mark compile-heavy end-to-end tests as ``slow`` so the default
    verification loop can run ``-m "not slow"`` in well under 5 minutes."""
    for item in items:
        if any(s in item.nodeid for s in ("dreamer", "p2e", "multi_iteration", "sac_ae", "droq")):
            item.add_marker(pytest.mark.slow)


def pytest_sessionfinish(session, exitstatus):
    """With the graft-sync runtime sanitizer armed (the chaos lane runs
    ``SHEEPRL_TPU_SYNC_SANITIZE=1 pytest -m chaos``), every drill doubled as
    a sanitizer run: fail the session unless the process-wide lock ledger
    validates clean — 0 order cycles, 0 inversions, 0 over-budget holds."""
    if os.environ.get("SHEEPRL_TPU_SYNC_SANITIZE", "").strip() != "1":
        return
    from sheeprl_tpu.analysis.lockstats import lockstats, validate_payload

    report = lockstats.report()
    problems, summary = validate_payload(report)
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    line = (
        "graft-sync sanitizer: {locks} lock(s), {edges} edge(s) — {cycles} cycle(s), "
        "{inversions} inversion(s), {over_budget_locks} over-budget lock(s)".format(**summary)
    )
    if tr is not None:
        tr.write_line(line)
        for p in problems:
            tr.write_line(f"graft-sync sanitizer: {p}", red=True)
    if problems:
        session.exitstatus = 1


@pytest.fixture()
def tmp_logdir(tmp_path):
    return str(tmp_path / "logs")


@pytest.fixture(autouse=True)
def _reset_metric_state():
    """Timers/aggregator flags are class-level; isolate tests. The gradient
    wire dtype is process-wide and now DEFAULTS to bf16 for any multi-device
    `Fabric.from_config` run — reset it so an e2e CLI test can't leak bf16
    reduction into a later unit test's (f32-calibrated) numerics. The
    analysis.tracecheck registry is process-wide too: drop the previous
    test's instrumented entries/events so report() stays per-test."""
    from sheeprl_tpu.analysis.tracecheck import tracecheck
    from sheeprl_tpu.parallel.comm import set_grad_reduce_dtype
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    tracecheck.reset()
    set_grad_reduce_dtype("float32", fresh_run=True)
    yield
    timer.timers.clear()
    timer.disabled = False
    MetricAggregator.disabled = False
