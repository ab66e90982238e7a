"""Unit tests for the config/env-driven jax.distributed bring-up
(``fabric.distributed.*`` + the ``SHEEPRL_*`` env vars) wired through BOTH
CLI entrypoints. ``jax.distributed.initialize`` is monkeypatched — the REAL
2-process bring-up is covered by ``test_multiprocess.py``."""

import pytest

import sheeprl_tpu.parallel.distributed as dist


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Each test starts un-initialized with a recording initialize stub."""
    calls = []

    def fake_initialize(coordinator_address=None, num_processes=None, process_id=None):
        calls.append(
            {"coordinator_address": coordinator_address, "num_processes": num_processes, "process_id": process_id}
        )

    monkeypatch.setattr(dist.jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.delenv("SHEEPRL_COORDINATOR", raising=False)
    monkeypatch.delenv("SHEEPRL_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("SHEEPRL_PROCESS_ID", raising=False)
    yield calls


def test_single_host_default_is_a_noop(_fresh):
    assert dist.maybe_init() is False
    assert dist.maybe_init({"enabled": None}) is False
    assert _fresh == []


def test_config_block_drives_init(_fresh):
    cfg = {"enabled": None, "coordinator": "10.0.0.1:1234", "num_processes": 4, "process_id": 2}
    assert dist.maybe_init(cfg) is True
    assert _fresh == [
        {"coordinator_address": "10.0.0.1:1234", "num_processes": 4, "process_id": 2}
    ]


def test_env_vars_win_over_config(_fresh, monkeypatch):
    """The pod runtime sets per-host env vars over one shared config file:
    env must win."""
    monkeypatch.setenv("SHEEPRL_COORDINATOR", "10.0.0.9:4321")
    monkeypatch.setenv("SHEEPRL_NUM_PROCESSES", "8")
    monkeypatch.setenv("SHEEPRL_PROCESS_ID", "5")
    cfg = {"coordinator": "10.0.0.1:1234", "num_processes": 4, "process_id": 2}
    assert dist.maybe_init(cfg) is True
    assert _fresh == [
        {"coordinator_address": "10.0.0.9:4321", "num_processes": 8, "process_id": 5}
    ]


def test_env_vars_alone_drive_init(_fresh, monkeypatch):
    monkeypatch.setenv("SHEEPRL_COORDINATOR", "127.0.0.1:9999")
    monkeypatch.setenv("SHEEPRL_NUM_PROCESSES", "2")
    monkeypatch.setenv("SHEEPRL_PROCESS_ID", "0")
    assert dist.maybe_init() is True
    assert _fresh[0]["coordinator_address"] == "127.0.0.1:9999"


def test_enabled_false_never_inits(_fresh, monkeypatch):
    """An operator can pin a host single-process even in a pod env."""
    monkeypatch.setenv("SHEEPRL_COORDINATOR", "127.0.0.1:9999")
    assert dist.maybe_init({"enabled": False}) is False
    assert _fresh == []


def test_enabled_true_without_coordinator_is_typed(_fresh):
    """Silently training solo on N-1 hosts is the failure mode; require the
    coordinator loudly."""
    with pytest.raises(ValueError, match="fabric.distributed.enabled=true"):
        dist.maybe_init({"enabled": True})
    assert _fresh == []


def test_second_call_is_a_noop(_fresh):
    cfg = {"coordinator": "10.0.0.1:1234", "num_processes": 2}
    assert dist.maybe_init(cfg) is True
    assert dist.maybe_init(cfg) is False
    assert len(_fresh) == 1


def test_cli_entrypoints_pass_the_config_block():
    """Both CLI bodies hand fabric.distributed to maybe_init (train via
    run_algorithm's set-up, ``_build_run``; serve via serve_algorithm) —
    source-level wiring check that survives refactors of either function."""
    import inspect

    from sheeprl_tpu import cli

    assert "_build_run" in inspect.getsource(cli.run_algorithm)
    for fn in (cli._build_run, cli.serve_algorithm):
        src = inspect.getsource(fn)
        assert "maybe_init" in src and "distributed" in src, fn.__name__
