"""Start-up and device-selection rules (PR 21), all chipless: where the compile
cache goes, what ``fabric.accelerator=tpu`` means without a TPU, who may start
JAX children, the host CPU device, and the launch lines ``chip_smoke.py``
reads."""

import os

import jax
import pytest

import sheeprl_tpu
from sheeprl_tpu.parallel.fabric import AcceleratorUnavailableError, Fabric
from sheeprl_tpu.utils import utils


@pytest.fixture()
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def _key_rules():
    """The cache key covers op_name metadata (utils.profiler's scope tables read it
    from cached executables), with source paths relative to the checkout."""
    import re

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__)))
    return [
        ("jax_compilation_cache_include_metadata_in_key", True),
        ("jax_hlo_source_file_canonicalization_regex", re.escape(checkout + os.sep)),
    ]


def test_cache_dir_from_outside_is_left_alone(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    utils.enable_compile_cache()
    # JAX read the variable itself; no directory is set in code (only the key's rules)
    assert config_updates == _key_rules()


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    utils.enable_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__)))
    assert config_updates == [("jax_compilation_cache_dir", os.path.join(checkout, ".xla_cache")), *_key_rules()]


def test_accelerator_tpu_without_a_tpu_raises_by_name():
    with pytest.raises(AcceleratorUnavailableError, match="fabric.accelerator=tpu"):
        Fabric(devices=1, accelerator="tpu")
    with pytest.raises(AcceleratorUnavailableError):
        Fabric.from_config({"devices": 1, "accelerator": "tpu"})
    assert Fabric(devices=1, accelerator="auto").device.platform == "cpu"  # auto stays JAX's choice
    with pytest.raises(ValueError, match="fabric.accelerator"):
        Fabric(devices=1, accelerator="gpu")


def test_launchers_refuse_jax_children_on_a_tpu(monkeypatch):
    utils.refuse_children_on_tpu("run --pod", "N workers")  # CPU: children are fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(utils.OneProcessPerChipError, match="serve --fleet.*one process at a time"):
        utils.refuse_children_on_tpu("serve --fleet", "N replica processes")


def test_missing_cpu_platform_is_named(monkeypatch):
    assert utils.host_cpu_device().platform == "cpu"

    def no_cpu(backend=None):
        raise RuntimeError("Unknown backend cpu")

    monkeypatch.setattr(jax, "local_devices", no_cpu)
    with pytest.raises(utils.HostCpuUnavailableError, match="admits no CPU platform"):
        utils.host_cpu_device()


def test_launch_lines_are_what_chip_smoke_reads(capsys):
    import chip_smoke

    fabric = Fabric(devices=2, accelerator="cpu")
    cfg = {"algo": {"hybrid_player": {"enabled": "auto"}}}
    fabric.launch(lambda fabric, cfg: None, cfg)
    out = capsys.readouterr().out
    # check_common demands a TPU; everything before that demand must parse
    with pytest.raises(chip_smoke.SmokeFailure, match="ran on platform 'cpu'"):
        chip_smoke.check_common("test", out, 2)
    info = chip_smoke.check_common("test", out.replace("platform=cpu", "platform=tpu"), 2)
    assert info["mesh"] == "dp=2" and info["hybrid_player"] == "off"
    assert "gru_gates=lax" in info["kernels"] and info["compile_programs"] >= 0
