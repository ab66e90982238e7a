"""The names inside the burst program: the compiled text of the gradient
steps of one burst, at tiny widths, carries every region of
``utils.profiler.REGIONS`` and ``kernel.<name>`` for each kernel
``registry.dispatch`` serves there, whichever tier runs it."""

import re

import jax
import jax.numpy as jnp
import pytest

import sheeprl_tpu.ops.kernels as K
from sheeprl_tpu.analysis.hlo import op_scopes
from sheeprl_tpu.utils.profiler import BURST_REGIONS, KERNEL_PREFIX, REGIONS

BURST_KERNELS = ("gru_gates", "two_hot_symlog_loss", "two_hot_symexp_decode", "ragged_ring_scatter")


def _burst_text():
    """Compiled text of the audit registry's tiny burst program (the same
    ``make_train_step(ring=...)`` the trainers dispatch)."""
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import audit_dreamer_setup, make_train_step
    from sheeprl_tpu.analysis.programs import AuditMesh
    from sheeprl_tpu.data.ring import effective_stage_buckets, make_blob_layouts, ring_cell

    s = audit_dreamer_setup(AuditMesh(devices=1))
    buckets = effective_stage_buckets((2,), 2)
    ring = {
        "capacity": s["capacity"], "n_envs": s["n_envs"], "grad_chunk": s["grad_chunk"], "seq_len": s["seq_len"],
        "batch_size": s["batch"], "ring_keys": s["ring_keys"], "stage_buckets": buckets, "stage_max": 2,
    }
    burst_fn = make_train_step(
        s["world_model"], s["actor"], s["critic"], s["cfg"], s["mesh"], s["actions_dim"], False, s["txs"], ring=ring
    )
    layouts = make_blob_layouts(s["ring_keys"], s["n_envs"], s["grad_chunk"], buckets)
    blob = jax.ShapeDtypeStruct((layouts[max(buckets)].nbytes,), jnp.uint8, sharding=s["rep"])
    rb = {
        k: jax.ShapeDtypeStruct((s["capacity"], s["n_envs"]) + ring_cell(shape), dtype, sharding=s["rep"])
        for k, (shape, dtype) in s["ring_keys"].items()
    }
    return burst_fn.lower(s["carry"], rb, blob).compile().as_text()


@pytest.mark.parametrize("backend", ["lax", "pallas"])  # pallas here is the interpreter
def test_compiled_burst_names_every_region_and_dispatched_kernel(backend):
    with K.use_backend(backend):
        for name in BURST_KERNELS:
            kernel_tier = "xla" if name in K.COMPILED_BY_XLA else "pallas-interpret"
            assert K.tier(name) == ("lax" if backend == "lax" else kernel_tier)
        text = _burst_text()
    table = op_scopes(text, regions=REGIONS, kernel_prefix=KERNEL_PREFIX)
    assert {v["outer"] for v in table.values()} == set(BURST_REGIONS) | {None}
    kernels = {v["scope"] for v in table.values() if v["scope"] and v["scope"].startswith(KERNEL_PREFIX)}
    assert kernels == {KERNEL_PREFIX + name for name in BURST_KERNELS}
    # each kernel's work lies inside the region that calls it
    homes = {(v["scope"], v["outer"]) for v in table.values() if v["scope"] in kernels}
    assert (KERNEL_PREFIX + "ragged_ring_scatter", "ring.append") in homes
    assert (KERNEL_PREFIX + "gru_gates", "wm.dynamics") in homes
    assert (KERNEL_PREFIX + "gru_gates", "behaviour.imagination") in homes
    assert (KERNEL_PREFIX + "two_hot_symlog_loss", "wm.heads") in homes
    assert (KERNEL_PREFIX + "two_hot_symexp_decode", "behaviour.returns") in homes
    # the world model and the critic are differentiated; the discrete actor's imagination is not
    backward = {v["outer"] for v in table.values() if v["backward"]}
    assert {"wm.encoder", "wm.dynamics", "wm.decoder", "wm.heads", "behaviour.heads"} <= backward
    assert not backward & {"wm.optim", "behaviour.optim", "target.ema", "ring.append", "ring.sample"}


def test_dispatch_wraps_the_chosen_tier_in_its_kernel_scope():
    x, h = jnp.ones((4, 24)), jnp.ones((4, 8))
    for backend, impl in (("lax", K.get("gru_gates").reference), ("pallas", K.get("gru_gates").pallas)):
        with K.use_backend(backend):
            fn = K.dispatch("gru_gates")
            assert fn.__wrapped__ is impl and fn.__name__ == impl.__name__
            text = jax.jit(fn).lower(x, h).compile().as_text()
        names = re.findall(r'op_name="([^"]*)"', text)
        assert any("/kernel.gru_gates/" in n for n in names), names[:5]


def test_one_way_to_name_device_work():
    """No ``named_call`` is left in ``ops/kernels/``: scopes come from
    ``registry.dispatch`` alone, and every ``pallas_call`` bears its kernel's
    registry name."""
    import importlib
    import inspect

    from sheeprl_tpu.ops.kernels import registry

    named = []
    for mod in ("attn", "gae", "gru", "moe", "scatter", "sumtree", "twohot"):
        src = inspect.getsource(importlib.import_module("sheeprl_tpu.ops.kernels." + mod))
        assert "named_call" not in src and "named_scope" not in src, mod
        named += re.findall(r'\n\s+name="([a-z_]+)",\n', src)
    # the decoder policy's two kernels call kernels that ship in jax (megablox, splash attention): no pallas_call of ours
    library = {"moe_grouped_ffn", "window_attention"}
    assert sorted(named) == sorted(set(registry.names()) - set(registry.COMPILED_BY_XLA) - library)  # no pallas_call there
    assert inspect.getsource(registry).count("with jax.named_scope(") == 1
