#!/usr/bin/env python
"""Template for a decoupled player/trainer algorithm on a TPU mesh.

The reference ships a torch-collectives multi-process skeleton
(``examples/architecture_template.py``: buffer/player/trainer processes
wired with TorchCollective broadcasts/gathers). The TPU-native architecture
this framework uses is different and simpler, and this runnable template
demonstrates it end to end on a toy problem:

- ONE process per host; the device mesh (``parallel.Fabric``) carries data
  parallelism inside XLA (``shard_map`` + ``psum``/``pmean``), not via
  explicit gather/broadcast calls;
- the ENV-SIDE policy runs on the host CPU from a packed parameter snapshot
  (``utils.burst.HostSnapshot``) — no per-step device round-trip;
- training dispatches on a trainer thread (``utils.burst.TrainerThread``)
  with a bounded queue as backpressure, so the env loop never blocks on the
  accelerator. Checkpoint-grade handles are always readable from
  ``trainer.carry`` (at most one dispatch stale).

This is exactly the topology of ``sac.py``'s hybrid path and the Dreamer
``HybridPlayerHarness`` — stripped to ~100 lines you can grow a new
algorithm from. Run it anywhere (CPU included):

    python examples/architecture_template.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
from jax import shard_map


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")  # template runs anywhere

    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.parallel import Fabric
    from sheeprl_tpu.utils.burst import HostSnapshot, TrainerThread

    # -- 1. mesh + model ------------------------------------------------------
    fabric = Fabric(devices=1, mesh_axes=("dp",))

    def net(params, x):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    key = jax.random.PRNGKey(0)
    params = {
        "w1": jax.random.normal(key, (4, 32)) * 0.3,
        "b1": jnp.zeros(32),
        "w2": jax.random.normal(key, (32, 1)) * 0.3,
        "b2": jnp.zeros(1),
    }
    tx = optax.adam(3e-3)
    opt = tx.init(params)
    params, opt = fabric.put_replicated(params), fabric.put_replicated(opt)

    # -- 2. the jitted train step: shard_map over the mesh, pmean gradients --
    def _step(params, opt, batch_x, batch_y):
        def loss_fn(p):
            return jnp.mean((net(p, batch_x) - batch_y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, "dp")
        loss = jax.lax.pmean(loss, "dp")
        updates, opt = tx.update(grads, opt)
        return optax.apply_updates(params, updates), opt, loss

    train_step = jax.jit(
        shard_map(
            _step,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P("dp"), P("dp")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    # -- 3. host-side "player" from a packed snapshot -------------------------
    snapshot = HostSnapshot(lambda p: p, params, wire_dtype=jnp.float32)
    host_params = snapshot.pull(params)
    host_policy = jax.jit(net)  # runs on the snapshot, on the host device

    # -- 4. trainer thread: jobs in, newest handles out -----------------------
    GRAD_CHUNK = 16  # gradient steps per burst (the Ratio grant analogue)

    def trainer_step(carry, batch):
        params, opt = carry
        for _ in range(GRAD_CHUNK):
            params, opt, loss = train_step(params, opt, *batch)
        return (params, opt), loss

    trainer = TrainerThread(
        trainer_step,
        (params, opt),
        on_step=lambda carry, _loss: snapshot.refresh(carry[0]),
    )

    # -- 5. the env loop: act on the host, stage data, submit bursts ---------
    rng = np.random.default_rng(0)
    target = lambda x: np.sin(x.sum(-1, keepdims=True))
    staged_x, staged_y = [], []
    for it in range(1, 201):
        fresh = snapshot.poll()
        if fresh is not None:
            host_params = fresh  # adopt the newest trainer weights

        x = rng.normal(size=(8, 4)).astype(np.float32)
        _action = np.asarray(host_policy(host_params, x))  # the "policy"
        staged_x.append(x)
        staged_y.append(target(x).astype(np.float32))

        if len(staged_x) == 8:  # one burst every 8 iterations
            batch = (jnp.concatenate(staged_x), jnp.concatenate(staged_y))
            staged_x, staged_y = [], []
            trainer.submit(batch)
            if it % 40 == 0 and trainer.metrics is not None:
                print(f"iter {it:4d}  loss={float(trainer.metrics):.4f}")

    (params, opt) = trainer.close()
    x = jnp.asarray(rng.normal(size=(256, 4)), dtype=jnp.float32)
    final = float(jnp.mean((net(params, x) - jnp.asarray(target(np.asarray(x)))) ** 2))
    print(f"final eval MSE: {final:.4f}")
    assert final < 0.5, "the toy problem should have converged"


if __name__ == "__main__":
    main()
