"""Paired microbenchmarks for the Pallas kernel tier (howto/kernels.md).

Every registered kernel is timed through its PUBLIC dispatch wrapper at 2-3
realistic call-site shapes, once per backend, on identical inputs:

- ``lax`` — the plain-lax reference, i.e. exactly the inline graph every
  call site ran before the kernel tier existed;
- ``pallas`` — the ``custom_vjp``-wrapped Pallas kernel (compiled on TPU,
  interpret mode everywhere else).

Knobs:

- ``BENCH_KERNEL``           one kernel name, or ``all`` (default);
- ``BENCH_KERNEL_BACKEND``   ``pallas`` | ``lax`` | ``both`` (default);
- ``BENCH_KERNEL_REPS``      timed calls per case (default 30);
- ``BENCH_KERNEL_OUT``       also write the full JSON payload to this path.

CAVEAT — read before comparing columns: on a host without a TPU the Pallas
column measures INTERPRET MODE, a correctness/lowering vehicle with no
performance claim whatsoever — it is expected to LOSE to the fused XLA:CPU
reference, often by orders of magnitude. The paired CPU numbers exist to (a)
pin the reference cost of each call site and (b) catch interpret-mode
pathologies; the pallas-vs-lax verdict only means anything on a real TPU.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Tuple


def _cases() -> Dict[str, List[Tuple[str, Any]]]:
    """kernel name -> [(case label, thunk building (fn, args))]. Shapes
    mirror the real call sites: RSSM widths for the GRU gates, the Dreamer
    255-bucket return head, PPO ``(T, num_envs)`` rollouts, the SAC PER
    tree, Sebulba burst/sequence ring appends."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.ops import kernels as K

    key = jax.random.PRNGKey(0)

    def gru(batch, width):
        fused = jax.random.normal(key, (batch, 3 * width), jnp.float32)
        h = jax.random.normal(key, (batch, width), jnp.float32)
        return lambda backend: (lambda: K.gru_gates(fused, h, backend=backend))

    def loss(rows, buckets=255):
        logits = jax.nn.log_softmax(jax.random.normal(key, (rows, buckets), jnp.float32))
        value = jax.random.normal(key, (rows, 1), jnp.float32) * 5.0
        return lambda backend: (
            lambda: K.two_hot_symlog_loss(logits, value, backend=backend)
        )

    def decode(rows, buckets=255):
        logits = jax.random.normal(key, (rows, buckets), jnp.float32)
        return lambda backend: (lambda: K.two_hot_symexp_decode(logits, backend=backend))

    def gae(horizon, envs):
        r = jax.random.normal(key, (horizon, envs), jnp.float32)
        v = jax.random.normal(key, (horizon, envs), jnp.float32)
        d = (jax.random.uniform(key, (horizon, envs)) < 0.05).astype(jnp.float32)
        nv = jax.random.normal(key, (envs,), jnp.float32)
        return lambda backend: (lambda: K.gae(r, v, d, nv, 0.99, 0.95, backend=backend))

    def sumtree(leaves, batch):
        from sheeprl_tpu.replay import sumtree as st

        tree = st.init(leaves)
        pri = jax.random.uniform(key, (leaves,), jnp.float32) + 0.1
        tree = st.update(tree, jnp.arange(leaves), pri)
        u = jax.random.uniform(key, (batch,), jnp.float32)
        n_valid = jnp.asarray(leaves, jnp.int32)
        beta = jnp.float32(0.4)
        return lambda backend: (
            lambda: K.sumtree_sample(tree, u, n_valid, beta, backend=backend)
        )

    def scatter(capacity, envs, feat, slots):
        # a feat-wide vector key as the ring stores it: (capacity, envs) + data.ring.ring_cell((feat,))
        storage = jnp.zeros((capacity, envs, 1, feat), jnp.float32)
        staged = jax.random.normal(key, (slots, envs, 1, feat), jnp.float32)
        pos = jnp.arange(envs, dtype=jnp.int32) % capacity
        row = (pos[None, :] + jnp.arange(slots, dtype=jnp.int32)[:, None]) % capacity
        return lambda backend: (
            lambda: K.ragged_ring_scatter(storage, staged, row, pos, backend=backend)
        )

    return {
        "gru_gates": [
            ("b256_h512", gru(256, 512)),
            ("b1024_h512", gru(1024, 512)),
            ("b64_h1024", gru(64, 1024)),
        ],
        "two_hot_symlog_loss": [
            ("rows1024_k255", loss(1024)),
            ("rows4096_k255", loss(4096)),
        ],
        "two_hot_symexp_decode": [
            ("rows1024_k255", decode(1024)),
            ("rows4096_k255", decode(4096)),
        ],
        "gae": [
            ("t128_n16", gae(128, 16)),
            ("t128_n64", gae(128, 64)),
            ("t512_n16", gae(512, 16)),
        ],
        "sumtree_sample": [
            ("leaves4096_b256", sumtree(4096, 256)),
            ("leaves16384_b1024", sumtree(16384, 1024)),
        ],
        "ragged_ring_scatter": [
            ("c64_e8_f32_s4", scatter(64, 8, 32, 4)),
            ("c128_e16_f64_s8", scatter(128, 16, 64, 8)),
        ],
    }


def _time_case(thunk, reps: int) -> Dict[str, float]:
    import jax

    fn = jax.jit(lambda: thunk())
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return {
        "median_ms": round(samples[len(samples) // 2] * 1e3, 4),
        "best_ms": round(samples[0] * 1e3, 4),
        "compile_s": round(compile_s, 3),
    }


def main() -> None:
    import jax

    from sheeprl_tpu.utils.utils import enable_compile_cache

    enable_compile_cache()

    which = os.environ.get("BENCH_KERNEL", "all").strip().lower()
    backend_sel = os.environ.get("BENCH_KERNEL_BACKEND", "both").strip().lower()
    reps = int(os.environ.get("BENCH_KERNEL_REPS", 30))
    if backend_sel not in ("pallas", "lax", "both"):
        raise SystemExit(
            f"Unknown BENCH_KERNEL_BACKEND '{backend_sel}' (expected 'pallas', 'lax' or 'both')"
        )
    backends = ("pallas", "lax") if backend_sel == "both" else (backend_sel,)

    cases = _cases()
    if which != "all":
        if which not in cases:
            raise SystemExit(f"Unknown BENCH_KERNEL '{which}' (expected one of {sorted(cases)} or 'all')")
        cases = {which: cases[which]}

    on_tpu = jax.default_backend() == "tpu"
    results: Dict[str, Any] = {}
    ratios: List[float] = []
    for name, kernel_cases in cases.items():
        rows = {}
        for label, build in kernel_cases:
            row: Dict[str, Any] = {}
            for backend in backends:
                row[backend] = _time_case(build(backend), reps)
            if "pallas" in row and "lax" in row and row["pallas"]["median_ms"] > 0:
                row["lax_over_pallas"] = round(
                    row["lax"]["median_ms"] / row["pallas"]["median_ms"], 3
                )
                ratios.append(row["lax_over_pallas"])
            rows[label] = row
        results[name] = rows

    ratios.sort()
    payload = {
        "metric": "kernel_tier_lax_over_pallas_median",
        # headline: median over cases of lax_ms / pallas_ms — > 1 means the
        # Pallas tier wins; meaningful ONLY on a real TPU (see note)
        "value": ratios[len(ratios) // 2] if ratios else None,
        "unit": "x (lax median ms / pallas median ms)",
        "backend_mode": backend_sel,
        "jax_backend": jax.default_backend(),
        "pallas_execution": "compiled" if on_tpu else "interpret",
        "reps": reps,
        "kernels": results,
        "note": (
            "pallas column is compiled Mosaic on TPU but INTERPRET MODE on cpu/gpu hosts — "
            "interpret mode carries no performance claim and is expected to lose to the fused "
            "XLA reference there; on CPU read the lax column as the call-site cost baseline "
            "and treat the ratio as TPU-only signal"
        ),
    }
    out_path = os.environ.get("BENCH_KERNEL_OUT")
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
