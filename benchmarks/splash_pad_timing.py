"""Splash attention's forward alone at the two language-model cells' shapes,
three ways (ISSUE 37): ``T`` = 7 936 on 256-row blocks (what 7 936 = 2**8 x 31
allows), 7 936 padded at the end to 8 192 on 512-row blocks, and 8 192 on
512-row blocks. The kernel is built here from the library, as
``ops/kernels/attn.py:_splash`` builds it, so the script times the same three
programs on any tree.

    chiprun -- python benchmarks/splash_pad_timing.py

Writes ``chiprun_out/splash_pad/result.json``; needs a TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

CELLS = {  # q (B, T, Hq, D), k (B, T, Hkv, D), v (B, T, Hkv, Dv); windows run
    "kanana2": dict(B=8, Hq=32, Hkv=32, D=192, Dv=128, windows=(0,)),
    "smallthinker": dict(B=8, Hq=28, Hkv=4, D=128, Dv=128, windows=(0, 4096)),
}
VARIANTS = (("t7936_blocks256", 7936, 7936, 256), ("t7936_padded8192_blocks512", 7936, 8192, 512),
            ("t8192_blocks512", 8192, 8192, 512))


def attention(q, k, v, window, run_len, block):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    one = sm.LocalMask((run_len, run_len), (window - 1, 0), 0) if window else sm.CausalMask((run_len, run_len))
    sizes = sk.BlockSizes(block_q=block, block_kv=block, block_kv_compute=block, block_q_dkv=block, block_kv_dkv=block,
                          block_kv_dkv_compute=block, block_q_dq=block, block_kv_dq=block)
    kernel = sk.make_splash_mqa_single_device(sm.MultiHeadMask([one] * groups), block_sizes=sizes)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, run_len - T), (0, 0), (0, 0))) if run_len != T else x

    qh = pad((q * D**-0.5).astype(jnp.bfloat16)).reshape(B, run_len, Hkv, groups, D).transpose(0, 2, 3, 1, 4)
    kh = pad(k.astype(jnp.bfloat16)).transpose(0, 2, 1, 3)
    vh = pad(v.astype(jnp.bfloat16)).transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(kernel))(qh, kh, vh)[..., :T, :]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)


def main(reps: int = 10) -> None:
    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    out = {"device_kind": jax.devices()[0].device_kind, "reps": reps, "cases": []}
    for cell, s in CELLS.items():
        for window in s["windows"]:
            inputs = {}
            for name, T, run_len, block in VARIANTS:
                if T not in inputs:
                    keys = jax.random.split(jax.random.PRNGKey(T), 3)
                    inputs[T] = [jax.random.normal(kk, (s["B"], T, h, d), jnp.float32)
                                 for kk, h, d in zip(keys, (s["Hq"], s["Hkv"], s["Hkv"]), (s["D"], s["D"], s["Dv"]))]
                fn = jax.jit(lambda q, k, v, w=window, n=run_len, b=block: attention(q, k, v, w, n, b))
                t0 = time.perf_counter()
                o = fn(*inputs[T]).block_until_ready()
                compile_s = time.perf_counter() - t0
                fn(*inputs[T]).block_until_ready()
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    fn(*inputs[T]).block_until_ready()
                    times.append((time.perf_counter() - t0) * 1e3)
                case = {"cell": cell, "window": window, "variant": name, "T": T, "run_len": run_len, "block": block,
                        "ms_median": statistics.median(times), "ms_min": min(times), "ms_max": max(times),
                        "compile_s": compile_s}
                if T == 7936:  # the padded call against the 256-row one: the same numbers, up to bfloat16 rounding
                    if name == "t7936_blocks256":
                        ref = o
                    else:
                        case["max_abs_diff_vs_blocks256"] = float(jnp.max(jnp.abs(o - ref)))
                out["cases"].append(case)
                print(json.dumps(case), flush=True)
                del o
            del inputs
    os.makedirs("chiprun_out/splash_pad", exist_ok=True)
    with open("chiprun_out/splash_pad/result.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
