"""graft-audit programs for the Pallas kernel tier.

Each registered kernel's PALLAS variant is audited as its own program
(``kernels.<name>``), called directly — NOT through the dispatch registry —
so the tier stays budgeted even though :func:`run_audit` pins the registry
to its default backend (which resolves to the lax references on the CPU
audit host). The lax references need no entries of their own: they are
verbatim extractions of the inline math the 23 algorithm programs already
compile and budget.

On a TPU-less audit host the kernels lower in interpret mode, so the
manifest rows record the interpret-mode CPU footprint; they still pin the
artifact against silent growth (an extra broadcast, a new f32 temp, an
accidental f64) exactly like every other program row.

Shapes mirror the real call sites at CI scale: the RSSM recurrent width for
the GRU gates, the Dreamer return head's 255-bucket support, a PPO
``(T, num_envs)`` rollout for GAE, the SAC PER tree, a Sebulba-style
burst append for the ring scatter, and the decoder policy's routed
feed-forward and windowed attention at the smallest tiles their kernels take.
"""

from __future__ import annotations

from sheeprl_tpu.analysis.programs import AuditMesh, AuditProgram, register_audit_programs
from sheeprl_tpu.ops.kernels import registry


@register_audit_programs("kernels.*")
def _audit_programs(spec: AuditMesh):
    import jax
    import jax.numpy as jnp

    def aval(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    k = {name: registry.get(name).pallas for name in registry.names()}

    cases = {
        # RSSM step tail: batch x recurrent-state width.
        "gru_gates": (
            jax.jit(k["gru_gates"]),
            (aval((256, 3 * 512)), aval((256, 512))),
        ),
        # Dreamer return head, (seq, batch) leading dims, 255 buckets.
        "two_hot_symlog_loss": (
            jax.jit(lambda logits, value: k["two_hot_symlog_loss"](logits, value)),
            (aval((16, 64, 255)), aval((16, 64, 1))),
        ),
        "two_hot_symexp_decode": (
            jax.jit(lambda logits: k["two_hot_symexp_decode"](logits)),
            (aval((16, 64, 255)),),
        ),
        # PPO rollout (T, num_envs) with the exp=ppo defaults for gamma/lambda.
        "gae": (
            jax.jit(lambda r, v, d, nv: k["gae"](r, v, d, nv, 0.99, 0.95)),
            (aval((128, 16)), aval((128, 16)), aval((128, 16)), aval((16,))),
        ),
        # SAC PER draw: 4096-leaf tree, one per_rank_batch of uniforms.
        "sumtree_sample": (
            jax.jit(k["sumtree_sample"]),
            (aval((8192,)), aval((256,)), aval((), jnp.int32), aval(())),
        ),
        # Sebulba burst append: a 32-wide vector key as the ring stores it,
        # (capacity, envs) + data.ring.ring_cell((32,)); 4-slot burst.
        "ragged_ring_scatter": (
            jax.jit(k["ragged_ring_scatter"]),
            (
                aval((64, 8, 1, 32)),
                aval((4, 8, 1, 32)),
                aval((4, 8), jnp.int32),
                aval((8,), jnp.int32),
            ),
        ),
        # the routed layer: 256 sorted assignment rows over 4 held experts at uneven loads
        "moe_grouped_ffn": (
            jax.jit(k["moe_grouped_ffn"]),
            (aval((256, 128)), aval((4, 128, 128)), aval((4, 128, 128)), aval((4, 128, 128)), aval((4,), jnp.int32)),
        ),
        # grouped-query attention, 4 query heads over 2 key-value heads, a 128-token window over 256 positions
        "window_attention": (
            jax.jit(lambda q, kk, v: k["window_attention"](q, kk, v, 128)),
            (aval((1, 256, 4, 128)), aval((1, 256, 2, 128)), aval((1, 256, 2, 128))),
        ),
    }
    for name, (fn, args) in cases.items():
        yield AuditProgram(
            name=f"kernels.{name}",
            fn=fn,
            args=args,
            source=__name__,
            check_input_shardings=False,
        )
