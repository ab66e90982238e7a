"""Causal attention over grouped or ungrouped key-value heads with an optional
sliding window, never as a ``(T, T)`` array.

``window_attention(q, k, v, window)``: ``q`` is ``(B, T, Hq, D)``, ``k``
``(B, T, Hkv, D)`` and ``v`` ``(B, T, Hkv, Dv)`` with ``Hq`` a multiple of
``Hkv`` (query head ``h`` reads key-value head ``h // (Hq // Hkv)``; one each
where they are as many) and ``Dv`` the values' own head size, which is the
output's (``(B, T, Hq, Dv)``; latent attention's expanded form has ``D`` 192
and ``Dv`` 128). Scores are scaled by ``D ** -0.5``; query ``i`` sees keys
``j`` with ``j <= i`` and, where ``window`` is a number, ``i - window < j``
(the window counts the query's own position). ``window`` is static: ``0``
means none.

- reference tier: a scan over query blocks and, inside it, over the key blocks
  the mask can reach, on :func:`sheeprl_tpu.ops.attention.block_attention` and
  :func:`~sheeprl_tpu.ops.attention.online_softmax_merge`, differentiated as
  it stands; each query block is under ``jax.checkpoint``, so the backward
  pass holds one query block's scores at a time and never the whole mask's;
- kernel tier: ``splash_attention`` (ships in jax) with a ``CausalMask`` or a
  ``LocalMask``, one multi-query kernel per key-value head, on bfloat16
  operands; a sequence longer than one ``SPLASH_BLOCK`` that no
  ``SPLASH_BLOCK`` divides is padded at its end to the next multiple
  (:func:`splash_len`) and run on full blocks, and the padded rows are
  dropped: no real query reaches a key after it, so the output and every
  gradient are those of the sequence as it came (PERF.md finding 37). The
  kernel's forward names its output and log-sum-exp ``RESIDUALS``
  (``jax.ad_checkpoint.checkpoint_name``), so that a ``jax.checkpoint``
  around it whose policy saves that name keeps them and its backward never
  runs the forward again; the reference tier names nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.attention import block_attention, online_softmax_merge
from sheeprl_tpu.ops.kernels import registry

__all__ = ["RESIDUALS", "splash_len", "window_attention", "window_attention_reference",
           "window_attention_pallas"]

BLOCK = 512  # rows of a query block and of a key block, at most, in the lax tier
SPLASH_BLOCK = 512  # and in the kernel tier, which wants multiples of 128
RESIDUALS = "window_attention.residuals"  # the kernel tier's forward output and log-sum-exp, as a remat policy names them


def _block_for(seq: int, cap: int) -> int:
    """The largest of ``cap``, ``cap / 2``, ``cap / 4`` that divides ``seq``;
    the whole of a sequence none divides (small ones, in tests)."""
    return next((b for b in (cap, cap // 2, cap // 4) if b and seq % b == 0), seq)


def splash_len(seq: int) -> int:
    """The length the kernel tier runs ``seq`` positions at: the next multiple
    of ``SPLASH_BLOCK`` for a sequence longer than one block, ``seq`` itself
    for one block or less (whose blocks ``_block_for`` picks)."""
    return -(-seq // SPLASH_BLOCK) * SPLASH_BLOCK if seq > SPLASH_BLOCK else seq


def _blocking(seq: int, window: int):
    """Block size, number of blocks, and how many key blocks back from the
    diagonal a query block can reach."""
    block = _block_for(seq, BLOCK)
    n = seq // block
    reach = n if not window else min(n, (window + block - 2) // block + 1)
    return block, n, reach


def _expand(x, groups):  # (B, b, Hkv, D) -> (B, b, Hkv * groups, D)
    return jnp.repeat(x, groups, axis=2) if groups > 1 else x


def window_attention_reference(q, k, v, window=0):
    B, T, Hq, D = q.shape
    groups, Dv = Hq // k.shape[2], v.shape[-1]
    scale = D**-0.5
    block, n, reach = _blocking(T, window)
    qb = q.reshape(B, n, block, Hq, D).swapaxes(0, 1)

    @jax.checkpoint
    def one_query_block(qi, q_blk):
        def one_key_block(acc, back):
            ki = qi - back

            def visit(acc):
                start = ki * block
                k_blk = _expand(jax.lax.dynamic_slice_in_dim(k, start, block, axis=1), groups)
                v_blk = _expand(jax.lax.dynamic_slice_in_dim(v, start, block, axis=1), groups)
                out, m, l = block_attention(q_blk, k_blk, v_blk, qi * block, start, True, scale, window=window)
                # a row this block hides entirely must not raise the running maximum
                return online_softmax_merge(acc, (out, jnp.where(l > 0, m, -1e30), l))

            return jax.lax.cond(ki >= 0, visit, lambda acc: acc, acc), None

        acc0 = (
            jnp.zeros((B, block, Hq, Dv), jnp.float32),
            jnp.full((B, Hq, block), -1e30, jnp.float32),
            jnp.zeros((B, Hq, block), jnp.float32),
        )
        (out, _, l), _ = jax.lax.scan(one_key_block, acc0, jnp.arange(reach))
        return (out / jnp.transpose(l, (0, 2, 1))[..., None]).astype(q.dtype)

    _, o = jax.lax.scan(lambda _, xs: (None, one_query_block(*xs)), None, (jnp.arange(n), qb))
    return o.swapaxes(0, 1).reshape(B, T, Hq, Dv)


def _splash_kernel(seq: int, heads_per_kv: int, window: int, interpret: bool):
    """Built anew in every trace: the kernel object holds its mask's block
    tables as arrays of the trace it was made in."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    one = sm.LocalMask((seq, seq), (window - 1, 0), 0) if window else sm.CausalMask((seq, seq))
    b = _block_for(seq, SPLASH_BLOCK)
    sizes = sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b, block_kv_dkv=b,
                          block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    return sk.make_splash_mqa_single_device(sm.MultiHeadMask([one] * heads_per_kv), block_sizes=sizes,
                                            residual_checkpoint_name=RESIDUALS, interpret=interpret)


def _splash(q, k, v, window, interpret=False):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    Tp = splash_len(T)
    kernel = _splash_kernel(Tp, groups, int(window), bool(interpret))
    scale = D**-0.5

    def pad(x):  # in the cast and transpose that copy it anyway
        return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, 0))) if Tp != T else x

    # (B, Hkv, groups, Tp, D) queries against (B, Hkv, Tp, D) keys and values
    qh = pad((q * scale).astype(jnp.bfloat16)).reshape(B, Tp, Hkv, groups, D).transpose(0, 2, 3, 1, 4)
    kh = pad(k.astype(jnp.bfloat16)).transpose(0, 2, 1, 3)
    vh = pad(v.astype(jnp.bfloat16)).transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(kernel))(qh, kh, vh)
    if Tp != T:  # the padded rows: thrown away, so their cotangent is zero and adds to no gradient
        o = o[..., :T, :]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)


def window_attention_pallas(q, k, v, window=0):
    return registry.platform_dispatch(
        functools.partial(_splash, window=window), functools.partial(window_attention_reference, window=window), q, k, v
    )


registry.register(
    "window_attention",
    reference=window_attention_reference,
    pallas=window_attention_pallas,
    doc="causal attention, grouped key-value heads or one a query head, values of their own head size, whole or over a "
        "sliding window, blockwise",
)


def window_attention(q, k, v, window=0):
    return registry.dispatch("window_attention")(q, k, v, window)
