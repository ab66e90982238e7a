"""The Pallas kernel tier (howto/kernels.md).

Each kernel ships as a triple — plain-lax reference, Pallas kernel with
``jax.custom_vjp``, registry entry — and call sites go through the
registry's dispatch, selected by the ``ops.backend=auto|pallas|lax`` config
knob with per-kernel overrides (``ops.kernels.<name>``). Importing this
package registers every kernel.
"""

from sheeprl_tpu.ops.kernels.registry import (
    AUTO_LAX_ON_TPU,
    COMPILED_BY_XLA,
    Kernel,
    UnknownKernelError,
    UnknownOpsBackendError,
    VALID_BACKENDS,
    backend,
    configure,
    configure_from_config,
    dispatch,
    get,
    names,
    overrides,
    register,
    resolve,
    tier,
    use_backend,
)
from sheeprl_tpu.ops.kernels.gru import gru_gates, gru_gates_pallas, gru_gates_reference
from sheeprl_tpu.ops.kernels.twohot import (
    two_hot_symexp_decode,
    two_hot_symexp_decode_reference,
    two_hot_symlog_loss,
    two_hot_symlog_loss_reference,
)
from sheeprl_tpu.ops.kernels.gae import gae, gae_reference
from sheeprl_tpu.ops.kernels.sumtree import sumtree_sample, sumtree_sample_reference
from sheeprl_tpu.ops.kernels.scatter import ragged_ring_scatter, ragged_ring_scatter_reference
from sheeprl_tpu.ops.kernels.moe import moe_grouped_ffn, moe_grouped_ffn_reference
from sheeprl_tpu.ops.kernels.attn import window_attention, window_attention_reference

__all__ = [
    "AUTO_LAX_ON_TPU",
    "COMPILED_BY_XLA",
    "Kernel",
    "UnknownKernelError",
    "UnknownOpsBackendError",
    "VALID_BACKENDS",
    "backend",
    "configure",
    "configure_from_config",
    "dispatch",
    "gae",
    "gae_reference",
    "get",
    "gru_gates",
    "gru_gates_pallas",
    "gru_gates_reference",
    "moe_grouped_ffn",
    "moe_grouped_ffn_reference",
    "names",
    "overrides",
    "ragged_ring_scatter",
    "ragged_ring_scatter_reference",
    "register",
    "resolve",
    "sumtree_sample",
    "sumtree_sample_reference",
    "tier",
    "two_hot_symexp_decode",
    "two_hot_symexp_decode_reference",
    "two_hot_symlog_loss",
    "two_hot_symlog_loss_reference",
    "use_backend",
    "window_attention",
    "window_attention_reference",
]
