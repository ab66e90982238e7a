"""Generic NN building blocks in Flax.

Capability parity with the reference's block library
(``sheeprl/models/models.py:16-525``) with TPU-native choices:

- images are **NHWC** end-to-end (XLA's preferred TPU conv layout) — the
  reference is NCHW; the env layer here already emits channel-last;
- "LayerNormChannelLast" is therefore just LayerNorm over the trailing axis —
  no permutes (the reference needs two, ``models.py:507-519``);
- the Hafner GRU cell (``models.py:331-412``: LayerNorm on the fused 3H
  projection, candidate gated by reset *inside* tanh, ``update - 1`` bias) is
  a scan-ready cell: ``(h, x) -> h`` — the RSSM wraps it in ``lax.scan``;
- activations/norms are selected by *name* (config strings); reference
  configs' ``torch.nn.X`` targets are mapped for config compatibility.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = [
    "get_activation",
    "MLP",
    "CNN",
    "DeCNN",
    "NatureCNN",
    "LayerNormGRUCell",
    "MultiEncoder",
    "MultiDecoder",
    "LayerNormChannelLast",
]

_ACTIVATIONS: Dict[str, Callable] = {
    "relu": nn.relu,
    "tanh": jnp.tanh,
    "silu": nn.silu,
    "swish": nn.silu,
    "elu": nn.elu,
    "gelu": nn.gelu,
    "sigmoid": nn.sigmoid,
    "leaky_relu": nn.leaky_relu,
    "identity": lambda x: x,
}


def get_activation(name: Optional[Union[str, Callable]]) -> Callable:
    """Resolve an activation by name; accepts reference-style ``torch.nn.X``
    strings for config compatibility."""
    if name is None:
        return lambda x: x
    if callable(name):
        return name
    key = str(name).rsplit(".", 1)[-1].lower()
    if key not in _ACTIVATIONS:
        raise ValueError(f"Unknown activation '{name}'. Known: {sorted(_ACTIVATIONS)}")
    return _ACTIVATIONS[key]


class LayerNormChannelLast(nn.Module):
    """LayerNorm over the channel axis of NHWC tensors. In channel-last layout
    this is plain LayerNorm (kept as a named class for parity with the
    reference's NCHW permute version, ``models.py:507-519``)."""

    eps: float = 1e-3
    use_scale: bool = True
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return nn.LayerNorm(epsilon=self.eps, use_scale=self.use_scale, use_bias=self.use_bias, dtype=self.dtype)(x)


class MLP(nn.Module):
    """Configurable MLP (reference: ``models.py:16-120``).

    Args mirror the reference: per-layer norm/dropout/activation, optional
    final ``output_dim`` linear with no activation, optional input flatten.
    ``addend`` is added to the first ``Dense``'s output, before its norm and
    activation: the part of that layer's product a caller formed elsewhere
    (``RSSM.dynamic_rollout`` forms the half that no scan carry feeds once,
    before its loop).
    """

    hidden_sizes: Sequence[int] = ()
    output_dim: Optional[int] = None
    activation: Union[str, Sequence[str], None] = "relu"
    layer_norm: bool = False
    norm_args: Optional[Sequence[Dict[str, Any]]] = None
    dropout: float = 0.0
    flatten_dim: Optional[int] = None
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True, addend: Optional[jax.Array] = None) -> jax.Array:
        if self.flatten_dim is not None:
            x = jnp.reshape(x, x.shape[: self.flatten_dim] + (-1,))
        acts = self.activation if isinstance(self.activation, (list, tuple)) else [self.activation] * len(
            self.hidden_sizes
        )
        for i, size in enumerate(self.hidden_sizes):
            x = nn.Dense(size, dtype=self.dtype, param_dtype=self.param_dtype, name=f"dense_{i}")(x)
            if i == 0 and addend is not None:
                x = x + addend
            if self.dropout > 0:
                x = nn.Dropout(self.dropout, deterministic=deterministic)(x)
            if self.layer_norm:
                kw = {}
                if self.norm_args is not None and i < len(self.norm_args):
                    kw = dict(self.norm_args[i])
                    kw.pop("normalized_shape", None)
                eps = kw.pop("eps", 1e-3)
                x = nn.LayerNorm(epsilon=eps, dtype=self.dtype, name=f"ln_{i}", **kw)(x)
            x = get_activation(acts[i])(x)
        if self.output_dim is not None:
            x = nn.Dense(self.output_dim, dtype=self.dtype, param_dtype=self.param_dtype, name="out")(x)
        return x


class CNN(nn.Module):
    """Conv stack over NHWC inputs (reference: ``models.py:122-204``)."""

    hidden_channels: Sequence[int]
    layer_args: Union[Dict[str, Any], Sequence[Dict[str, Any]], None] = None
    activation: Union[str, Sequence[str], None] = "relu"
    layer_norm: bool = False
    norm_eps: float = 1e-3
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = len(self.hidden_channels)
        args = self.layer_args if isinstance(self.layer_args, (list, tuple)) else [self.layer_args] * n
        acts = self.activation if isinstance(self.activation, (list, tuple)) else [self.activation] * n
        for i, ch in enumerate(self.hidden_channels):
            kw = dict(args[i] or {})
            kernel = kw.pop("kernel_size", 3)
            stride = kw.pop("stride", 1)
            padding = kw.pop("padding", 0)
            use_bias = kw.pop("bias", True)
            if isinstance(kernel, int):
                kernel = (kernel, kernel)
            if isinstance(stride, int):
                stride = (stride, stride)
            if isinstance(padding, int):
                padding = [(padding, padding), (padding, padding)]
            x = nn.Conv(
                ch,
                kernel_size=kernel,
                strides=stride,
                padding=padding,
                use_bias=use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"conv_{i}",
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name=f"ln_{i}")(x)
            x = get_activation(acts[i])(x)
        return x


class DeCNN(nn.Module):
    """Transposed-conv stack over NHWC inputs (reference: ``models.py:205-287``)."""

    hidden_channels: Sequence[int]
    layer_args: Union[Dict[str, Any], Sequence[Dict[str, Any]], None] = None
    activation: Union[str, Sequence[str], None] = "relu"
    layer_norm: bool = False
    norm_eps: float = 1e-3
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = len(self.hidden_channels)
        args = self.layer_args if isinstance(self.layer_args, (list, tuple)) else [self.layer_args] * n
        acts = self.activation if isinstance(self.activation, (list, tuple)) else [self.activation] * n
        for i, ch in enumerate(self.hidden_channels):
            kw = dict(args[i] or {})
            kernel = kw.pop("kernel_size", 3)
            stride = kw.pop("stride", 1)
            padding = kw.pop("padding", 0)
            output_padding = kw.pop("output_padding", 0)
            use_bias = kw.pop("bias", True)
            if isinstance(kernel, int):
                kernel = (kernel, kernel)
            if isinstance(stride, int):
                stride = (stride, stride)
            x = _conv_transpose_torchlike(
                x,
                ch,
                kernel,
                stride,
                padding,
                output_padding,
                use_bias,
                self.dtype,
                self.param_dtype,
                name=f"deconv_{i}",
                parent=self,
            )
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name=f"ln_{i}")(x)
            x = get_activation(acts[i])(x)
        return x


class _ConvTranspose(nn.Module):
    """ConvTranspose with torch-style padding/output_padding semantics.

    torch's output size: (in-1)*stride - 2*padding + kernel + output_padding.
    flax's ConvTranspose with padding='VALID' gives (in-1)*stride + kernel; we
    trim ``padding`` from both sides and add ``output_padding`` at the end so
    decoder geometries copied from reference configs (e.g. Dreamer's 4-step
    64×64 decoder) produce identical shapes.
    """

    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int]
    padding: int = 0
    output_padding: int = 0
    use_bias: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        y = nn.ConvTranspose(
            self.features,
            kernel_size=self.kernel_size,
            strides=self.strides,
            padding="VALID",
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x)
        p = self.padding
        if p:
            y = y[:, p:-p or None, p:-p or None, :]
        if self.output_padding:
            op = self.output_padding
            y = jnp.pad(y, ((0, 0), (0, op), (0, op), (0, 0)))
        return y


def _conv_transpose_torchlike(x, ch, kernel, stride, padding, output_padding, use_bias, dtype, param_dtype, name, parent):
    return _ConvTranspose(
        features=ch,
        kernel_size=kernel,
        strides=stride,
        padding=padding,
        output_padding=output_padding,
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=param_dtype,
        name=name,
        parent=parent,
    )(x)


class NatureCNN(nn.Module):
    """DQN Nature conv net + projection (reference: ``models.py:288-330``)."""

    features_dim: int = 512
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = CNN(
            hidden_channels=(32, 64, 64),
            layer_args=[
                {"kernel_size": 8, "stride": 4},
                {"kernel_size": 4, "stride": 2},
                {"kernel_size": 3, "stride": 1},
            ],
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="cnn",
        )(x)
        x = jnp.reshape(x, x.shape[:-3] + (-1,))
        x = nn.Dense(self.features_dim, dtype=self.dtype, param_dtype=self.param_dtype, name="fc")(x)
        return nn.relu(x)


class LayerNormGRUCell(nn.Module):
    """Hafner-style GRU cell (reference: ``models.py:331-412``).

    One fused ``Dense([h, x]) -> 3H`` projection, optional LayerNorm on the
    projection, candidate gated by reset inside tanh, and the stabilizing
    ``update - 1`` bias. Shaped ``(h, x) -> (h, h)`` so it drops directly into
    ``lax.scan`` / ``nn.scan`` for the RSSM sequence loop.
    """

    hidden_size: int
    use_bias: bool = True
    layer_norm: bool = False
    norm_eps: float = 1e-3
    use_pallas: Optional[bool] = None  # None = follow the ops.backend registry
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        fused = nn.Dense(
            3 * self.hidden_size,
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="fused",
        )(jnp.concatenate([h, x], axis=-1))
        if self.layer_norm:
            fused = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name="ln")(fused)
        if h.ndim == 2 and self.use_pallas is not False:
            from sheeprl_tpu.ops.kernels import gru_gates

            # None follows the ops.backend registry (auto = Pallas iff the
            # process holds a TPU; a host-CPU lowering there takes the lax
            # reference); an explicit True forces the Pallas tier.
            h_new = gru_gates(fused, h, backend="pallas" if self.use_pallas else None)
            return h_new, h_new
        reset, cand, update = jnp.split(fused, 3, axis=-1)
        reset = nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = nn.sigmoid(update - 1)
        h_new = update * cand + (1 - update) * h
        return h_new, h_new


class MultiEncoder(nn.Module):
    """Concatenate a CNN encoder over pixel keys with an MLP encoder over
    vector keys (reference: ``models.py:413-477``). Sub-encoders are arbitrary
    modules taking the obs dict and returning a flat feature vector."""

    cnn_encoder: Optional[nn.Module] = None
    mlp_encoder: Optional[nn.Module] = None

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        if self.cnn_encoder is None and self.mlp_encoder is None:
            raise ValueError("There must be at least one encoder")
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=-1)


class MultiDecoder(nn.Module):
    """Decode a latent into per-key reconstructions
    (reference: ``models.py:478-506``)."""

    cnn_decoder: Optional[nn.Module] = None
    mlp_decoder: Optional[nn.Module] = None

    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        if self.cnn_decoder is None and self.mlp_decoder is None:
            raise ValueError("There must be at least one decoder")
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(x))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(x))
        return out
