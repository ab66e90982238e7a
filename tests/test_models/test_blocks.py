import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import (
    CNN,
    MLP,
    DeCNN,
    LayerNormGRUCell,
    MultiDecoder,
    MultiEncoder,
    NatureCNN,
    get_activation,
)


def test_mlp_shapes():
    m = MLP(hidden_sizes=(32, 32), output_dim=5, activation="tanh")
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((4, 10)))
    out = m.apply(params, jnp.zeros((4, 10)))
    assert out.shape == (4, 5)


def test_mlp_no_output_dim():
    m = MLP(hidden_sizes=(16,), activation="relu", layer_norm=True)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)))
    out = m.apply(params, jnp.zeros((2, 8)))
    assert out.shape == (2, 16)


def test_mlp_flatten():
    m = MLP(hidden_sizes=(8,), output_dim=3, flatten_dim=1)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 4, 5)))
    out = m.apply(params, jnp.zeros((2, 4, 5)))
    assert out.shape == (2, 3)


def test_mlp_addend_is_the_other_half_of_the_first_layer():
    """``MLP(x, addend=y @ K[n:])`` on the kernel's first ``n`` rows is
    ``MLP(concat([x, y]))`` on the whole kernel: the seam
    ``RSSM.dynamic_rollout`` splits the representation model's input at."""
    m = MLP(hidden_sizes=(16, 8), output_dim=3, activation="silu", layer_norm=True)
    x, y = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 6)), jax.random.normal(jax.random.PRNGKey(2), (2, 4, 5))
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((4, 11)))
    kernel = params["params"]["dense_0"]["kernel"]
    first = {"params": {**params["params"], "dense_0": {**params["params"]["dense_0"], "kernel": kernel[:6]}}}
    whole = m.apply(params, jnp.concatenate([x, y], axis=-1))
    np.testing.assert_allclose(m.apply(first, x, addend=y @ kernel[6:]), whole, rtol=1e-5, atol=1e-6)


def test_cnn_nhwc():
    m = CNN(hidden_channels=(8, 16), layer_args={"kernel_size": 3, "stride": 2, "padding": 1})
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 16, 16, 3)))
    out = m.apply(params, jnp.zeros((2, 16, 16, 3)))
    assert out.shape == (2, 4, 4, 16)


def test_decnn_doubles_spatial():
    # Dreamer-style stride-2 kernel-4 pad-1 doubling
    m = DeCNN(hidden_channels=(8,), layer_args={"kernel_size": 4, "stride": 2, "padding": 1})
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 8, 8, 4)))
    out = m.apply(params, jnp.zeros((2, 8, 8, 4)))
    assert out.shape == (2, 16, 16, 8)


def test_nature_cnn():
    m = NatureCNN(features_dim=512)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 64, 64, 4)))
    out = m.apply(params, jnp.zeros((2, 64, 64, 4)))
    assert out.shape == (2, 512)


def test_layer_norm_gru_cell():
    cell = LayerNormGRUCell(hidden_size=16, layer_norm=True)
    params = cell.init(jax.random.PRNGKey(0), jnp.zeros((3, 16)), jnp.zeros((3, 8)))
    h, out = cell.apply(params, jnp.ones((3, 16)), jnp.ones((3, 8)))
    assert h.shape == (3, 16)
    assert np.allclose(h, out)


def test_gru_cell_scan():
    cell = LayerNormGRUCell(hidden_size=8)
    params = cell.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)), jnp.zeros((2, 4)))
    xs = jnp.ones((5, 2, 4))

    def step(h, x):
        return cell.apply(params, h, x)

    h_final, hs = jax.lax.scan(step, jnp.zeros((2, 8)), xs)
    assert hs.shape == (5, 2, 8)


def test_multi_encoder_decoder():
    import flax.linen as nn

    class CnnEnc(nn.Module):
        @nn.compact
        def __call__(self, obs):
            x = obs["rgb"]
            return x.reshape(x.shape[0], -1)

    class MlpEnc(nn.Module):
        @nn.compact
        def __call__(self, obs):
            return obs["state"]

    enc = MultiEncoder(CnnEnc(), MlpEnc())
    obs = {"rgb": jnp.zeros((2, 4, 4, 1)), "state": jnp.zeros((2, 3))}
    params = enc.init(jax.random.PRNGKey(0), obs)
    out = enc.apply(params, obs)
    assert out.shape == (2, 16 + 3)


def test_get_activation_torch_compat():
    assert get_activation("torch.nn.Tanh") is get_activation("tanh")
    assert get_activation("torch.nn.SiLU") is get_activation("silu")
    with pytest.raises(ValueError):
        get_activation("nosuch")


def test_dreamer_v2_cnn_encoder_pad_trick_matches_plain_valid_conv():
    """The exact-VALID end-pad trick in the V2/V1 encoder must be a no-op on
    values for every input geometry, including non-square frames (crafter/
    diambra accept tuple screen sizes)."""
    import flax.linen as nn

    from sheeprl_tpu.algos.dreamer_v2.agent import CNNEncoder

    class PlainStack(nn.Module):
        channels_multiplier: int = 4

        @nn.compact
        def __call__(self, x):
            for i, mult in enumerate((1, 2, 4, 8)):
                x = nn.Conv(
                    mult * self.channels_multiplier,
                    kernel_size=(4, 4),
                    strides=(2, 2),
                    padding="VALID",
                    use_bias=True,
                    name=f"conv_{i}",
                )(x)
                x = nn.elu(x)
            return x.reshape(x.shape[0], -1)

    for h, w in ((64, 64), (96, 64)):
        x = jnp.asarray(np.random.RandomState(h + w).rand(2, h, w, 3), jnp.float32)
        enc = CNNEncoder(keys=["rgb"], channels_multiplier=4, layer_norm=False, activation="elu")
        ref = PlainStack()
        p_ref = ref.init(jax.random.PRNGKey(0), x)
        out_ref = ref.apply(p_ref, x)
        # Graft the plain stack's kernels into the encoder so outputs are comparable.
        graft = {"params": {k: dict(p_ref["params"][k]) for k in p_ref["params"]}}
        out_enc = enc.apply(graft, {"rgb": x})
        np.testing.assert_array_equal(np.asarray(out_enc), np.asarray(out_ref))
