"""Model FLOPs from shapes (bench/flops.py) against XLA's own count."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from bench import flops
from bench.reference import cnn_mnist as M


def xla_flops(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def conv(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                    dimension_numbers=("NHWC", "HWIO",
                                                       "NHWC"))


def test_cnn_mnist_forward_is_961000():
    assert flops.cnn_mnist_fwd() == 961_000
    assert flops.FWD_FLOPS["cnn_mnist"]() == 961_000


@pytest.mark.parametrize("fn,shapes,want", [
    (conv, [(1, 28, 28, 1), (5, 5, 1, 10)], 288_000),
    (conv, [(1, 12, 12, 10), (5, 5, 10, 20)], 640_000),
    (jnp.dot, [(1, 320), (320, 50)], 32_000),
    (jnp.dot, [(1, 50), (50, 10)], 1_000),
])
def test_each_layer_matches_xla(fn, shapes, want):
    assert xla_flops(fn, *shapes) == want


def test_whole_forward_matches_xla_within_the_uncounted_ops():
    """XLA also counts biases, ReLU and pooling, which the model count
    leaves out: under 3% more on top of the layers' products."""
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in M.SHAPES.items()}
    x = jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32)
    fwd = jax.jit(lambda p, x: M.logits(p, x, jnp.float32))
    got = fwd.lower(params, x).compile().cost_analysis()["flops"]
    assert 961_000 <= got <= 961_000 * 1.03
