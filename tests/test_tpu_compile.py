"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: the TPU compiler (libtpu) refuses here what interpret mode
accepts, such as a block shape that does not match the chip's tiling or a
kernel that needs more VMEM than it may use.  Nothing runs, so these tests
say nothing about results or speed.

Every compile test of the repository lives in this one file.  The
topology is described inside module-scoped fixtures, never at import:
only one process at a time may load libtpu, and test workers import every
test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import clustering as CL
from repro.kernels import ops
from repro.kernels.kmeans import kmeans_assign, lloyd_step

K = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", [lloyd_step, kmeans_assign],
                         ids=["lloyd_step", "kmeans_assign"])
@pytest.mark.parametrize("f", [256, 4096])
@pytest.mark.parametrize("n", [100, 100_000])
def test_kmeans_kernel_compiles_for_v5e(one_chip, kernel, n, f):
    x = jax.ShapeDtypeStruct((n, f), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((K, f), jnp.float32, sharding=one_chip)
    text = _compiled_text(functools.partial(kernel, interpret=False), x, c)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [100, 100_000])
def test_stage1_kmeans_program_compiles_for_v5e(one_chip, monkeypatch, n):
    """The whole stage-1 program (k-means++ seeding, the Lloyd scan, all
    restarts vmapped) as the server runs it on a TPU: ``impl="auto"``
    takes the Pallas lloyd_step there.  The platform probe sees this
    process's CPU, so the test steers it to the TPU branch."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    program = functools.partial(CL._kmeans_batched.__wrapped__, k=K,
                                iters=25, restarts=4, assign_fn=None,
                                impl="auto")
    x = jax.ShapeDtypeStruct((n, 256), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(program, x, key)
