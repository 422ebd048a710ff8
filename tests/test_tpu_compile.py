"""Compile the main path's Pallas kernels and CNN programs for a TPU v5e
that is described, not attached: the TPU compiler (libtpu) refuses here
what interpret mode accepts, such as a block shape that does not match the
chip's tiling or a kernel that needs more VMEM than it may use, and its
optimized HLO shows how a program was lowered for the chip.  Nothing runs,
so these tests say nothing about results or speed.

Every compile test of the repository lives in this one file.  The
topology is described inside module-scoped fixtures, never at import:
only one process at a time may load libtpu, and test workers import every
test file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FLConfig
from repro.core import clustering as CL
from repro.core.adapters import cnn_adapter
from repro.kernels import ops
from repro.kernels.kmeans import kmeans_assign, lloyd_step
from repro.models import cnn as CNN
from repro.sim.engine import CohortEngine
from repro.sim.fleet import store_shape

K = 10
# im2col patches: a concatenate with a floating-point result (the gathers'
# index concatenates are s32)
PATCHES = re.compile(r"= (?:bf16|f32)\[[^\]]*\]\S* concatenate\(")
# a convolution that is not a matmul: XLA's convolution (and its
# transposes), where the TPU lowers every dot_general to a convolution too
LAX_CONV = re.compile(r" convolution\(.*op_name=\"(?![^\"]*dot_general\")")


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


def _cnn_lowering(text: str) -> tuple:
    return len(LAX_CONV.findall(text)), len(PATCHES.findall(text))


def _specs(sharding, tree):
    """``jax.ShapeDtypeStruct``s of ``tree``'s leaves on ``sharding``."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


FEAT = CNN.image_shape("mnist")


def _class_program_text(devices, store_x, tier: int, step_cap: int) -> str:
    """Optimized HLO of the device runtime's class-training program (bs
    32) over a class store of flat sample rows ``store_x (P, n_rows,
    width)``: the one-chip program, or the cohort mesh's shard_map
    program over ``devices``."""
    chips = len(devices)
    mesh = Mesh(np.array(devices).reshape(chips, 1), ("data", "model"))
    engine = CohortEngine(cnn_adapter("mnist"), FLConfig(),
                          mesh=mesh if chips > 1 else None)
    bs, f32, i32 = 32, jnp.float32, jnp.int32
    store, client = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    args = _specs(store, (
        jax.eval_shape(lambda: CNN.init_cnn(jax.random.PRNGKey(0), "mnist")),
        jax.ShapeDtypeStruct(store_x, f32),
        jax.ShapeDtypeStruct(store_x[:2], i32))) + _specs(client, (
        jax.ShapeDtypeStruct((tier,), i32),
        jax.ShapeDtypeStruct((tier, step_cap, bs), i32),
        jax.ShapeDtypeStruct((tier, step_cap), f32),
        jax.ShapeDtypeStruct((tier,), i32),
        jax.ShapeDtypeStruct((tier,), f32)))
    program = (engine._train_gather_sharded if chips > 1
               else engine._train_gather)
    return program.lower(*args, feat=FEAT).compile().as_text()


def _class_training_lowering(devices) -> tuple:
    """(convolutions, patch concatenates) of the class-training program
    at a paper-job class shape (6 members of up to 64 samples, step cap
    8, tier 4 per chip)."""
    return _cnn_lowering(_class_program_text(
        devices, store_shape(6, 64, FEAT), 4 * len(devices), 8))


def test_class_training_program_convolves_for_v5e(topo):
    """On one chip the class program convolves with XLA's convolution
    and builds no im2col patches."""
    convs, patches = _class_training_lowering(topo.devices[:1])
    assert convs > 0 and patches == 0


def test_mesh_class_training_program_convolves_for_v5e(topo):
    """So does the 2x2 cohort mesh's program (clients over 'data',
    FedAvg psum-reduced on the mesh)."""
    convs, patches = _class_training_lowering(topo.devices)
    assert convs > 0 and patches == 0


def _store_copies(text: str, members: int) -> list:
    """Instructions, parameters aside, with float data whose shape leads
    with a class store's ``members`` axis: whole-store copies."""
    lead = re.compile(rf"= (?:f32|bf16)\[{members},\d+,")
    return [line for line in text.splitlines()
            if lead.search(line) and " parameter(" not in line]


@pytest.mark.parametrize("tiled", [True, False], ids=["store", "untiled"])
def test_fleet_class_program_reads_only_winner_rows_for_v5e(topo, tiled):
    """The fleet cell's largest class (1,448 members of 128 to 159
    training samples, tier 64, step cap 4): its one-chip program copies
    no whole store, whose bytes would grow with the fleet, and no take
    fills out-of-range indices (``jit(_take)/select_n``).  Control: flat
    rows that are not whole (8, 128) tiles, ``(P, 159, 784)``, get a
    layout with the member axis on a tile, and every call relayouts the
    whole store."""
    members, n_cap = 1448, 159
    store_x = (store_shape(members, n_cap, FEAT) if tiled
               else (members, n_cap, math.prod(FEAT)))
    text = _class_program_text(topo.devices[:1], store_x, 64, 4)
    if tiled:
        assert _store_copies(text, members) == []
        assert "jit(_take)/select_n" not in text
    else:
        assert _store_copies(text, members)


def _im2col_conv2d(x, w, b, padding="VALID"):
    """Control for the patch count: the conv as shifted views of ``x``
    concatenated on the channel axis, times the flattened kernel."""
    kh, kw, cin, cout = w.shape
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                               for i in range(kh) for j in range(kw)], -1)
    return patches @ w.reshape(kh * kw * cin, cout) + b


@pytest.mark.parametrize("variant,im2col", [
    ("mnist", False), ("fmnist", False), ("cifar", False),
    ("mnist", True),        # control: the patches are seen
])
def test_cnn_grad_step_convolves_for_v5e(one_chip, monkeypatch, variant,
                                         im2col):
    """A vmapped SGD step of each CNN variant (4 clients, batch 32), as
    the cohort engine runs it on the TPU."""
    if im2col:
        monkeypatch.setattr(CNN, "conv2d", _im2col_conv2d)

    def step(params, x, y):
        grads = jax.vmap(jax.grad(
            lambda p, xs, ys: CNN.cnn_loss(p, {"x": xs, "y": ys}, variant)))(
                params, x, y)
        return jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)

    clients, bs = 4, 32
    args = _specs(one_chip, (
        jax.eval_shape(jax.vmap(lambda k: CNN.init_cnn(k, variant)),
                       jax.random.split(jax.random.PRNGKey(0), clients)),
        jax.ShapeDtypeStruct((clients, bs) + CNN.image_shape(variant),
                             jnp.float32),
        jax.ShapeDtypeStruct((clients, bs), jnp.int32)))
    convs, patches = _cnn_lowering(_compiled_text(step, *args))
    if im2col:
        assert convs == 0 and patches > 0
    else:
        assert convs > 0 and patches == 0
