"""Model FLOPs from shapes, for the utilization metrics.

A forward pass counts two FLOPs per multiply-add of its convolutions and
dense layers; biases, activations and pooling are not counted.  A training
sample counts three forward passes (forward, and the backward pass's two
products per layer)."""
from __future__ import annotations

TRAIN_PER_FWD = 3


def conv2d_flops(out_hw: tuple, cout: int, kh: int, kw: int, cin: int) -> int:
    return 2 * out_hw[0] * out_hw[1] * cout * kh * kw * cin


def dense_flops(d_in: int, d_out: int) -> int:
    return 2 * d_in * d_out


def cnn_mnist_fwd() -> int:
    """CNN-MNIST on a 28x28x1 image: 961,000 FLOPs."""
    return (conv2d_flops((24, 24), 10, 5, 5, 1)      # 288,000
            + conv2d_flops((8, 8), 20, 5, 5, 10)     # 640,000
            + dense_flops(320, 50)                   # 32,000
            + dense_flops(50, 10))                   # 1,000


FWD_FLOPS = {"cnn_mnist": cnn_mnist_fwd}
