"""Synthetic class-conditional images, made on the device from the seed.

A copy of the program's ``repro.data.synthetic.make_image_dataset``
(28x28x1, 10 classes: a smooth random template per class, a per-sample
translation of -2..2 pixels, Gaussian pixel noise, clipped to [0, 1]),
kept here so that no later change to the program can move the benchmark's
inputs.  Departures, for set-up time and seeds: images are drawn on the
device in chunks of one jitted program, the translation is a gather, and
keys come from any whole-number seed through :func:`seed_key`.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NUM_CLASSES = 10
HW = (28, 28, 1)
NOISE = 0.12
CHUNK = 16384          # images per jitted draw


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw ``uint32[2]`` PRNG key for ``(seed, stream)``; ``seed`` may
    exceed 32 bits."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def _templates(key):
    h, w, c = HW
    seeds = jax.random.normal(key, (NUM_CLASSES, 7, 7, c))
    t = jax.image.resize(seeds, (NUM_CLASSES, h, w, c), "bilinear")
    return 0.5 + 0.35 * t / jnp.maximum(jnp.abs(t).max(), 1e-6)


@jax.jit
def _draw(template_key, key):
    """One chunk of CHUNK images: a template per label, translated by a
    per-sample shift (a circular roll, as ``jnp.roll``), plus noise."""
    temps = _templates(template_key)[..., 0]                # (10, 28, 28)
    ky, kshift, knoise = jax.random.split(key, 3)
    y = jax.random.randint(ky, (CHUNK,), 0, NUM_CLASSES)
    sh = jax.random.randint(kshift, (CHUNK, 2), -2, 3)
    h, w, _ = HW
    rows = (jnp.arange(h)[None, :] - sh[:, :1]) % h             # (n, 28)
    cols = (jnp.arange(w)[None, :] - sh[:, 1:]) % w
    base = temps[y[:, None, None], rows[:, :, None], cols[:, None, :]]
    x = base + NOISE * jax.random.normal(knoise, base.shape)
    return jnp.clip(x, 0.0, 1.0)[..., None], y.astype(jnp.int32)


def _draw_set(template_key, key, n: int):
    xs, ys = [], []
    for i in range(-(-n // CHUNK)):
        x, y = _draw(template_key, jax.random.fold_in(key, i))
        xs.append(np.asarray(x))
        ys.append(np.asarray(y))
    return (np.concatenate(xs)[:n].astype(np.float32),
            np.concatenate(ys)[:n].astype(np.int32))


def make_images(seed: int, n_train: int, n_test: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """``(x_train, y_train, x_test, y_test)`` as host arrays.  Train and
    test share the class templates and differ in their sample draws."""
    kt, k1, k2 = jax.random.split(seed_key(seed, 0), 3)
    return _draw_set(kt, k1, n_train) + _draw_set(kt, k2, n_test)
