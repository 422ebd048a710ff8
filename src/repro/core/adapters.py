"""Model adapters: the FL server is model-agnostic; an adapter binds a
trainable model (the paper's CNNs, or any registry transformer) to the
(loss, grad, metrics) interface the federated loop needs.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.models import cnn as CNN
from repro.models import model as MD


@dataclass(frozen=True)
class ModelAdapter:
    init: Callable[[Any], Any]                  # key -> params
    loss: Callable[[Any, Dict], jnp.ndarray]    # (params, batch) -> scalar
    grad: Callable[[Any, Dict], Any]            # (params, batch) -> grads
    accuracy: Callable[[Any, Dict], jnp.ndarray]
    # (params, batch, n) -> grads of the mean loss over the batch's first
    # n samples alone: a batch padded past its n real samples trains as
    # the n-sample batch would (the rest weigh 0)
    masked_grad: Callable[[Any, Dict, jnp.ndarray], Any]
    batch_fields: tuple = ("x", "y")


def _real(batch_size: int, n) -> jnp.ndarray:
    """(B,) float32: 1 for the first ``n`` samples of the batch, else 0."""
    return (jnp.arange(batch_size) < n).astype(jnp.float32)


def cnn_adapter(variant: str) -> ModelAdapter:
    loss = partial(CNN.cnn_loss, variant=variant)

    def masked_loss(params, batch, n):
        nll = CNN.cnn_nll(params, batch, variant)
        return (nll * _real(nll.shape[0], n)).sum() / n

    return ModelAdapter(
        init=lambda key: CNN.init_cnn(key, variant),
        loss=jax.jit(loss),
        grad=jax.jit(jax.grad(loss)),
        accuracy=jax.jit(partial(CNN.cnn_accuracy, variant=variant)),
        masked_grad=jax.jit(jax.grad(masked_loss)),
    )


def transformer_adapter(cfg) -> ModelAdapter:
    """FL over a registry architecture: batches carry token sequences; the
    'label' used for non-IID partitioning is the topic id (data pipeline).

    Batch format: {"x": tokens (B, S), "y": topic (unused by loss)}. The LM
    objective is next-token prediction over x.
    """

    def loss(params, batch, rows=None):
        toks = batch["x"]
        mask = jnp.ones_like(toks[:, 1:], jnp.float32)
        lm_batch = {
            "tokens": toks[:, :-1],
            "labels": toks[:, 1:],
            "mask": mask if rows is None else mask * rows[:, None],
        }
        return MD.loss_fn(cfg, params, lm_batch)

    def masked_loss(params, batch, n):
        # the token mean over the real rows' tokens is the n-row batch's
        return loss(params, batch, _real(batch["x"].shape[0], n))

    def accuracy(params, batch):
        toks = batch["x"]
        logits = MD.logits_fn(cfg, params, toks[:, :-1])
        return (logits.argmax(-1) == toks[:, 1:]).mean()

    return ModelAdapter(
        init=lambda key: MD.init_params(cfg, key),
        loss=jax.jit(loss),
        grad=jax.jit(jax.grad(loss)),
        accuracy=jax.jit(accuracy),
        masked_grad=jax.jit(jax.grad(masked_loss)),
    )
