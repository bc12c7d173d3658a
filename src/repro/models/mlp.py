"""Small MLP classifier used by the examples
(stands in for the paper's 2-layer CNN — same scale, pure JAX)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mlp_loss_builder(dim, n_classes, width=64):
    """Small MLP classifier (stands in for the paper's 2-layer CNN — same
    scale, pure-JAX) on {x, y} batches."""
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w1": jax.random.normal(k1, (dim, width)) / np.sqrt(dim),
                "b1": jnp.zeros(width),
                "w2": jax.random.normal(k2, (width, width)) / np.sqrt(width),
                "b2": jnp.zeros(width),
                "w3": jax.random.normal(k3, (width, n_classes)) / np.sqrt(width),
                "b3": jnp.zeros(n_classes)}

    def logits_fn(p, x):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        h = jax.nn.relu(h @ p["w2"] + p["b2"])
        return h @ p["w3"] + p["b3"]

    def loss_fn(p, batch):
        lg = logits_fn(p, batch["x"])
        lp = jax.nn.log_softmax(lg)
        oh = jax.nn.one_hot(batch["y"], n_classes)
        return -jnp.mean(jnp.sum(lp * oh, axis=-1))

    def acc_fn(p, x, y):
        return float(jnp.mean(jnp.argmax(logits_fn(p, x), -1) == y))

    return init, loss_fn, acc_fn
