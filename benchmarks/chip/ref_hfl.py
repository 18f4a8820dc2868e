"""Plain reference of one hierarchical FL round, for any model.

The model enters only through its ``loss(params, tokens, labels, c,
dtype)``, which the configuration's reference module gives (see
``spec.load_reference``). Straightforward ``jax.numpy``: local SGD steps
one microbatch at a time, edge and cloud means in float32, replicas
replayed one after another.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_sgd_step(loss, c: dict, lr: float, dtype=jnp.float32):
    """One local SGD step of the model ``loss`` on one microbatch:
    p <- p - lr * grad, with the parameters held in ``dtype``."""

    def step(p, tokens, labels):
        g = jax.grad(loss)(p, tokens, labels, c, dtype)
        return jax.tree.map(
            lambda a, b: (a.astype(jnp.float32)
                          - lr * b.astype(jnp.float32)).astype(dtype), p, g)

    return jax.jit(step, donate_argnums=0)


def _scaled_sum(acc, model, scale):
    """acc + scale * model, in float32 (acc None starts the sum)."""
    if acc is None:
        return jax.tree.map(lambda a: a.astype(jnp.float32) * scale, model)
    return jax.tree.map(lambda s, a: s + a.astype(jnp.float32) * scale,
                        acc, model)


def hfl_round(model, rows, replicas, g1, g2, microbatches, sgd, dtype):
    """One cloud round from the shared ``model``.

    ``rows``: (tokens, labels) of the round, shape (R * rows_per_replica,
    S), replica r taking rows [r * k, (r + 1) * k) in (pod, edge, fl)
    order, each local epoch going over its rows in ``microbatches``
    equal steps. Every g1 epochs each edge averages its fl replicas and
    they restart from that mean; after g2 such periods the cloud
    averages the edges (all edges hold equally many replicas, so this is
    the mean of all replicas). Edges are independent until the cloud
    mean, so they are computed one after another and at most one
    replica is held beside the running sums.
    """
    n_pod, n_edge, n_fl = replicas
    n_rep = n_pod * n_edge * n_fl
    tokens, labels = rows
    k = tokens.shape[0] // n_rep
    mb = k // microbatches
    cloud = None
    for e in range(n_pod * n_edge):
        edge = model
        for _ in range(g2):
            acc = None
            for f in range(n_fl):
                r = e * n_fl + f
                m = jax.tree.map(jnp.copy, edge)
                for _ in range(g1):
                    for i in range(microbatches):
                        lo = r * k + i * mb
                        m = sgd(m, tokens[lo:lo + mb], labels[lo:lo + mb])
                acc = _scaled_sum(acc, m, 1.0 / n_fl)
                del m
            edge = jax.tree.map(lambda a: a.astype(dtype), acc)
            del acc
        cloud = _scaled_sum(cloud, edge, 1.0 / (n_pod * n_edge))
    return jax.tree.map(lambda a: a.astype(dtype), cloud)
