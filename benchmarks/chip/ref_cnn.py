"""Plain reference of the Arena paper's MNIST CNN and of one synchronous
hierarchical FL cloud round (paper §2.1, Eqs. 1, 2, 5).

Straightforward ``jax.numpy`` with every convolution and matmul at
``Precision.HIGHEST``; no kernels, no flat bank. It imports nothing of
the program. It builds w(0) itself from the seed by the initializer the
paper's model names (truncated normal: scale 0.1 for the convolutions,
1/sqrt(fan_in) for the dense layers; zero biases) and draws each
device's minibatch order from the round's key as the configuration
states: per cloud round, per edge period a key, per local epoch a key,
per device a permutation of its samples. Its inputs are the
environment's own: the data shards, the device-to-edge assignment and
the per-edge action.

``dtype`` selects the precision the parameters and the computation are
held in: float32 for the reference, bfloat16 for the check's control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _tn(key, shape, std):
    return jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std


def init(key) -> dict:
    """w(0) of conv(1->10, 5x5), conv(10->20, 5x5), fc(320->50), fc(50->10)."""
    ks = jax.random.split(key, 4)
    return {
        "c1_w": _tn(ks[0], (5, 5, 1, 10), 0.1),
        "c1_b": jnp.zeros((10,), jnp.float32),
        "c2_w": _tn(ks[1], (5, 5, 10, 20), 0.1),
        "c2_b": jnp.zeros((20,), jnp.float32),
        "f1_w": _tn(ks[2], (320, 50), 1.0 / math.sqrt(320)),
        "f1_b": jnp.zeros((50,), jnp.float32),
        "f2_w": _tn(ks[3], (50, 10), 1.0 / math.sqrt(50)),
        "f2_b": jnp.zeros((10,), jnp.float32),
    }


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return y + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def logits(p, x):
    x = x.astype(p["c1_w"].dtype)
    x = _pool(jax.nn.relu(_conv(x, p["c1_w"], p["c1_b"])))
    x = _pool(jax.nn.relu(_conv(x, p["c2_w"], p["c2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.matmul(x, p["f1_w"], precision=HIGHEST) + p["f1_b"])
    return jnp.matmul(x, p["f2_w"], precision=HIGHEST) + p["f2_b"]


def loss(p, x, y):
    lp = jax.nn.log_softmax(logits(p, x).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=1))


def accuracy(p, x, y):
    return jnp.mean((jnp.argmax(logits(p, x), -1) == y).astype(jnp.float32))


def make_epoch(lr: float, batch: int, dtype, batch_used: int = 0):
    """One local epoch of a group of devices: (bank, x, y, keys) -> bank,
    each device stepping through whole minibatches of the permutation
    its own key draws. ``batch_used`` < ``batch`` plants the check's
    half-batch fault (only the first rows of each minibatch enter the
    mean)."""
    used = batch_used or batch

    def device_epoch(p, x, y, key):
        nb = x.shape[0] // batch
        perm = jax.random.permutation(key, x.shape[0])
        idx = perm[:nb * batch].reshape(nb, batch)[:, :used]

        def step(p, bidx):
            g = jax.grad(loss)(p, x[bidx], y[bidx])
            return jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              - lr * b.astype(jnp.float32)).astype(dtype),
                p, g), None

        return jax.lax.scan(step, p, idx)[0]

    return jax.jit(jax.vmap(device_epoch))


def weighted_mean(bank, w, seg, n_seg: int):
    """Per segment sum_i w_i x_i / sum_i w_i, in float32."""
    def one(a):
        a32 = a.astype(jnp.float32).reshape(a.shape[0], -1)
        s = jax.ops.segment_sum(a32 * w[:, None], seg, n_seg)
        ws = jax.ops.segment_sum(w, seg, n_seg)
        return (s / ws[:, None]).reshape((n_seg,) + a.shape[1:])

    return jax.tree.map(one, bank)


def cloud_round(model, x, y, sizes, edge_assign, g1, g2, key, epoch,
                max_g1: int, max_g2: int, dtype, aggregate: bool = True):
    """One cloud round from the global ``model``; returns the new one.

    Every edge period t2 < max_g2 draws a key; every local epoch
    e < max_g1 inside it draws one more. Devices of edge j train while
    t2 < g2[j] and e < g1[j]; after each period an edge still within
    its g2 takes the size-weighted mean of its devices, whose models
    then restart from it. The cloud takes the size-weighted mean of the
    edges. ``aggregate=False`` plants the check's fault of an exchange
    left out: device 0's model comes back as the global one."""
    ea = np.asarray(edge_assign)
    g1, g2 = np.asarray(g1), np.asarray(g2)
    n, m = ea.shape[0], g1.shape[0]
    sizes = jnp.asarray(sizes, jnp.float32)
    ea_j = jnp.asarray(ea, jnp.int32)
    bank = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape)
                        .astype(dtype), model)
    edges = jax.tree.map(lambda a: jnp.broadcast_to(a, (m,) + a.shape)
                         .astype(jnp.float32), model)
    groups = [np.flatnonzero(ea == j) for j in range(m)]
    for t2 in range(max_g2):
        key, sub = jax.random.split(key)
        for e in range(max_g1):
            sub, ek = jax.random.split(sub)
            keys = jax.random.split(ek, n)       # one per device
            # only the devices of edges that train in this epoch
            for j in range(m):
                rows = groups[j]
                if t2 < g2[j] and e < g1[j] and rows.size:
                    new = epoch(jax.tree.map(lambda a: a[rows], bank),
                                x[rows], y[rows], keys[rows])
                    bank = jax.tree.map(lambda a, b: a.at[rows].set(b),
                                        bank, new)
        if not aggregate:
            continue
        live = t2 < g2
        new = weighted_mean(bank, sizes, ea_j, m)
        edges = jax.tree.map(
            lambda o, nw: jnp.where(
                jnp.asarray(live).reshape((-1,) + (1,) * (o.ndim - 1)),
                nw, o), edges, new)
        bank = jax.tree.map(lambda a: a[ea_j].astype(dtype), edges)
    if not aggregate:
        return jax.tree.map(lambda a: a[0].astype(jnp.float32), bank)
    edge_sizes = jax.ops.segment_sum(sizes, ea_j, m)
    return jax.tree.map(lambda a: a[0], weighted_mean(
        edges, edge_sizes, jnp.zeros((m,), jnp.int32), 1))
