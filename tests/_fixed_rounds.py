"""Oracle for ``core/hfl``'s rounds: the Arena rounds with loops of fixed
length, ``max_g2`` edge steps of ``max_g1`` epochs each, whatever the
action. Epochs and steps past a device's (γ1, γ2) are computed and then
masked away. The rounds in ``core/hfl`` run only the epochs some device
still needs; the tests hold them to these bit for bit.

Single chip or under a mesh, the kernels are launched as the rounds
launch them (``ops.segment_agg`` / ``ops.segment_broadcast``, or their
``shard_map`` wrappers), and the rounds are jitted the same way
(``hfl._jit_round``, no donation).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flatbank, hfl
from repro.kernels import ops


def fixed_rounds(loss_fn, lr, batch_size, n_edges, max_g1, max_g2,
                 ctx=None):
    """Returns jitted ``(cloud, edge, fedavg)`` rounds with the argument
    lists of ``hfl.make_cloud_round``, ``make_edge_round`` and
    ``make_fedavg_round``."""
    mesh = (ctx or hfl.AggContext.single_chip()).mesh

    def agg(mat, w, seg, k):
        if mesh is None:
            return ops.segment_agg(mat, w, seg, k)
        return hfl._smap_segment_agg(mesh, k)(mat, w, seg)

    def agg_small(mat, w, seg, k):
        if mesh is None:
            return ops.segment_agg(mat, w, seg, k)
        return hfl._smap_segment_agg_rep(mesh, k)(mat, w, seg)

    def resync(em, seg, dtype):
        if mesh is None:
            return ops.segment_broadcast(em, seg, out_dtype=dtype)
        return hfl._smap_segment_broadcast(mesh, dtype)(em, seg)

    def device_epoch(params, x, y, perm):
        nb = x.shape[0] // batch_size
        idx = perm[:nb * batch_size].reshape(nb, batch_size)

        def step(p, bidx):
            g = jax.grad(loss_fn)(p, {"x": x[bidx], "y": y[bidx]})
            return jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              - lr * b.astype(jnp.float32)
                              ).astype(a.dtype), p, g), None

        return jax.lax.scan(step, params, idx)[0]

    def local_train(bank, x, y, g1_dev, key):
        n, n_local = x.shape[0], x.shape[1]

        def one_epoch(carry, e):
            bank, key = carry
            key, sub = jax.random.split(key)
            perms = jax.vmap(lambda k: jax.random.permutation(k, n_local))(
                jax.random.split(sub, n))
            new = jax.vmap(device_epoch)(bank, x, y, perms)
            act = e < g1_dev
            bank = jax.tree.map(
                lambda a, b: jnp.where(
                    act.reshape((-1,) + (1,) * (a.ndim - 1)), b, a),
                bank, new)
            return (bank, key), None

        return jax.lax.scan(one_epoch, (bank, key),
                            jnp.arange(max_g1))[0][0]

    def t2_loop(bank, x, y, g1_dev, g2_dev, edge_active, w, ea, rows, key):
        """The fixed γ2 loop; ``edge_active(t2)`` -> (E, 1) edges whose
        aggregate replaces theirs, ``rows`` the (N, 1) rows resynced."""
        spec = flatbank.bank_spec(bank)

        def t2_step(carry, t2):
            bank, edge_mat, key = carry
            key, sub = jax.random.split(key)
            bank = local_train(bank, x, y,
                               jnp.where(t2 < g2_dev, g1_dev, 0), sub)
            edge_mat = jnp.where(edge_active(t2),
                                 agg(spec.flatten(bank), w, ea, n_edges),
                                 edge_mat)
            mat = jnp.where(rows, resync(edge_mat, ea, spec.dtype),
                            spec.flatten(bank))
            return (spec.unflatten(mat), edge_mat, key), None

        edge_mat0 = agg(spec.flatten(bank), w, ea, n_edges)
        (bank, edge_mat, _), _ = jax.lax.scan(
            t2_step, (bank, edge_mat0, key), jnp.arange(max_g2))
        return bank, edge_mat

    def cloud(bank, x, y, sizes, ea, g1, g2, key):
        spec = flatbank.bank_spec(bank)
        _, edge_mat = t2_loop(bank, x, y, g1[ea], g2[ea],
                              lambda t2: (t2 < g2).reshape(-1, 1), sizes,
                              ea, jnp.ones((x.shape[0], 1), bool), key)
        glob = agg_small(edge_mat, jax.ops.segment_sum(sizes, ea, n_edges),
                         jnp.zeros((n_edges,), jnp.int32), 1)[0]
        model = spec.unflatten_model(glob)
        return (hfl.broadcast_model(model, x.shape[0]), model,
                spec.unflatten(edge_mat))

    def edge(bank, x, y, sizes, ea, edge_id, g1, g2, global_vec, key):
        spec = flatbank.bank_spec(bank)
        row = ea == edge_id
        mat = jnp.where(row[:, None],
                        global_vec[None, :].astype(spec.dtype),
                        spec.flatten(bank))
        edge_1h = (jnp.arange(n_edges) == edge_id).reshape(-1, 1)
        bank, edge_mat = t2_loop(
            spec.unflatten(mat), x, y, jnp.where(row, g1, 0),
            jnp.where(row, g2, 0),
            lambda t2: jnp.logical_and(t2 < g2, edge_1h),
            sizes * row.astype(sizes.dtype), ea, row.reshape(-1, 1), key)
        return bank, jnp.take(edge_mat, edge_id, axis=0)

    def fedavg(bank, x, y, sizes, participate, g1, key):
        n = x.shape[0]
        spec = flatbank.bank_spec(bank)
        bank = local_train(bank, x, y, jnp.where(participate, g1, 0), key)
        glob = agg(spec.flatten(bank), sizes * participate.astype(
            sizes.dtype), jnp.zeros((n,), jnp.int32), 1)[0]
        model = spec.unflatten_model(glob)
        return hfl.broadcast_model(model, n), model

    return tuple(hfl._jit_round(f, mesh, n_row_args=5, donate=())
                 for f in (cloud, edge, fedavg))


# a tiny bank and the actions the exactness tests cover: (g1 (M,), g2
# (M,), participation (N,)) with max_g1 = max_g2 = 3 and M = 3 edges
MAX_G = 3
ACTIONS = {
    "all_one": ([1, 1, 1], [1, 1, 1], None),
    "all_max": ([3, 3, 3], [3, 3, 3], None),
    "mixed": ([2, 1, 3], [2, 3, 1], None),
    "g2_one_beside_max": ([3, 1, 2], [1, 3, 1], None),
    "non_participating": ([2, 3, 1], [3, 1, 2],
                          [True, False, True, True, True, False]),
}


def tiny_loss(p, batch):
    """Softmax regression on two leaves."""
    lp = jax.nn.log_softmax(batch["x"] @ p["w"] + p["b"])
    return -jnp.mean(jnp.take_along_axis(lp, batch["y"][:, None], 1))


def tiny_case(name, n=6, m=3, seed=0):
    """Bank, data and action of one case for ``tiny_loss``: ``n``
    devices on ``m`` edges (device i on edge i % m), 8 local samples.
    A device that does not participate has size 0."""
    g1, g2, part = ACTIONS[name]
    rng = np.random.default_rng(seed)
    bank = {"w": jnp.asarray(rng.normal(size=(n, 4, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}
    part = np.ones(n, bool) if part is None else np.asarray(part)
    return dict(
        bank=bank,
        x=jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32),
        y=jnp.asarray(rng.integers(0, 3, size=(n, 8)), jnp.int32),
        sizes=jnp.asarray(rng.uniform(1, 3, size=(n,)) * part, jnp.float32),
        part=jnp.asarray(part), ea=jnp.asarray(np.arange(n) % m, jnp.int32),
        g1=jnp.asarray(g1, jnp.int32), g2=jnp.asarray(g2, jnp.int32))


def run_case(rounds, kind, c, key):
    """Runs one kind of round (``"cloud"``, ``"edge"`` for every edge,
    ``"fedavg"`` with each device's edge γ1) of ``rounds``, a dict of
    kind -> round, on case ``c``; returns the outputs' leaves as host
    arrays."""
    fresh = lambda: jax.tree.map(jnp.copy, c["bank"])
    if kind == "cloud":
        out = rounds["cloud"](fresh(), c["x"], c["y"], c["sizes"], c["ea"],
                              c["g1"], c["g2"], key)
    elif kind == "edge":
        gvec = flatbank.bank_spec(c["bank"]).flatten_model(
            jax.tree.map(lambda a: a[0], c["bank"]))
        out = [rounds["edge"](fresh(), c["x"], c["y"], c["sizes"], c["ea"],
                              jnp.int32(j), c["g1"][j], c["g2"][j], gvec,
                              key) for j in range(c["g1"].shape[0])]
    else:
        out = rounds["fedavg"](fresh(), c["x"], c["y"], c["sizes"],
                               c["part"], c["g1"][c["ea"]], key)
    return [np.asarray(a) for a in jax.tree.leaves(out)]
