"""Hierarchical FL aggregation (paper §2.1, Eqs. 1, 2, 5).

The *model bank* holds every device's parameters as one pytree whose
leaves carry a leading ``N_devices`` axis; device-local training vmaps
over it. Edge aggregation (Eq. 1) is a dataset-size-weighted segment-sum
over the bank; cloud aggregation (Eq. 2) the same over edge models.

Aggregation routes through the **flat-bank engine**
(``repro.core.flatbank`` + the ``segment_agg`` / ``segment_broadcast``
Pallas kernels): the bank pytree is flattened once per round into a
single ``(N, P)`` matrix, the weighted segment means run as one fused
kernel launch per aggregation (normalization in-kernel, no per-leaf f32
temporaries), and the edge->device resync is a fused gather emitted
directly in the bank's storage dtype. The old per-leaf tree path lives
on as the parity oracle ``repro.kernels.ref.weighted_aggregate_ref``.

Per-edge frequencies (γ1_j, γ2_j) are traced values — one compiled
``hfl_cloud_round`` serves every action the agent picks. Its loops take
their trip counts from the action: the γ2 loop runs max_j γ2_j steps and
each step's epoch loop max γ1 over the edges still active in it; a
device whose own γ1 is smaller is masked. The iterations left out are
the tail of each loop, so the key chain and the result are those of
loops run to the ceilings ``max_g1``/``max_g2`` (``round_epochs``
counts what a round runs). ``make_cloud_round``
and ``make_fedavg_round`` return jit-compiled rounds that donate the
incoming bank buffer, so steady-state training re-uses the bank
allocation instead of copying it every round.

Multi-host banks — the **AggContext contract**: every aggregation entry
point and round factory takes an optional ``ctx: AggContext``, the one
object that carries the placement policy (mesh + the
``flatbank.ShardedBankSpec`` row layout + buffer-donation policy).
Build it once — ``AggContext.for_mesh(mesh)`` or
``AggContext.single_chip()`` — and thread it everywhere; the old
per-call ``mesh=`` kwargs survive as one-cycle deprecation shims.

With a sharded context the bank's device axis is sharded over all the
mesh's axes (layout contract: ``flatbank.ShardedBankSpec``). The round
body is the *same program* compiled under GSPMD with row-sharded in/out
shardings — device-local training partitions trivially on the row axis
(and so keeps exact RNG parity with the single-chip path) — while the
Pallas launches, which GSPMD cannot partition, are wrapped in
``shard_map``: each shard runs ``segment_agg`` on its local rows and the
partial edge sums meet in an axis-scoped ``psum``
(``segment_agg_sharded``); the edge->device resync is a shard-local
``segment_broadcast`` of the replicated edge matrix, so the full (N, P)
bank never materializes on one device. Small (E, P)-scale aggregations
(the cloud step, staleness-buffer flushes) instead run the plain kernel
replicated on every shard (``AggContext.segment_agg_small``) — bitwise
identical to the single-chip launch for *any* row count. Without a mesh
the single-chip path is unchanged.

Bitwise contract of the sharded paths: zero-weight rows and zero psum
partials are reduction-neutral (``fma(0, x, acc) == acc``), so when
every edge's rows live within a single shard — the
``flatbank.ShardedBankSpec`` layout contract — the psum-combined
aggregation reproduces the single-chip accumulation chain exactly and
the sharded round matches the single-chip round **bit for bit**
(tests/test_sharded_bank.py pins this for the async edge round on
1/2/4-shard and 2x2 meshes). An edge spanning shards splits the chain
at a psum and parity drops to tolerance-level (f32 reduction order).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import flatbank
from repro.kernels import ops


# ---------------------------------------------------------------------------
# model bank
# ---------------------------------------------------------------------------

def init_bank(init_fn: Callable, key, n_devices: int):
    """Replicates one init across devices (all start from w(0))."""
    p0 = init_fn(key)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_devices,) + a.shape), p0)


def broadcast_model(model, n: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), model)


def bank_select(bank, i: int):
    return jax.tree.map(lambda a: a[i], bank)


# ---------------------------------------------------------------------------
# aggregation (Eqs. 1 and 2) — flat-bank path (single-chip or sharded)
# ---------------------------------------------------------------------------

def _mesh_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _check_rows(n: int, mesh) -> None:
    flatbank.local_rows(n, mesh)     # one shared divisibility contract


@functools.lru_cache(maxsize=None)
def _smap_segment_agg(mesh, num_segments: int):
    """shard_map of the sharded segment_agg for one mesh: rows of
    (bank, weights, segment_ids) sharded over all mesh axes, (E, P)
    output replicated (post-psum). Composable inside a larger jit."""
    axes = _mesh_axes(mesh)
    row, rep = P(axes), P()
    return shard_map(
        lambda m, w, s: ops.segment_agg_sharded(m, w, s, num_segments,
                                                axes),
        mesh=mesh, in_specs=(row, row, row), out_specs=rep,
        check_vma=False)


@functools.lru_cache(maxsize=None)
def _smap_segment_agg_rep(mesh, num_segments: int):
    """shard_map of the plain segment_agg on fully replicated inputs —
    the (E, P)-level aggregations are tiny, every shard just computes
    them identically (keeps the Pallas launch out of GSPMD's hands)."""
    rep = P()
    return shard_map(
        lambda m, w, s: ops.segment_agg(m, w, s, num_segments),
        mesh=mesh, in_specs=(rep, rep, rep), out_specs=rep,
        check_vma=False)


@functools.lru_cache(maxsize=None)
def _smap_segment_broadcast(mesh, out_dtype):
    """shard_map of the shard-local bank resync: replicated (E, P) edge
    models x row-sharded segment ids -> row-sharded (N, P) bank. Each
    shard gathers only its own rows — no full-bank broadcast."""
    axes = _mesh_axes(mesh)
    row, rep = P(axes), P()
    return shard_map(
        lambda m, s: ops.segment_broadcast(m, s, out_dtype=out_dtype),
        mesh=mesh, in_specs=(rep, row), out_specs=row, check_vma=False)


@functools.lru_cache(maxsize=None)
def _sharded_segment_agg(mesh, num_segments: int):
    """jit'd standalone entry point (weighted_aggregate's mesh path).
    Explicit in_shardings commit host arrays to the row layout before
    the shard_map runs."""
    from jax.sharding import NamedSharding
    row = NamedSharding(mesh, P(_mesh_axes(mesh)))
    rep = NamedSharding(mesh, P())
    return jax.jit(_smap_segment_agg(mesh, num_segments),
                   in_shardings=(row, row, row), out_shardings=rep)


@functools.lru_cache(maxsize=None)
def _rep_segment_agg(mesh, num_segments: int):
    """jit'd replicated launch: the plain ``segment_agg`` computed
    identically on every shard (``AggContext.segment_agg_small``'s mesh
    path). Same launch shape as single chip -> bitwise-identical result
    for any row count, and the (E, P)-scale inputs are tiny."""
    from jax.sharding import NamedSharding
    rep = NamedSharding(mesh, P())
    return jax.jit(_smap_segment_agg_rep(mesh, num_segments),
                   in_shardings=(rep, rep, rep), out_shardings=rep)


@functools.lru_cache(maxsize=None)
def _jit_masked_resync(mesh, out_dtype):
    """jit'd sharded ``masked_resync``: replicated (E, P) edge matrix,
    row-sharded bank / segment ids, replicated alive mask -> row-sharded
    bank. The ``segment_broadcast`` is shard-local (each shard gathers
    only its own rows); the keep/overwrite ``where`` partitions on the
    row axis under GSPMD."""
    from jax.sharding import NamedSharding
    row = NamedSharding(mesh, P(_mesh_axes(mesh)))
    rep = NamedSharding(mesh, P())

    def resync(edge_mat, bank_mat, edge_assign, alive):
        out = _smap_segment_broadcast(mesh, out_dtype)(edge_mat,
                                                       edge_assign)
        keep = alive[edge_assign]
        return jnp.where(keep[:, None], out, bank_mat)

    return jax.jit(resync, in_shardings=(rep, row, row, rep),
                   out_shardings=row)


# ---------------------------------------------------------------------------
# AggContext — the one aggregation/placement contract
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AggContext:
    """The aggregation contract every ``hfl`` entry point runs under.

    One frozen, hashable object in place of the ``mesh=`` kwarg sprawl:
    it carries the mesh (or ``None`` for single chip), the
    ``flatbank.ShardedBankSpec`` row-layout policy (bank rows shard over
    *all* mesh axes; edge/global models replicate), and whether round
    factories donate the incoming bank buffer. Build it once —
    :meth:`for_mesh` / :meth:`single_chip` — and pass it to
    ``weighted_aggregate`` / ``cloud_aggregate`` / ``masked_resync`` /
    ``make_cloud_round`` / ``make_edge_round`` / ``make_fedavg_round``,
    to ``runtime.buffer.StalenessBuffer(ctx=...)``, and to
    ``sim.EnvConfig(agg=...)``.
    """
    mesh: Optional[object] = None        # jax.sharding.Mesh | None
    donate: bool = True

    # -- constructors -------------------------------------------------
    @classmethod
    def single_chip(cls, *, donate: bool = True) -> "AggContext":
        """No mesh: every entry point takes the unchanged one-device
        path and the placement helpers are identities."""
        return cls(mesh=None, donate=donate)

    @classmethod
    def for_mesh(cls, mesh, *, donate: bool = True) -> "AggContext":
        """Sharded context over ``mesh`` (usually
        ``launch.mesh.make_bank_mesh`` / ``derive_bank_mesh``): bank
        rows shard over all its axes."""
        if mesh is None:
            raise ValueError("AggContext.for_mesh needs a mesh; use "
                             "AggContext.single_chip() for one device")
        try:
            axes = tuple(mesh.axis_names)
            n_dev = int(mesh.size)
        except (AttributeError, TypeError) as e:
            raise TypeError(f"AggContext.for_mesh expects a "
                            f"jax.sharding.Mesh, got {type(mesh).__name__}"
                            ) from e
        if not axes or n_dev < 1:
            raise ValueError("AggContext.for_mesh: mesh has no axes or "
                             "no devices")
        return cls(mesh=mesh, donate=donate)

    # -- introspection ------------------------------------------------
    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def axes(self) -> tuple:
        """Mesh axis names the bank rows shard over (() on one chip)."""
        return () if self.mesh is None else tuple(self.mesh.axis_names)

    @property
    def n_shards(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.size)

    def check_rows(self, n: int) -> int:
        """Raise ValueError unless ``n`` rows divide over the shards
        (the single shared divisibility contract); returns the rows per
        shard (``n`` itself on single chip)."""
        if self.mesh is None:
            return int(n)
        return flatbank.local_rows(n, self.mesh)

    def donate_argnums(self, *argnums: int) -> tuple:
        return tuple(argnums) if self.donate else ()

    # -- placement policy (flatbank.ShardedBankSpec layout) -----------
    def row_sharding(self):
        """NamedSharding for row-axis data; None on a single chip."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P(self.axes))

    def replicated_sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P())

    def place_rows(self, arr):
        """Commit an array with a leading device-row axis to the row
        layout (identity on one chip)."""
        if self.mesh is None:
            return arr
        self.check_rows(jax.tree.leaves(arr)[0].shape[0])
        return jax.device_put(arr, self.row_sharding())

    def place_replicated(self, tree):
        """Replicate a pytree on every shard (identity on one chip)."""
        if self.mesh is None:
            return tree
        rep = self.replicated_sharding()
        return jax.tree.map(lambda a: jax.device_put(a, rep), tree)

    def place_bank(self, bank):
        """Shard a model bank's leaves row-wise (identity on one chip);
        validates the ``ShardedBankSpec`` layout contract."""
        if self.mesh is None:
            return bank
        return flatbank.sharded_bank_spec(bank, self.mesh).place_bank(bank)

    # -- kernel routing -----------------------------------------------
    def segment_agg_small(self, mat, weights, segment_ids,
                          num_segments: int):
        """Aggregate a *small* (K, P) stack (edge matrices, staleness
        flushes): the plain fused kernel, computed replicated on every
        shard under a mesh — bitwise-identical to the single-chip
        launch for any K (no psum, no divisibility condition)."""
        if self.mesh is None:
            return ops.segment_agg(mat, weights, segment_ids,
                                   num_segments)
        return _rep_segment_agg(self.mesh, int(num_segments))(
            mat, weights, segment_ids)


def _resolve_ctx(ctx, mesh, where: str) -> AggContext:
    """Normalize the (ctx, deprecated mesh kwarg) pair every entry
    point accepts into one AggContext."""
    if ctx is not None and mesh is not None:
        raise ValueError(f"{where}: pass ctx=AggContext(...) or the "
                         f"deprecated mesh=, not both")
    if mesh is not None:
        warnings.warn(
            f"{where}(mesh=...) is deprecated; build the context once "
            f"with hfl.AggContext.for_mesh(mesh) and pass ctx= instead "
            f"(the mesh= kwarg goes away next cycle)",
            DeprecationWarning, stacklevel=3)
        return AggContext.for_mesh(mesh)
    if ctx is None:
        return AggContext.single_chip()
    if not isinstance(ctx, AggContext):
        raise TypeError(f"{where}: ctx must be an hfl.AggContext, got "
                        f"{type(ctx).__name__}")
    return ctx


def weighted_aggregate(bank, weights, segment_ids, num_segments: int,
                       *, ctx: Optional[AggContext] = None, mesh=None):
    """Generic dataset-size-weighted aggregation on the flat bank.

    bank leaves: (N, ...); weights: (N,) |D_i|; segment_ids: (N,) edge of
    each device. Returns pytree with leading ``num_segments`` axis:
        out_j = sum_{i in j} w_i x_i / sum_{i in j} w_i          (Eq. 1)

    One ``segment_agg`` kernel launch over the flattened ``(N, P)``
    bank; leaf dtypes are restored on unflatten. With a sharded ``ctx``
    the rows shard over the mesh and each shard launches on its local
    rows only (partial sums combined by ``psum``); the result is
    replicated.
    """
    ctx = _resolve_ctx(ctx, mesh, "weighted_aggregate")
    spec = flatbank.bank_spec(bank)
    mat = spec.flatten(bank)
    if ctx.mesh is None:
        out = ops.segment_agg(mat, weights, segment_ids, num_segments)
    else:
        ctx.check_rows(mat.shape[0])
        out = _sharded_segment_agg(ctx.mesh, int(num_segments))(
            mat, weights, segment_ids)
    return spec.unflatten(out)


def edge_aggregate(bank, device_sizes, edge_assign, n_edges: int,
                   *, ctx: Optional[AggContext] = None, mesh=None):
    """Eq. 1: w_j^e = Σ_i |D_i| w_i / Σ_i |D_i| over the devices of edge j."""
    ctx = _resolve_ctx(ctx, mesh, "edge_aggregate")
    return weighted_aggregate(bank, device_sizes, edge_assign, n_edges,
                              ctx=ctx)


def cloud_aggregate(edge_models, edge_sizes, *,
                    ctx: Optional[AggContext] = None, mesh=None):
    """Eq. 2: w = Σ_j |D_j| w_j^e / Σ_j |D_j| (single segment). The edge
    matrix is small, so under a mesh every shard computes the plain
    launch replicated (``AggContext.segment_agg_small``) — bitwise
    identical to single chip for any number of edges."""
    ctx = _resolve_ctx(ctx, mesh, "cloud_aggregate")
    n = edge_sizes.shape[0]
    spec = flatbank.bank_spec(edge_models)
    seg = jnp.zeros((n,), jnp.int32)
    out = ctx.segment_agg_small(spec.flatten(edge_models), edge_sizes,
                                seg, 1)
    return spec.unflatten_model(out[0])


def masked_resync(edge_mat, bank_mat, edge_assign, alive, *,
                  ctx: Optional[AggContext] = None):
    """Fault-tolerant edge→device resync: broadcast the ``(E, P)`` edge
    matrix to the ``(N, P)`` bank through ``segment_broadcast``, but
    only onto rows of *alive* edges — rows belonging to dropped /
    departed edges come back **bit-identical** (their devices are
    offline; overwriting their in-flight state would corrupt a later
    rejoin). ``alive``: (E,) bool. With ``alive`` all-True this is
    exactly the plain resync.

    Used by the async runtime's churn handling (a rejoining edge's rows
    sync to the current global model while every other row stays put)
    and available to degraded synchronous rounds.

    With a sharded ``ctx`` the bank matrix and segment ids stay
    row-sharded end to end: the broadcast is shard-local and the
    keep/overwrite ``where`` partitions on the row axis, so the full
    bank never gathers onto one device and the result is bitwise the
    single-chip one (the gather copies one edge row per device row).
    """
    ctx = _resolve_ctx(ctx, None, "masked_resync")
    if ctx.mesh is None:
        out = ops.segment_broadcast(edge_mat, edge_assign,
                                    out_dtype=bank_mat.dtype)
        keep = jnp.asarray(alive, bool)[edge_assign]
        return jnp.where(keep[:, None], out, bank_mat)
    ctx.check_rows(bank_mat.shape[0])
    return _jit_masked_resync(ctx.mesh, jnp.dtype(bank_mat.dtype))(
        edge_mat, bank_mat, jnp.asarray(edge_assign, jnp.int32),
        jnp.asarray(alive, bool))


# ---------------------------------------------------------------------------
# device-local training (vmapped SGD epochs)
# ---------------------------------------------------------------------------

def make_local_trainer(loss_fn: Callable, lr: float, batch_size: int):
    """Returns ``local_train(bank, x, y, gamma1_dev, max_g1, key)``.

    loss_fn(params, batch) -> scalar. One 'epoch' = one pass over the
    device's local shard in shuffled minibatches (the paper's unit: γ1
    epochs of local SGD between edge aggregations).
    gamma1_dev: (N,) traced per-device epoch counts. The loop runs
    max_i γ1_i epochs (at most ``max_g1``, the action's ceiling), and
    a device whose γ1 is smaller keeps its model through the epochs
    past it.

    The same function serves sharded rounds: under GSPMD with bank/x/y
    row-sharded, the vmapped epoch partitions on the device axis and the
    (replicated) key chain is identical to the single-chip program — so
    sharded training is bit-compatible with one-chip training.
    """

    def device_epoch(params, x, y, perm):
        nb = x.shape[0] // batch_size
        idx = perm[:nb * batch_size].reshape(nb, batch_size)

        def step(p, bidx):
            g = jax.grad(loss_fn)(p, {"x": x[bidx], "y": y[bidx]})
            with jax.named_scope("hfl.update"):
                return jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - lr * b.astype(jnp.float32)
                                  ).astype(a.dtype), p, g), None

        params, _ = jax.lax.scan(step, params, idx)
        return params

    def local_train(bank, x, y, gamma1_dev, max_g1: int, key):
        n, n_local = x.shape[0], x.shape[1]

        @jax.named_scope("hfl.local")
        def one_epoch(e, carry):
            bank, key = carry
            key, sub = jax.random.split(key)
            perms = jax.vmap(
                lambda k: jax.random.permutation(k, n_local))(
                    jax.random.split(sub, n))
            new = jax.vmap(device_epoch)(bank, x, y, perms)
            active = (e < gamma1_dev)

            def mask(a, b):
                am = active.reshape((-1,) + (1,) * (a.ndim - 1))
                return jnp.where(am, b, a)

            bank = jax.tree.map(mask, bank, new)
            return bank, key

        n_epochs = jnp.minimum(jnp.max(gamma1_dev), max_g1)
        bank, _ = jax.lax.fori_loop(0, n_epochs, one_epoch, (bank, key))
        return bank

    return local_train


# ---------------------------------------------------------------------------
# one cloud round (Eq. 5 composition)
# ---------------------------------------------------------------------------

def _jit_round(fn, mesh, n_row_args: int, donate: tuple):
    """jit a round function. Single chip: plain jit with donation. With
    a mesh: the first ``n_row_args`` arguments are row-sharded over all
    mesh axes (bank, data shards, per-device vectors), the rest
    replicated; the first output (the bank) is constrained to stay
    row-sharded, the rest (global/edge models) replicated. A thin
    wrapper validates row-count divisibility before dispatch."""
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate)
    from jax.sharding import NamedSharding
    row = NamedSharding(mesh, P(_mesh_axes(mesh)))
    rep = NamedSharding(mesh, P())

    def constrained(*args):
        out = fn(*args)
        bank = jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, row), out[0])
        rest = jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, rep), out[1:])
        return (bank,) + rest

    state = {}

    def call(*args):
        _check_rows(jax.tree.leaves(args[0])[0].shape[0], mesh)
        if "jitted" not in state:
            in_sh = (row,) * n_row_args + (rep,) * (len(args) - n_row_args)
            state["jitted"] = jax.jit(constrained, in_shardings=in_sh,
                                      donate_argnums=donate)
        return state["jitted"](*args)

    return call


def round_epochs(g1, g2, edge_assign, max_g1: int, max_g2: int) -> int:
    """Device-epochs one ``make_cloud_round`` round computes for the
    action (g1 (M,), g2 (M,)): every device runs each epoch of the
    loops, N × Σ_{t2 < max_j γ2_j} max{γ1_i : γ2_i > t2} over its
    devices i, with the round's ceilings applied as its loops apply
    them. The masked epochs are counted; the useful ones are Σ_i γ1_i γ2_i.
    """
    ea = np.asarray(edge_assign)
    g1_dev = np.asarray(g1)[ea]
    g2_dev = np.asarray(g2)[ea]
    steps = min(int(np.max(g2)), max_g2)
    return ea.size * sum(min(int(np.max(np.where(t2 < g2_dev, g1_dev, 0))),
                             max_g1) for t2 in range(steps))


def make_cloud_round(loss_fn: Callable, lr: float, batch_size: int,
                     n_edges: int, max_g1: int, max_g2: int,
                     ctx: Optional[AggContext] = None, mesh=None):
    """Builds a jit-compiled ``cloud_round`` (bank buffer donated):

    cloud_round(bank, x, y, sizes, edge_assign, g1 (M,), g2 (M,), key)
      -> (bank synced to the new global model, global model, edge models)

    Composition per Eq. 5: for t2 < γ2_j, devices of edge j run γ1_j local
    epochs then edge-aggregate; edges past their γ2_j freeze; finally the
    cloud aggregates the edge models and broadcasts. The t2 loop runs
    max_j γ2_j steps and step t2's epoch loop max{γ1_j : γ2_j > t2}
    epochs (each at most its ceiling ``max_g2``/``max_g1``), so a round
    computes ``round_epochs`` device-epochs.

    The t2 loop carries the edge models as a flat ``(E, P)`` f32 matrix:
    each step flattens the trained bank once, edge-aggregates in one
    ``segment_agg`` launch, masks frozen edges with a single 2-D
    ``where``, and resyncs the bank through ``segment_broadcast`` — no
    per-leaf tree traffic inside the loop.

    With a sharded ``ctx`` the same body compiles under GSPMD with bank
    rows, data shards, sizes, and edge assignment partitioned over the
    mesh axes: training partitions trivially (identical key material to
    the single-chip program), the edge aggregation runs as per-shard
    ``segment_agg`` launches whose partial sums meet in a ``psum``
    (``shard_map``-wrapped), and the resync ``segment_broadcast`` is
    shard-local — the full (N, P) bank never lands on one device. The
    returned global/edge models are replicated; the returned bank stays
    row-sharded.
    """
    ctx = _resolve_ctx(ctx, mesh, "make_cloud_round")
    mesh = ctx.mesh
    local_train = make_local_trainer(loss_fn, lr, batch_size)

    def cloud_round(bank, x, y, sizes, edge_assign, g1, g2, key):
        spec = flatbank.bank_spec(bank)
        g1_dev = g1[edge_assign]
        g2_dev = g2[edge_assign]

        if mesh is None:
            agg = lambda mat: ops.segment_agg(mat, sizes, edge_assign,
                                              n_edges)
            agg1 = lambda em, w: ops.segment_agg(
                em, w, jnp.zeros((n_edges,), jnp.int32), 1)
            resync = lambda em: ops.segment_broadcast(
                em, edge_assign, out_dtype=spec.dtype)
        else:
            agg = lambda mat: _smap_segment_agg(mesh, n_edges)(
                mat, sizes, edge_assign)
            agg1 = lambda em, w: _smap_segment_agg_rep(mesh, 1)(
                em, w, jnp.zeros((n_edges,), jnp.int32))
            resync = lambda em: _smap_segment_broadcast(mesh, spec.dtype)(
                em, edge_assign)

        def t2_step(t2, carry):
            bank, edge_mat, key = carry
            key, sub = jax.random.split(key)
            active_dev = t2 < g2_dev
            g1_eff = jnp.where(active_dev, g1_dev, 0)
            bank = local_train(bank, x, y, g1_eff, max_g1, sub)
            with jax.named_scope("hfl.edge_agg"):
                a = agg(spec.flatten(bank))
                active_edge = (t2 < g2).reshape(-1, 1)
                edge_mat = jnp.where(active_edge, a, edge_mat)
                # devices resume from their edge's current model (each
                # shard gathers only its own rows under the mesh path)
                bank = spec.unflatten(resync(edge_mat))
            return bank, edge_mat, key

        with jax.named_scope("hfl.edge_agg"):
            edge_mat0 = agg(spec.flatten(bank))
        # from the replicated (M,) action: under a mesh the trip count
        # needs no cross-shard reduction
        n_steps = jnp.minimum(jnp.max(g2), max_g2)
        bank, edge_mat, _ = jax.lax.fori_loop(
            0, n_steps, t2_step, (bank, edge_mat0, key))
        with jax.named_scope("hfl.cloud_agg"):
            edge_sizes = jax.ops.segment_sum(sizes, edge_assign, n_edges)
            glob = agg1(edge_mat, edge_sizes)[0]
            global_model = spec.unflatten_model(glob)
            bank = broadcast_model(global_model, x.shape[0])
        with jax.named_scope("hfl.edge_agg"):
            edge_models = spec.unflatten(edge_mat)
        return bank, global_model, edge_models

    return _jit_round(cloud_round, mesh, n_row_args=5,
                      donate=ctx.donate_argnums(0))


# ---------------------------------------------------------------------------
# one edge-local round — the async runtime's unit of work
# ---------------------------------------------------------------------------

def make_edge_round(loss_fn: Callable, lr: float, batch_size: int,
                    n_edges: int, max_g1: int, max_g2: int,
                    ctx: Optional[AggContext] = None):
    """Builds a jit-compiled *edge-local* round (bank buffer donated):

    edge_round(bank, x, y, sizes, edge_assign, edge_id, g1, g2,
               global_vec, key) -> (bank, edge_vec (P,) f32)

    The async runtime's unit of work (repro.runtime): edge ``edge_id``'s
    devices seed from the flat global snapshot ``global_vec`` (the model
    version the edge last downloaded), run gamma2 edge syncs of gamma1
    local epochs, and return their edge aggregate as a flat ``(P,)``
    update for the cloud's staleness buffer. Rows of other edges are
    carried untouched (the bank is a shared scratch buffer across
    interleaved edge rounds).

    Bitwise contract with ``make_cloud_round``: the loop body, key
    chain, and kernel launches are the *same program* restricted to one
    edge — masked weights zero the other edges out of the one-hot
    matmuls, and the loops run this edge's γ2 steps of γ1 epochs, the
    head of the synchronous round's loops — so with every edge starting
    from the same ``global_vec`` and the same ``key``, edge ``j``'s
    returned update equals row ``j`` of the synchronous round's edge
    matrix bit for bit (the async-parity test in
    tests/test_async_runtime.py pins this).

    With a sharded ``ctx`` the round compiles under GSPMD exactly like
    ``make_cloud_round``: bank/data/sizes/assignment row-sharded,
    training in plain GSPMD (identical key chain — the RNG/grad chain
    must *never* move inside ``shard_map``, see ROADMAP's PR-2
    caution), the masked-weight edge aggregation as per-shard
    ``segment_agg`` launches + psum, and the resync as the shard-local
    ``segment_broadcast``. Because the mask zeroes every other edge and
    zero rows/partials are reduction-neutral, the sharded round
    reproduces the single-chip round **bitwise** whenever the active
    edge's rows live within one shard — the ``ShardedBankSpec`` layout
    contract (tests/test_sharded_bank.py pins this on 1/2/4-shard and
    2x2 meshes). The returned bank stays row-sharded; ``edge_vec`` is
    replicated.

    ``edge_id``/``g1``/``g2`` are traced scalars — one compiled round
    serves every (edge, action) pair the agent picks.
    """
    ctx = _resolve_ctx(ctx, None, "make_edge_round")
    mesh = ctx.mesh
    local_train = make_local_trainer(loss_fn, lr, batch_size)

    def edge_round(bank, x, y, sizes, edge_assign, edge_id, g1, g2,
                   global_vec, key):
        spec = flatbank.bank_spec(bank)
        row_active = (edge_assign == edge_id)
        w = sizes * row_active.astype(sizes.dtype)
        g1_dev = jnp.where(row_active, g1, 0)
        g2_dev = jnp.where(row_active, g2, 0)

        if mesh is None:
            agg = lambda mat: ops.segment_agg(mat, w, edge_assign,
                                              n_edges)
            resync = lambda em: ops.segment_broadcast(
                em, edge_assign, out_dtype=spec.dtype)
        else:
            agg = lambda mat: _smap_segment_agg(mesh, n_edges)(
                mat, w, edge_assign)
            resync = lambda em: _smap_segment_broadcast(mesh, spec.dtype)(
                em, edge_assign)

        # devices resume from the global snapshot the edge downloaded
        mat = spec.flatten(bank)
        mat = jnp.where(row_active[:, None],
                        global_vec[None, :].astype(mat.dtype), mat)
        bank = spec.unflatten(mat)
        row_mask = row_active.reshape(-1, 1)
        edge_1h = (jnp.arange(n_edges) == edge_id).reshape(-1, 1)

        def t2_step(t2, carry):
            bank, edge_mat, key = carry
            key, sub = jax.random.split(key)
            active_dev = t2 < g2_dev
            g1_eff = jnp.where(active_dev, g1_dev, 0)
            bank = local_train(bank, x, y, g1_eff, max_g1, sub)
            with jax.named_scope("hfl.edge_agg"):
                a = agg(spec.flatten(bank))
                active_edge = jnp.logical_and(t2 < g2, edge_1h)
                edge_mat = jnp.where(active_edge, a, edge_mat)
                # resync only this edge's rows; the rest of the bank is
                # other edges' in-flight state and must not move
                mat = jnp.where(row_mask, resync(edge_mat),
                                spec.flatten(bank))
                bank = spec.unflatten(mat)
            return bank, edge_mat, key

        with jax.named_scope("hfl.edge_agg"):
            edge_mat0 = agg(spec.flatten(bank))
        bank, edge_mat, _ = jax.lax.fori_loop(
            0, jnp.minimum(g2, max_g2), t2_step, (bank, edge_mat0, key))
        edge_vec = jnp.take(edge_mat, edge_id, axis=0)
        return bank, edge_vec

    return _jit_round(edge_round, mesh, n_row_args=5,
                      donate=ctx.donate_argnums(0))


# ---------------------------------------------------------------------------
# Vanilla-FL (FedAvg) round — the paper's two-layer baseline
# ---------------------------------------------------------------------------

def make_fedavg_round(loss_fn: Callable, lr: float, batch_size: int,
                      max_g1: int, ctx: Optional[AggContext] = None,
                      mesh=None):
    """FedAvg with random participation: selected devices run γ1 local
    epochs, the cloud aggregates them directly (γ2 ≡ 1). Jit-compiled,
    bank donated; the single-segment aggregation runs on the flat bank.
    With a sharded ``ctx`` the round compiles under GSPMD like
    ``make_cloud_round`` (row-sharded bank and data, per-shard kernel +
    psum aggregation, replicated global model)."""
    ctx = _resolve_ctx(ctx, mesh, "make_fedavg_round")
    mesh = ctx.mesh
    local_train = make_local_trainer(loss_fn, lr, batch_size)

    def round_(bank, x, y, sizes, participate, g1, key):
        n = x.shape[0]
        spec = flatbank.bank_spec(bank)
        g1_dev = jnp.where(participate, g1, 0)
        bank = local_train(bank, x, y, g1_dev, max_g1, key)
        with jax.named_scope("hfl.cloud_agg"):
            w = sizes * participate.astype(sizes.dtype)
            seg = jnp.zeros((n,), jnp.int32)
            if mesh is None:
                glob = ops.segment_agg(spec.flatten(bank), w, seg, 1)[0]
            else:
                glob = _smap_segment_agg(mesh, 1)(spec.flatten(bank), w,
                                                  seg)[0]
            global_model = spec.unflatten_model(glob)
            bank = broadcast_model(global_model, n)
        return bank, global_model

    return _jit_round(round_, mesh, n_row_args=5,
                      donate=ctx.donate_argnums(0))
