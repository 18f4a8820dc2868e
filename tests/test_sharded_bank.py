"""Sharded multi-host flat bank: parity of the mesh path against the
single-chip kernels and the per-leaf tree-path oracle
(``ref.weighted_aggregate_ref``) on 1/2/4-shard meshes, uneven
edge->shard splits, bf16 banks, and the no-full-bank placement contract
(the sharded round's output bank stays row-sharded; edge/global models
replicated).

The mesh tests need >1 device. In the sharded-parity CI tier this file
runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(scripts/ci.sh test-sharded) and everything executes in-process. In the
plain tier-1 run (one device) the mesh tests skip and a single wrapper
test re-runs this file in a subprocess with the forced device count, so
tier-1 still covers the sharded engine end to end.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import flatbank, hfl
from repro.kernels import ops, ref
from repro.launch import mesh as mesh_lib

import _fixed_rounds
import _subproc

NDEV = jax.device_count()
needs_mesh = pytest.mark.skipif(
    NDEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(scripts/ci.sh test-sharded); tier-1 covers this via the "
           "subprocess wrapper test")

MESH_SHAPES = [(1, 1), (2, 1), (4, 1), (2, 2)]   # 1/2/4 shards, 2 axes


def _mixed_bank(rng, n):
    """Nested pytree, f32 + bf16 leaves, P = 140 (not lane-aligned)."""
    return {
        "conv": {"w": jnp.asarray(rng.normal(size=(n, 2, 3, 5)),
                                  jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(n, 74)), jnp.bfloat16)},
        "head": [jnp.asarray(rng.normal(size=(n, 5, 7)), jnp.bfloat16),
                 jnp.asarray(rng.normal(size=(n,)), jnp.float32)],
    }


def _assert_tree_close(got, want, f32_tol=1e-5, bf16_tol=2e-2):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        tol = bf16_tol if g.dtype == jnp.bfloat16 else f32_tol
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# mesh construction + spec plumbing (device-count independent parts)
# ---------------------------------------------------------------------------

def test_make_bank_mesh_single():
    m = mesh_lib.make_bank_mesh(1)
    assert dict(m.shape) == {"edge": 1, "fl": 1}


def test_make_bank_mesh_too_many_devices_raises():
    with pytest.raises(ValueError):
        mesh_lib.make_bank_mesh(jax.device_count() + 1)


def test_sharded_bank_spec_plumbing():
    rng = np.random.default_rng(0)
    bank = _mixed_bank(rng, 8)
    sbs = flatbank.sharded_bank_spec(bank, mesh_lib.make_bank_mesh(1))
    assert sbs.axes == ("edge", "fl")
    assert sbs.n_shards == 1
    assert sbs.local_rows(8) == 8
    p = sbs.pspec(3)
    assert p[0] == ("edge", "fl") and p[1] is None and p[2] is None
    specs = jax.tree.leaves(
        sbs.tree_pspecs(bank),
        is_leaf=lambda x: not isinstance(x, (dict, list)))
    assert len(specs) == 4


@needs_mesh
def test_local_rows_divisibility_raises():
    rng = np.random.default_rng(1)
    bank = _mixed_bank(rng, 8)
    sbs = flatbank.sharded_bank_spec(bank, mesh_lib.make_bank_mesh(4))
    assert sbs.local_rows(8) == 2
    with pytest.raises(ValueError):
        sbs.local_rows(7)
    with pytest.raises(ValueError):
        sbs.place_bank(_mixed_bank(rng, 7))


@needs_mesh
def test_derive_bank_mesh_from_hfl_mesh():
    devs = np.array(jax.devices()[:8]).reshape(1, 2, 2, 2, 1)
    hfl_mesh = jax.sharding.Mesh(devs, mesh_lib.HFL_AXES)
    bm = mesh_lib.derive_bank_mesh(hfl_mesh)
    assert dict(bm.shape) == {"edge": 2, "fl": 2}
    with pytest.raises(ValueError):
        mesh_lib.derive_bank_mesh(bm)          # not a 5-axis HFL mesh


# ---------------------------------------------------------------------------
# aggregation parity: sharded vs single-chip vs tree-path oracle
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_weighted_aggregate_sharded_matches_oracle(shape):
    rng = np.random.default_rng(2)
    n, m = 16, 5
    bank = _mixed_bank(rng, n)
    w = jnp.asarray(rng.uniform(0.1, 3.0, size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)), jnp.int32)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    got = hfl.weighted_aggregate(bank, w, seg, m, ctx=ctx)
    want = ref.weighted_aggregate_ref(bank, w, seg, m)
    _assert_tree_close(got, want)
    # and identical (to f32 reduction order) with the single-chip path
    single = hfl.weighted_aggregate(bank, w, seg, m)
    _assert_tree_close(got, single, f32_tol=1e-5, bf16_tol=2e-2)


@needs_mesh
def test_uneven_edge_to_shard_split():
    """Edges straddle shard boundaries and one edge is empty: segment 0
    spans shards 0-2, segment 2 lives in one shard, segment 3 is empty —
    the psum-combined means must still match the oracle exactly."""
    rng = np.random.default_rng(3)
    n, m = 16, 4
    # edge 0: 9 rows (spans shards 0-2), edge 1: 3 rows (straddles the
    # shard 2/3 boundary), edge 2: 4 rows (shard 3), edge 3: empty
    seg = jnp.asarray([0] * 9 + [1] * 3 + [2] * 4, jnp.int32)
    bank = {"w": jnp.asarray(rng.normal(size=(n, 130)), jnp.float32)}
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)), jnp.float32)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(4))
    got = hfl.weighted_aggregate(bank, w, seg, m, ctx=ctx)["w"]
    want = ref.weighted_aggregate_ref(
        {"w": bank["w"]}, w, seg, m)["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert np.abs(np.asarray(got[3])).max() == 0.0      # empty segment


@needs_mesh
@pytest.mark.parametrize("shape", [(2, 1), (2, 2)])
def test_sharded_bf16_bank(shape):
    """A uniformly-bf16 bank stays bf16 through the sharded flat path
    (upcast only inside the kernels / psum in f32)."""
    rng = np.random.default_rng(4)
    n, m = 8, 3
    bank = {"a": jnp.asarray(rng.normal(size=(n, 9)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(size=(n, 3, 2)), jnp.bfloat16)}
    assert flatbank.bank_spec(bank).dtype == jnp.dtype(jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)), jnp.int32)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    got = hfl.weighted_aggregate(bank, w, seg, m, ctx=ctx)
    want = ref.weighted_aggregate_ref(bank, w, seg, m)
    _assert_tree_close(got, want, bf16_tol=4e-2)


@needs_mesh
def test_shard_local_broadcast_matches_ref():
    """The shard-local resync: replicated (E, P) models x row-sharded
    segment ids -> row-sharded (N, P) bank, equal to the gather oracle,
    with each shard holding only its rows."""
    rng = np.random.default_rng(5)
    e, p, n, k = 4, 137, 16, 4
    mesh = mesh_lib.make_bank_mesh(k)
    models = jnp.asarray(rng.normal(size=(e, p)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, e, size=(n,)), jnp.int32)
    fn = jax.jit(hfl._smap_segment_broadcast(mesh, jnp.dtype(jnp.float32)))
    out = fn(models, seg)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.segment_broadcast_ref(models, seg)))
    shapes = sorted(s.data.shape for s in out.addressable_shards)
    assert shapes == [(n // k, p)] * k       # rows stay sharded


@needs_mesh
def test_cloud_aggregate_sharded_and_fallback():
    rng = np.random.default_rng(6)
    m = 4
    edge_models = {"w": jnp.asarray(rng.normal(size=(m, 33)), jnp.float32)}
    esz = jnp.asarray(rng.uniform(1, 3, size=(m,)), jnp.float32)
    want = hfl.cloud_aggregate(edge_models, esz)
    # replicated plain launch under a mesh: bitwise for any E, even when
    # E does not divide the shard count
    got = hfl.cloud_aggregate(
        edge_models, esz,
        ctx=hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(2)))
    _assert_tree_close(got, want, f32_tol=0.0)
    got_fb = hfl.cloud_aggregate(
        edge_models, esz,
        ctx=hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(3)))
    _assert_tree_close(got_fb, want, f32_tol=0.0)


# ---------------------------------------------------------------------------
# staleness-weighted aggregation (async runtime flush) on the mesh path
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_staleness_flush_sharded_matches_oracle(shape):
    """The async cloud flush is staleness folded into the weight vector
    (repro.runtime.buffer), so the unchanged shard_map + psum path must
    match the numpy staleness oracle and the single-chip flush on
    1/2/4-shard and two-axis meshes."""
    from repro.kernels import ref as ref_mod
    from repro.runtime import StalenessBuffer
    rng = np.random.default_rng(11)
    k, p = 8, 130
    vecs = [jnp.asarray(rng.normal(size=(p,)), jnp.float32)
            for _ in range(k)]
    w = np.asarray(rng.uniform(0.5, 2.0, size=k), np.float32)
    tau = rng.integers(0, 4, size=k)

    def fill(buf):
        for j in range(k):
            buf.push(j, vecs[j], float(w[j]), version=10 - int(tau[j]))
        return buf

    single, _ = fill(StalenessBuffer(k, decay="poly",
                                     decay_a=0.5)).flush(version=10)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    sharded, info = fill(StalenessBuffer(
        k, decay="poly", decay_a=0.5, ctx=ctx)).flush(version=10)
    assert info["staleness"] == tau.tolist()
    want = ref_mod.staleness_aggregate_ref(np.stack(vecs), w, tau,
                                           decay="poly", a=0.5)
    np.testing.assert_allclose(np.asarray(sharded), want, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-5, rtol=1e-5)


@needs_mesh
@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_degraded_flush_sharded_matches_oracle(shape):
    """The coverage-corrected (degraded) flush appends the anchor row to
    the stack, so 7 survivors + 1 anchor = 8 rows still divide the
    1/2/4-shard meshes — the unchanged shard_map + psum path must match
    ``ref.coverage_aggregate_ref`` and the single-chip degraded flush."""
    from repro.kernels import ref as ref_mod
    from repro.runtime import StalenessBuffer
    rng = np.random.default_rng(13)
    k, p = 7, 130                       # +1 anchor row -> 8 total
    vecs = [jnp.asarray(rng.normal(size=(p,)), jnp.float32)
            for _ in range(k)]
    anchor = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    w = np.asarray(rng.uniform(0.5, 2.0, size=k), np.float32)
    tau = rng.integers(0, 4, size=k)
    m_w = 2.5                           # missing data mass

    def fill(buf):
        for j in range(k):
            buf.push(j, vecs[j], float(w[j]), version=10 - int(tau[j]))
        return buf

    single, _ = fill(StalenessBuffer(k + 1, decay="poly")).flush(
        version=10, anchor=anchor, anchor_weight=m_w)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    sharded, info = fill(StalenessBuffer(k + 1, decay="poly",
                                         ctx=ctx)).flush(
        version=10, anchor=anchor, anchor_weight=m_w)
    assert 0.0 < info["coverage"] < 1.0
    want = ref_mod.coverage_aggregate_ref(np.stack(vecs), w, tau,
                                          np.asarray(anchor), m_w,
                                          decay="poly", a=0.5)
    np.testing.assert_allclose(np.asarray(sharded), want, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=1e-5, rtol=1e-5)


@needs_mesh
def test_staleness_flush_indivisible_k_is_bitwise():
    """The flush is a replicated plain launch under a mesh
    (``AggContext.segment_agg_small``), so K not dividing the shard
    count is fine and the result is *bitwise* the single-chip launch."""
    from repro.runtime import StalenessBuffer
    rng = np.random.default_rng(12)
    k, p = 5, 140
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(4))  # 5 % 4 != 0
    buf = StalenessBuffer(k, decay="none", ctx=ctx)
    vecs = [jnp.asarray(rng.normal(size=(p,)), jnp.float32)
            for _ in range(k)]
    for j in range(k):
        buf.push(j, vecs[j], 1.0 + j, version=0)
    glob, _ = buf.flush(version=0)
    want = ops.segment_agg(jnp.stack(vecs),
                           jnp.asarray(np.arange(k) + 1.0, jnp.float32),
                           jnp.zeros((k,), jnp.int32), 1)[0]
    np.testing.assert_array_equal(np.asarray(glob), np.asarray(want))


# ---------------------------------------------------------------------------
# round-level parity (training on) + placement/donation contract
# ---------------------------------------------------------------------------

def _round_fixtures(rng, n):
    bank = {"w": jnp.asarray(rng.normal(size=(n, 4, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
    sizes = jnp.asarray(rng.uniform(1, 3, size=(n,)), jnp.float32)

    def loss(p, batch):
        return jnp.mean((batch["x"] @ p["w"][..., 0] - batch["y"]) ** 2)

    return bank, x, y, sizes, loss


@needs_mesh
@pytest.mark.parametrize("shape", [(2, 1), (4, 1), (2, 2)])
def test_cloud_round_sharded_matches_single_chip(shape):
    """Full cloud round with local SGD on: the sharded round must match
    the single-chip round (same RNG keys by construction; the only
    difference is f32 psum reduction order)."""
    rng = np.random.default_rng(7)
    n, m = 16, 5
    bank, x, y, sizes, loss = _round_fixtures(rng, n)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)), jnp.int32)
    g1 = jnp.asarray([2, 1, 3, 2, 1])
    g2 = jnp.asarray([1, 2, 1, 2, 1])
    key = jax.random.PRNGKey(0)
    single = hfl.make_cloud_round(loss, 0.05, 4, m, 3, 2)
    b0, gm0, em0 = single(jax.tree.map(jnp.copy, bank), x, y, sizes,
                          seg, g1, g2, key)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    sharded = hfl.make_cloud_round(loss, 0.05, 4, m, 3, 2, ctx=ctx)
    b1, gm1, em1 = sharded(jax.tree.map(jnp.copy, bank), x, y, sizes,
                           seg, g1, g2, key)
    _assert_tree_close((b1, gm1, em1), (b0, gm0, em0), f32_tol=1e-4)


@needs_mesh
def test_fedavg_round_sharded_matches_single_chip():
    rng = np.random.default_rng(8)
    n = 16
    bank, x, y, sizes, loss = _round_fixtures(rng, n)
    part = jnp.asarray(rng.random(n) < 0.7)
    key = jax.random.PRNGKey(1)
    single = hfl.make_fedavg_round(loss, 0.05, 4, max_g1=2)
    b0, g0 = single(jax.tree.map(jnp.copy, bank), x, y, sizes, part,
                    jnp.asarray(2), key)
    sharded = hfl.make_fedavg_round(
        loss, 0.05, 4, max_g1=2,
        ctx=hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(4)))
    b1, g1_ = sharded(jax.tree.map(jnp.copy, bank), x, y, sizes, part,
                      jnp.asarray(2), key)
    _assert_tree_close((b1, g1_), (b0, g0), f32_tol=1e-4)


@needs_mesh
def test_sharded_round_never_materializes_full_bank():
    """Placement/donation contract: the input bank is placed row-sharded
    and donated; the output bank's every leaf lives as N/k-row shards
    (no device holds the full bank) while edge/global models come back
    replicated."""
    rng = np.random.default_rng(9)
    n, m, k = 16, 4, 4
    bank, x, y, sizes, loss = _round_fixtures(rng, n)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)), jnp.int32)
    mesh = mesh_lib.make_bank_mesh(k)
    sbs = flatbank.sharded_bank_spec(bank, mesh)
    bank_p = sbs.place_bank(bank)
    for leaf in jax.tree.leaves(bank_p):
        assert {s.data.shape[0] for s in leaf.addressable_shards} \
            == {n // k}
    round_ = hfl.make_cloud_round(loss, 0.05, 4, m, 2, 2,
                                  ctx=hfl.AggContext.for_mesh(mesh))
    out_bank, glob, edges = round_(
        bank_p, x, y, sizes, seg, jnp.full((m,), 2), jnp.full((m,), 2),
        jax.random.PRNGKey(2))
    for leaf in jax.tree.leaves(out_bank):
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert shard_rows == {n // k}, (leaf.shape, shard_rows)
    for leaf in jax.tree.leaves((glob, edges)):
        # replicated: every device holds the whole (small) array
        assert {s.data.shape for s in leaf.addressable_shards} \
            == {leaf.shape}
    # the donated input buffer must be gone (no second full-bank copy)
    assert all(l.is_deleted() for l in jax.tree.leaves(bank_p))


@needs_mesh
def test_round_rejects_indivisible_rows():
    rng = np.random.default_rng(10)
    bank, x, y, sizes, loss = _round_fixtures(rng, 10)   # 10 % 4 != 0
    round_ = hfl.make_cloud_round(
        loss, 0.05, 4, 2, 2, 2,
        ctx=hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(4)))
    with pytest.raises(ValueError):
        round_(bank, x, y, sizes, jnp.zeros((10,), jnp.int32),
               jnp.ones((2,)), jnp.ones((2,)), jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def two_shard_rounds():
    """The rounds under test and the fixed-loop oracle on a forced-host
    2-shard mesh, built once so every action reuses their programs."""
    fr = _fixed_rounds
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(2, 1))
    m, g = 3, fr.MAX_G
    new = {"cloud": hfl.make_cloud_round(fr.tiny_loss, 0.1, 4, m, g, g,
                                         ctx=ctx),
           "edge": hfl.make_edge_round(fr.tiny_loss, 0.1, 4, m, g, g,
                                       ctx=ctx),
           "fedavg": hfl.make_fedavg_round(fr.tiny_loss, 0.1, 4, g,
                                           ctx=ctx)}
    old = dict(zip(("cloud", "edge", "fedavg"),
                   fr.fixed_rounds(fr.tiny_loss, 0.1, 4, m, g, g, ctx=ctx)))
    return new, old


@needs_mesh
@pytest.mark.parametrize("action", list(_fixed_rounds.ACTIONS))
@pytest.mark.parametrize("kind", ["cloud", "edge", "fedavg"])
def test_sharded_rounds_skip_only_unneeded_epochs_bitwise(
        two_shard_rounds, kind, action):
    """Under a mesh the loops' trip counts come from the action too
    (the epoch bound a cross-shard max of the row-sharded gamma1), and
    the rounds give the fixed masked loops' result bit for bit."""
    new, old = two_shard_rounds
    case = _fixed_rounds.tiny_case(action)
    key = jax.random.PRNGKey(6)
    got = _fixed_rounds.run_case(new, kind, case, key)
    want = _fixed_rounds.run_case(old, kind, case, key)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# AggContext: construction, validation, deprecation shims
# ---------------------------------------------------------------------------

def test_agg_context_construction_and_validation():
    ctx = hfl.AggContext.single_chip()
    assert not ctx.sharded and ctx.mesh is None and ctx.n_shards == 1
    assert ctx.donate_argnums(0) == (0,)
    assert hfl.AggContext.single_chip(donate=False).donate_argnums(0) \
        == ()
    with pytest.raises(ValueError):
        hfl.AggContext.for_mesh(None)
    with pytest.raises(TypeError):
        hfl.AggContext.for_mesh("not a mesh")
    ctx1 = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(1))
    assert ctx1.sharded and ctx1.axes == ("edge", "fl")
    assert ctx1.n_shards == 1
    assert ctx1.check_rows(8) == 8          # rows per shard


def test_mesh_kwarg_deprecation_shims():
    """The one-cycle ``mesh=`` spelling warns and routes to the same
    sharded path; passing both spellings is an error; a non-AggContext
    ``ctx`` is a TypeError."""
    rng = np.random.default_rng(21)
    bank = {"w": jnp.asarray(rng.normal(size=(4, 9)), jnp.float32)}
    w = jnp.ones((4,), jnp.float32)
    seg = jnp.zeros((4,), jnp.int32)
    m1 = mesh_lib.make_bank_mesh(1)
    with pytest.warns(DeprecationWarning):
        got = hfl.weighted_aggregate(bank, w, seg, 1,
                                     mesh=m1)  # allow-mesh-kwarg
    want = hfl.weighted_aggregate(bank, w, seg, 1)
    _assert_tree_close(got, want, f32_tol=0.0)
    with pytest.raises(ValueError):
        hfl.weighted_aggregate(bank, w, seg, 1,
                               ctx=hfl.AggContext.for_mesh(m1),
                               mesh=m1)  # allow-mesh-kwarg
    with pytest.raises(TypeError):
        hfl.weighted_aggregate(bank, w, seg, 1, ctx="nope")


# ---------------------------------------------------------------------------
# sharded async edge round: bitwise parity + placement + churn resync
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_edge_round_sharded_bitwise(shape):
    """Tentpole acceptance: the async per-edge round compiled under a
    sharded AggContext is **bitwise** the single-chip round when the
    edge->row assignment is shard-aligned (contiguous blocks — the
    ShardedBankSpec layout contract). Zero-masked rows and zero psum
    partials are reduction-neutral, so the owner shard reproduces the
    single-chip FMA accumulation chain exactly."""
    rng = np.random.default_rng(20)
    n, m = 16, 4
    bank, x, y, sizes, loss = _round_fixtures(rng, n)
    seg = jnp.asarray(np.repeat(np.arange(m), n // m), jnp.int32)
    p = flatbank.bank_spec(bank).width
    gvec = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    key = jax.random.PRNGKey(3)
    single = hfl.make_edge_round(loss, 0.05, 4, m, 3, 3)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    sharded = hfl.make_edge_round(loss, 0.05, 4, m, 3, 3, ctx=ctx)
    for j in range(m):
        b0, e0 = single(jax.tree.map(jnp.copy, bank), x, y, sizes, seg,
                        jnp.int32(j), jnp.int32(2), jnp.int32(2),
                        gvec, key)
        b1, e1 = sharded(jax.tree.map(jnp.copy, bank), x, y, sizes, seg,
                         jnp.int32(j), jnp.int32(2), jnp.int32(2),
                         gvec, key)
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e0))
        for l0, l1 in zip(jax.tree.leaves(b0), jax.tree.leaves(b1)):
            np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                          np.asarray(l0, np.float32))


@needs_mesh
def test_edge_round_sharded_placement_and_donation():
    """No-full-bank contract for the async round: input bank placed
    row-sharded and donated, output bank leaves stay as N/k-row shards,
    the returned edge update is replicated."""
    rng = np.random.default_rng(22)
    n, m, k = 16, 4, 4
    bank, x, y, sizes, loss = _round_fixtures(rng, n)
    seg = jnp.asarray(np.repeat(np.arange(m), n // m), jnp.int32)
    p = flatbank.bank_spec(bank).width
    gvec = jnp.zeros((p,), jnp.float32)
    mesh = mesh_lib.make_bank_mesh(k)
    ctx = hfl.AggContext.for_mesh(mesh)
    bank_p = ctx.place_bank(bank)
    round_ = hfl.make_edge_round(loss, 0.05, 4, m, 2, 2, ctx=ctx)
    out_bank, evec = round_(bank_p, x, y, sizes, seg, jnp.int32(1),
                            jnp.int32(2), jnp.int32(2), gvec,
                            jax.random.PRNGKey(4))
    for leaf in jax.tree.leaves(out_bank):
        assert {s.data.shape[0] for s in leaf.addressable_shards} \
            == {n // k}
    assert {s.data.shape for s in evec.addressable_shards} \
        == {evec.shape}                                    # replicated
    assert all(l.is_deleted() for l in jax.tree.leaves(bank_p))


@needs_mesh
@pytest.mark.parametrize("shape", [(2, 1), (4, 1), (2, 2)])
def test_masked_resync_sharded_churn_join_bitwise(shape):
    """Churn-join on the sharded bank: ``masked_resync`` under a
    sharded AggContext re-seeds only the joining edge's (shard-local)
    rows, bitwise the single-chip result, and the bank stays
    row-sharded."""
    rng = np.random.default_rng(23)
    n, m, p = 16, 4, 37
    seg = np.repeat(np.arange(m), n // m)
    bank_mat = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    edge_mat = jnp.asarray(rng.normal(size=(m, p)), jnp.float32)
    alive = np.zeros(m, bool)
    alive[1] = True                              # edge 1 rejoins
    want = hfl.masked_resync(edge_mat, bank_mat, seg, alive)
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    got = hfl.masked_resync(edge_mat, ctx.place_rows(bank_mat), seg,
                            alive, ctx=ctx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    k = ctx.n_shards
    assert {s.data.shape[0] for s in got.addressable_shards} == {n // k}


# ---------------------------------------------------------------------------
# end-to-end: async env trajectories bitwise across mesh configs
# ---------------------------------------------------------------------------

TRAJ_CFG = dict(task="mnist", mode="real", n_devices=8, n_edges=4,
                n_local=32, batch_size=16, threshold_time=300.0,
                gamma_max=2, seed=0)


def _run_async_traj(ctx, steps, async_cfg, faults):
    """Run ``steps`` upload events on an AsyncHFLEnv with contiguous
    (shard-aligned) edge assignment; returns the acc trajectory, the
    flat global vector, the flat bank, and the degraded-flush count."""
    from repro.runtime import AsyncConfig
    from repro.sim import AsyncHFLEnv, EnvConfig
    cfg = EnvConfig(**dict(TRAJ_CFG, agg=ctx))
    env = AsyncHFLEnv(cfg, async_cfg, faults=faults)
    env.set_topology(np.repeat(np.arange(4), 2))
    env.reset()
    traj, degr = [], 0
    for _ in range(steps):
        _, _, done, info = env.step(np.array([2.0, 2.0]))
        traj.append(info["acc"])
        if info["flushed"] and env._flush_info.get("degraded"):
            degr += 1
        if done:
            break
    return (traj, np.asarray(env._global_vec),
            np.asarray(env._spec.flatten(env.bank), np.float32),
            degr, env)


@needs_mesh
@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (4, 1)])
def test_async_env_trajectory_sharded_bitwise(shape):
    """ISSUE acceptance: an all-zeros-FaultSpec async run on a sharded
    AggContext reproduces the single-chip trajectory **bitwise** —
    accuracies, global vector, and bank — with the bank row-sharded
    throughout (no device holds all rows)."""
    from repro.runtime import AsyncConfig, FaultSpec
    acfg = lambda: AsyncConfig(buffer_k=2, decay="none")
    spec = lambda: FaultSpec(seed=3)             # all-zeros: no faults
    t0, g0, b0, _, _ = _run_async_traj(None, 4, acfg(), spec())
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    t1, g1_, b1, _, env = _run_async_traj(ctx, 4, acfg(), spec())
    assert t1 == t0
    np.testing.assert_array_equal(g1_, g0)
    np.testing.assert_array_equal(b1, b0)
    k = ctx.n_shards
    n = TRAJ_CFG["n_devices"]
    for leaf in jax.tree.leaves(env.bank):
        assert {s.data.shape[0] for s in leaf.addressable_shards} \
            == {n // k}


@needs_mesh
@pytest.mark.parametrize("shape", [(2, 1), (4, 1)])
def test_async_env_trajectory_sharded_faults_bitwise(shape):
    """ISSUE acceptance, degraded-flush run: dropout + deadline flushes
    + leave/join churn — the injector's RNG draws are identical across
    mesh configs, so the full faulty trajectory (including at least one
    coverage-corrected flush and the churn-join resync) stays bitwise
    the single-chip run."""
    from repro.runtime import AsyncConfig, ChurnEvent, FaultSpec
    acfg = lambda: AsyncConfig(buffer_k=3, flush_deadline=20.0)
    spec = lambda: FaultSpec(drop_prob=0.6,
                             churn=(ChurnEvent(30.0, 1, "leave"),
                                    ChurnEvent(60.0, 1, "join")),
                             seed=5)
    t0, g0, b0, d0, _ = _run_async_traj(None, 6, acfg(), spec())
    assert d0 >= 1                       # the scenario actually degrades
    ctx = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(*shape))
    t1, g1_, b1, d1, env = _run_async_traj(ctx, 6, acfg(), spec())
    assert t1 == t0 and d1 == d0
    np.testing.assert_array_equal(g1_, g0)
    np.testing.assert_array_equal(b1, b0)
    for leaf in jax.tree.leaves(env.bank):
        assert {s.data.shape[0] for s in leaf.addressable_shards} \
            == {TRAJ_CFG["n_devices"] // ctx.n_shards}


# ---------------------------------------------------------------------------
# tier-1 wrapper: run this file under a forced 8-device backend
# ---------------------------------------------------------------------------

def test_sharded_suite_in_subprocess():
    """Tier-1 runs with one device (the suite default); the sharded
    engine still gets covered by re-running this file in a subprocess
    with 8 forced host devices — the same command the sharded CI tier
    runs directly."""
    if NDEV >= 8:
        pytest.skip("already running under a multi-device backend")
    if os.environ.get("GITHUB_ACTIONS"):
        pytest.skip("CI runs the dedicated sharded-parity job "
                    "(scripts/ci.sh test-sharded); no need to pay the "
                    "suite twice per workflow run")
    _subproc.run_pytest(__file__, device_count=8)
