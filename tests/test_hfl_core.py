"""HFL aggregation math + Arena components: unit tests and hypothesis
property tests on the system invariants. Property tests skip cleanly
when ``hypothesis`` is not installed (see ``_hypothesis_compat``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import _fixed_rounds
from _hypothesis_compat import given, settings, st

from repro.core import convergence, hfl, pca, profiling
from repro.core.reward import UPSILON, reward


# ---------------------------------------------------------------------------
# weighted aggregation (Eqs. 1-2)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.integers(1, 4), st.data())
def test_aggregate_is_convex_combination(n, m, data):
    """Every aggregated coordinate lies within [min, max] of its segment's
    inputs, and weights of zero drop a device entirely."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    bank = {"w": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)}
    weights = jnp.asarray(rng.uniform(0.1, 5.0, size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)))
    out = hfl.weighted_aggregate(bank, weights, seg, m)["w"]
    for j in range(m):
        sel = np.asarray(seg) == j
        if not sel.any():
            continue
        lo = np.asarray(bank["w"])[sel].min(0) - 1e-5
        hi = np.asarray(bank["w"])[sel].max(0) + 1e-5
        assert (np.asarray(out[j]) >= lo).all()
        assert (np.asarray(out[j]) <= hi).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.data())
def test_aggregate_weight_scale_invariance(n, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    bank = {"w": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)}
    w = jnp.asarray(rng.uniform(0.1, 2.0, size=(n,)), jnp.float32)
    seg = jnp.zeros((n,), jnp.int32)
    a = hfl.weighted_aggregate(bank, w, seg, 1)["w"]
    b = hfl.weighted_aggregate(bank, w * 7.5, seg, 1)["w"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_two_level_equals_flat_when_weights_match():
    """Edge agg then cloud agg == direct global weighted mean when edge
    weights are the summed device weights (the identity that lets the HFL
    env express Vanilla-FL exactly)."""
    rng = np.random.default_rng(0)
    n, m = 12, 3
    bank = {"w": jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)}
    sizes = jnp.asarray(rng.uniform(1, 3, size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, m, size=(n,)))
    edge = hfl.edge_aggregate(bank, sizes, seg, m)
    esz = jax.ops.segment_sum(sizes, seg, m)
    cloud = hfl.cloud_aggregate(edge, esz)["w"]
    direct = hfl.weighted_aggregate(bank, sizes,
                                    jnp.zeros((n,), jnp.int32), 1)["w"][0]
    np.testing.assert_allclose(np.asarray(cloud), np.asarray(direct),
                               atol=1e-5)


def test_cloud_round_synchronizes_bank():
    """After a cloud round every device holds the same model, and with
    gamma=0-masking inactive edges keep training frozen."""
    rng = np.random.default_rng(1)
    n, m = 6, 2
    x = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(n, 8)))

    def loss(p, batch):
        logits = batch["x"] @ p["w"]
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, batch["y"][:, None], 1))

    round_ = hfl.make_cloud_round(loss, 0.1, 4, m, 3, 3)  # self-jitting
    p0 = {"w": jnp.asarray(rng.normal(size=(4, 2)), jnp.float32)}
    bank = hfl.init_bank(lambda k: p0, jax.random.PRNGKey(0), n)
    sizes = jnp.ones((n,), jnp.float32)
    seg = jnp.asarray([0, 0, 0, 1, 1, 1])
    g1 = jnp.asarray([2, 1])
    g2 = jnp.asarray([1, 2])
    bank, glob, edges = round_(bank, x, y, sizes, seg, g1, g2,
                               jax.random.PRNGKey(1))
    w = np.asarray(bank["w"])
    for i in range(1, n):
        np.testing.assert_allclose(w[i], w[0], atol=1e-6)
    # training moved the model
    assert np.abs(np.asarray(glob["w"]) - np.asarray(p0["w"])).max() > 1e-4


@pytest.fixture(scope="module")
def one_chip_rounds():
    """The rounds under test and the fixed-loop oracle, built once so
    every action reuses their compiled programs."""
    c = _fixed_rounds
    m, g = 3, c.MAX_G
    new = {"cloud": hfl.make_cloud_round(c.tiny_loss, 0.1, 4, m, g, g),
           "edge": hfl.make_edge_round(c.tiny_loss, 0.1, 4, m, g, g),
           "fedavg": hfl.make_fedavg_round(c.tiny_loss, 0.1, 4, g)}
    old = dict(zip(("cloud", "edge", "fedavg"),
                   c.fixed_rounds(c.tiny_loss, 0.1, 4, m, g, g)))
    return new, old


@pytest.mark.parametrize("action", list(_fixed_rounds.ACTIONS))
@pytest.mark.parametrize("kind", ["cloud", "edge", "fedavg"])
def test_rounds_skip_only_unneeded_epochs_bitwise(one_chip_rounds, kind,
                                                  action):
    """The rounds run only the epochs some device still needs, and give
    the fixed max_g1 x max_g2 masked loops' result bit for bit."""
    new, old = one_chip_rounds
    case = _fixed_rounds.tiny_case(action)
    key = jax.random.PRNGKey(5)
    got = _fixed_rounds.run_case(new, kind, case, key)
    want = _fixed_rounds.run_case(old, kind, case, key)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_round_epochs_counts_the_loops():
    """N x sum over t2 < max gamma2 of the largest gamma1 still active;
    an edge with no device adds nothing, the ceilings cap both loops."""
    ea = np.array([0, 1, 0, 1])
    assert hfl.round_epochs([2, 2], [2, 2], ea, 3, 3) == 4 * 4
    assert hfl.round_epochs([1, 3], [2, 3], ea, 3, 3) == 4 * 9
    assert hfl.round_epochs([3, 1], [1, 3], ea, 3, 3) == 4 * (3 + 1 + 1)
    assert hfl.round_epochs([2, 2, 5], [1, 1, 4], ea, 8, 8) == 4 * 2
    assert hfl.round_epochs([5, 1], [5, 1], ea, 3, 2) == 4 * 3 * 2


# ---------------------------------------------------------------------------
# PCA (Eq. 6)
# ---------------------------------------------------------------------------

def test_pca_reconstruction_on_span():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(6, 300)), jnp.float32)
    state = pca.fit(x, 6)
    z = pca.transform(state, x)
    # loadings orthonormal
    g = np.asarray(state["loadings"] @ state["loadings"].T)
    np.testing.assert_allclose(g[:5, :5], np.eye(5), atol=1e-3)
    # 6 samples: 5 nonzero PCs capture the centered span exactly
    xc = np.asarray(x - state["mean"])
    rec = np.asarray(z) @ np.asarray(state["loadings"])
    np.testing.assert_allclose(rec, xc, atol=1e-3)


def test_pca_flatten_deterministic_order():
    p = {"b": jnp.ones((2,)), "a": {"x": jnp.zeros((3,))}}
    v1 = pca.flatten_model(p)
    v2 = pca.flatten_model({"a": {"x": jnp.zeros((3,))},
                            "b": jnp.ones((2,))})
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


# ---------------------------------------------------------------------------
# profiling / clustering (§3.1)
# ---------------------------------------------------------------------------

def test_clustering_balanced_and_groups_similar():
    from repro.sim.hardware import DeviceProfiles
    rng = np.random.default_rng(3)
    prof = DeviceProfiles.sample(rng, 50)
    assign = profiling.cluster_devices(prof, 5, seed=0)
    counts = np.bincount(assign, minlength=5)
    assert counts.max() - counts.min() <= 2
    # devices with identical usage class should mostly co-cluster:
    # within-cluster usage spread < global spread
    spread = [prof.cpu_usage[assign == j].std() for j in range(5)]
    assert np.mean(spread) < prof.cpu_usage.std()


@settings(max_examples=10, deadline=None)
@given(st.integers(8, 40), st.integers(2, 5), st.data())
def test_balanced_kmeans_caps(n, k, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    x = rng.normal(size=(n, 3))
    assign = profiling.balanced_kmeans(rng, x, k)
    counts = np.bincount(assign, minlength=k)
    assert counts.max() <= -(-n // k)
    assert (assign >= 0).all()


# ---------------------------------------------------------------------------
# reward (Eq. 11) + convergence bound (Thm 1)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.floats(0.05, 0.95), st.floats(0.05, 0.95), st.floats(0, 100))
def test_reward_monotonicity(a_new, a_old, energy):
    r1 = reward(a_new, a_old, energy, 0.002)
    r2 = reward(a_new, a_old, energy + 10.0, 0.002)
    assert r1 > r2          # more energy, less reward
    if a_new > a_old:
        assert reward(a_new, a_old, 0.0, 0.002) > 0


def test_convergence_bound_on_quadratic():
    """For f(w) = 0.5 L ||w||^2 with noisy gradients, the measured descent
    of one cloud round must respect the Theorem-1 upper bound."""
    rng = np.random.default_rng(4)
    L, eta, sigma2 = 1.0, 0.01, 0.04
    M, N = 2, 8
    g1, g2 = 3, 2
    bp = convergence.BoundParams(L=L, eta=eta, sigma2=sigma2, M=M, N=N)
    assert convergence.stepsize_feasible(
        bp, np.full(M, g1), np.full(M, g2))
    w = rng.normal(size=(4,)) * 2.0
    f0 = 0.5 * L * (w ** 2).sum()
    grad_norm_sq = ((L * w) ** 2).sum()
    # simulate: devices run g1*g2 noisy GD steps from w, then average
    trials = []
    for _ in range(200):
        dev = np.tile(w, (N, 1))
        for _t2 in range(g2):
            for _t1 in range(g1):
                noise = rng.normal(size=dev.shape) * np.sqrt(sigma2 / 4)
                dev = dev - eta * (L * dev + noise)
        wa = dev.mean(0)
        trials.append(0.5 * L * (wa ** 2).sum())
    measured = np.mean(trials) - f0
    bound = convergence.one_round_bound(bp, g1, g2, grad_norm_sq)
    assert measured <= bound + 1e-6, (measured, bound)


def test_max_feasible_eta_satisfies_condition():
    bp = convergence.BoundParams(L=2.0, eta=0.0, sigma2=1.0, M=3, N=12)
    for g1, g2 in [(1, 1), (4, 2), (8, 8)]:
        eta = convergence.max_feasible_eta(bp, g1, g2)
        bp2 = convergence.BoundParams(L=2.0, eta=eta * 0.999, sigma2=1.0,
                                      M=3, N=12)
        assert convergence.stepsize_feasible(
            bp2, np.full(3, g1), np.full(3, g2))
