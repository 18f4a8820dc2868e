"""The ``arena_env`` runner at a tiny size on the CPU, through the same
``bench.run`` that a chip run takes (the look for a chip skipped): a
sound run is correct, the control and each fault the cell can have are
not."""
import jax
import jax.numpy as jnp
import pytest

import _chipbench
import bench
import compare
import drive_arena

SEED = _chipbench.BIG_SEED
TINY = dict(gamma_range=[1, 2])


def _run(cell, seconds=1.0, **runner_kw):
    return bench.run(cell, SEED, seconds, False, jax.devices(),
                     runner_kw=runner_kw)


def test_tiny_run_is_correct():
    cell = _chipbench.tiny_cell("mnist-varfreq", **TINY)
    r = _run(cell, seconds=2.0)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "env_steps_per_s"}
    assert set(r["checks"]) == {"delta1_gap", "delta3_gap", "acc_gap"}
    assert list(r)[-1] == "checks"


def test_control_is_not_correct():
    """The reference held in bfloat16, put in the program's place."""
    cell = _chipbench.tiny_cell("mnist-varfreq", **TINY)
    d = drive_arena.ArenaCell(cell, SEED, jax.devices())
    d.setup()
    d.release()
    ref = d.reference_models()
    assert compare.judge(d.check(ref))
    ctl = d.reference_models(dtype=jnp.bfloat16)
    models = {k: {n: v.astype("float32") for n, v in m.items()}
              for k, m in ctl["models"].items()}
    checks = drive_arena.judge_numbers(models, d.accs, ref, d.inputs,
                                       cell.limits,
                                       cell.traffic["check_steps"])
    assert not compare.judge(checks), checks


def _faulty_env(fault):
    from repro.core import hfl
    from repro.models import model as model_mod
    from repro.sim import HFLEnv

    def make(cfg):
        env = HFLEnv(cfg)
        if fault == "unchanged":
            def same(bank, x, y, sizes, ea, g1, g2, key):
                glob = hfl.bank_select(bank, 0)
                edges = jax.tree.map(
                    lambda a: jnp.stack([a] * cfg.n_edges), glob)
                return bank, glob, edges
            env._cloud_round = same
        elif fault == "half_batch":
            half = cfg.batch_size // 2

            def loss(p, b):
                return model_mod.cnn_loss(env._apply_fn, p, {
                    "x": b["x"][:half], "y": b["y"][:half]})
            env._cloud_round = hfl.make_cloud_round(
                loss, cfg.lr, cfg.batch_size, cfg.n_edges, cfg.gamma_max,
                cfg.gamma_max, ctx=env.agg_ctx)
        elif fault == "altered":
            acc = env._acc_fn
            env._acc_fn = lambda p, x, y: acc(p, x, y) + 0.05
        return env

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_aggregation", "altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    """The timed path broken underneath: a round that returns the bank
    unchanged; half of each minibatch left out of the mean; the edge and
    cloud aggregation left out (device 0's model taken for every edge
    and the cloud); the reported accuracy altered where it is made."""
    from repro.kernels import ops
    if fault == "no_aggregation":
        monkeypatch.setattr(
            ops, "segment_agg", lambda mat, w, seg, e, **kw:
            jnp.broadcast_to(mat[:1].astype(jnp.float32), (e, mat.shape[1])))
    cell = _chipbench.tiny_cell("mnist-varfreq", **TINY)
    r = _run(cell, seconds=0.5, env_factory=_faulty_env(fault))
    assert r["correct"] is False, r["checks"]


def test_reference_builds_the_programs_weights():
    """The reference's w(0), written from the paper's model, is the
    program's."""
    import numpy as np
    import ref_cnn
    from repro.models import model as model_mod
    key = jax.random.PRNGKey(1234)
    mine, prog = ref_cnn.init(key), model_mod.mnist_cnn_init(key)
    assert sorted(mine) == sorted(prog)
    for k in mine:
        np.testing.assert_allclose(np.asarray(mine[k]), np.asarray(prog[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


@pytest.mark.parametrize("gamma_range", [[1, 1], [1, 2]])
def test_useful_share_is_over_the_epochs_the_rounds_ran(gamma_range):
    """The window's useful share divides the samples of the epochs the
    actions ask for by those of the epochs the rounds ran
    (``hfl.round_epochs`` of each action), not by gamma_max^2 epochs a
    round: where every gamma is 1, every epoch run is useful."""
    from repro.core import hfl
    cell = _chipbench.tiny_cell("mnist-varfreq", gamma_range=gamma_range)
    d = drive_arena.ArenaCell(cell, SEED, jax.devices())
    d.setup()
    start = d.steps_done
    counts = d.window(0.5)
    assert counts["resets"] == 0
    gmax = cell.config["gamma_max"]
    ran = sum(hfl.round_epochs(*d._gammas(d.actions[(start + i)
                                                    % len(d.actions)]),
                               d.env.edge_assign, gmax, gmax)
              for i in range(counts["rounds"]))
    per_epoch = (cell.config["n_local"] // cell.config["batch_size"]
                 * cell.config["batch_size"])
    assert counts["useful_share"] == pytest.approx(
        counts["useful_samples"] / (ran * per_epoch), rel=1e-12)
    assert 0 < counts["useful_share"] <= 1
    if gamma_range == [1, 1]:
        assert counts["useful_share"] == 1.0
