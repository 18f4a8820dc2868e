"""The program's own tracing: device scopes, host spans and epoch counts.

* every round the program compiles carries its ``hfl.*`` device scopes in
  the compiled HLO's ``op_name`` metadata (which the TPU trace reads),
  the Arena rounds and the LLM train step alike;
* the environment's accuracy and PCA projection are scoped ``hfl.eval``;
* ``HFLEnv`` writes its host spans into the profiler's trace, and counts
  the device-epochs a round computes and those it keeps.

Scopes are metadata: the numeric and bitwise tests of ``core/hfl``,
``launch/train`` and ``sim/env`` run on the scoped code unchanged.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hfl
from repro.core import state as state_mod
from repro.sim import EnvConfig, HFLEnv

SCOPES = {"hfl.local", "hfl.update", "hfl.edge_agg", "hfl.cloud_agg"}


def _scopes(compiled_text: str) -> set:
    return set(re.findall(r"hfl\.[a-z_]+", " ".join(
        re.findall(r'op_name="([^"]*)"', compiled_text))))


def _linear_loss(p, batch):
    lp = jax.nn.log_softmax(batch["x"] @ p["w"])
    return -jnp.mean(jnp.take_along_axis(lp, batch["y"][:, None], 1))


def _round_args(n=4, m=2):
    rng = np.random.default_rng(0)
    bank = hfl.init_bank(lambda k: {"w": jnp.zeros((4, 2))}, None, n)
    x = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(n, 8)))
    seg = jnp.asarray(np.arange(n) % m, jnp.int32)
    return bank, x, y, jnp.ones(n), seg


@pytest.mark.parametrize("kind,want", [
    ("cloud_round", SCOPES),
    ("edge_round", {"hfl.local", "hfl.update", "hfl.edge_agg"}),
    ("fedavg_round", {"hfl.local", "hfl.update", "hfl.cloud_agg"}),
])
def test_arena_rounds_carry_their_scopes(kind, want):
    bank, x, y, sizes, seg = _round_args()
    key = jax.random.PRNGKey(0)
    g = jnp.array([1, 2])
    if kind == "cloud_round":
        rnd = hfl.make_cloud_round(_linear_loss, 0.1, 4, 2, 2, 2)
        args = (bank, x, y, sizes, seg, g, g, key)
    elif kind == "edge_round":
        rnd = hfl.make_edge_round(_linear_loss, 0.1, 4, 2, 2, 2)
        args = (bank, x, y, sizes, seg, 1, 2, 2, jnp.zeros(8), key)
    else:
        rnd = hfl.make_fedavg_round(_linear_loss, 0.1, 4, 2)
        args = (bank, x, y, sizes, jnp.ones(4, bool), 2, key)
    assert _scopes(rnd.lower(*args).compile().as_text()) == want


@pytest.mark.parametrize("dynamic", [False, True])
def test_llm_step_carries_its_scopes(dynamic):
    from repro.configs import get_config
    from repro.launch import train
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduce(), vocab=128)
    step, params, _ = train.make_trainer(
        cfg, (1, 2, 2), lr=1e-2, mb_per_epoch=2, remat=False,
        attn_chunk=16, dynamic=dynamic,
        **(dict(max_g1=2, max_g2=2) if dynamic else dict(g1=1, g2=2)))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    args = (params, batch) + ((jnp.ones(2, jnp.int32),) * 2
                              if dynamic else ())
    assert _scopes(step.lower(*args).compile().as_text()) == SCOPES


def _tiny_env(**kw):
    cfg = EnvConfig(task="mnist", mode="real", n_devices=4, n_edges=2,
                    n_local=32, batch_size=16, threshold_time=3000.0,
                    gamma_max=3, use_profiling=False, seed=0, **kw)
    return HFLEnv(cfg)


def test_environment_eval_is_scoped():
    env = _tiny_env()
    env.reset()
    acc = env._acc_fn.lower(env.global_model, env.fed.test_x,
                            env.fed.test_y).compile().as_text()
    proj = state_mod.project.lower(env.pca_state, env.global_model,
                                   env.edge_models).compile().as_text()
    assert _scopes(acc) == _scopes(proj) == {"hfl.eval"}


def test_epoch_counts_and_info():
    """Every device runs each epoch of the round's loops, N x the sum
    over t2 < max gamma2 of the largest gamma1 still active; the useful
    ones are gamma1 * gamma2 of its edge, on participating devices only;
    the reset round (gamma 2, 2) counts too."""
    env = _tiny_env()
    env.reset()
    n = 4
    assert env.epoch_counts == {"computed": n * 4, "useful": n * 4}
    _, _, _, info = env.step(np.array([1, 3, 2, 3]))   # g1 (1, 3), g2 (2, 3)
    # devices 0, 2 on edge 0 (1 * 2), devices 1, 3 on edge 1 (3 * 3);
    # all three steps run edge 1's 3 epochs
    assert (info["epochs_computed"], info["epochs_useful"]) == (
        n * 9, 2 * 2 + 2 * 9)
    _, _, _, info = env.step_raw(np.array([2, 2]), np.array([1, 1]),
                                 participate=np.array([1, 0, 0, 1], bool))
    assert (info["epochs_computed"], info["epochs_useful"]) == (n * 2, 2 * 2)
    assert env.epoch_counts == {"computed": n * (4 + 9 + 2),
                                "useful": n * 4 + 22 + 4}


@pytest.mark.parametrize("g1,g2", [
    ([1, 1, 1], [1, 1, 1]),
    ([3, 1, 2], [1, 3, 2]),
    ([2, 3, 1], [3, 3, 3]),
])
def test_compiled_round_runs_the_counted_epochs(g1, g2):
    """The compiled cloud round evaluates the loss once per device and
    minibatch of each epoch ``hfl.round_epochs`` counts: the loops stop
    where the action's epochs end."""
    calls = []

    def loss(p, batch):
        # one value per device and minibatch, whether vmap calls back
        # once per device or once for all of them
        jax.debug.callback(lambda v: calls.append(np.size(v)),
                           batch["x"][0, 0])
        return _linear_loss(p, batch)

    n, m, max_g = 6, 3, 3
    bank, x, y, sizes, seg = _round_args(n, m)
    rnd = hfl.make_cloud_round(loss, 0.1, 4, m, max_g, max_g)
    out = rnd(bank, x, y, sizes, seg, jnp.array(g1), jnp.array(g2),
              jax.random.PRNGKey(0))
    jax.block_until_ready(out)
    jax.effects_barrier()
    minibatches = x.shape[1] // 4
    assert sum(calls) == minibatches * hfl.round_epochs(
        g1, g2, np.asarray(seg), max_g, max_g)


def test_host_spans_land_in_the_profiler_trace(tmp_path):
    import glob
    from jax.profiler import ProfileData
    env = _tiny_env()
    env.reset()            # compile outside the trace
    env.step(np.array([1, 1, 1, 1]))
    jax.profiler.start_trace(str(tmp_path))
    env.reset()
    env.step(np.array([2, 1, 1, 2]))
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    prof = ProfileData.from_file(path)
    spans = {}
    for plane in prof.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hfl."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(spans) == {"hfl.reset", "hfl.fit_pca", "hfl.step",
                          "hfl.costs", "hfl.round", "hfl.acc", "hfl.state"}
    # the reset's round and the step's
    assert len(spans["hfl.costs"]) == len(spans["hfl.round"]) == 2
    (rs, re_), = spans["hfl.reset"]
    (fs, fe), = spans["hfl.fit_pca"]
    assert rs <= fs and fe <= re_
    # the step's whole host path, its state included, is inside hfl.step
    (ss, se), = spans["hfl.step"]
    assert re_ <= ss
    inside = [name for name, ivs in spans.items() for s, e in ivs
              if ss <= s and e <= se and name != "hfl.step"]
    assert sorted(inside) == ["hfl.acc", "hfl.costs", "hfl.round",
                              "hfl.state"]


def _eager_state(env) -> np.ndarray:
    """The state as ``build_state`` computed it op by op, before the
    projection was one jit: flatten, stack and transform eagerly."""
    flat = [state_mod.pca.flatten_model(env.global_model)]
    for j in range(env.cfg.n_edges):
        flat.append(state_mod.pca.flatten_model(
            jax.tree.map(lambda a: a[j], env.edge_models)))
    s1 = np.asarray(state_mod.pca.transform(env.pca_state, jnp.stack(flat)))
    s3 = np.array([[env.k / 50.0, env.t_re / env.cfg.threshold_time,
                    env.acc]], np.float32)
    s2 = env._h_edges.astype(np.float32) / np.array(
        [[100.0, 100.0, 50.0]], np.float32)
    return np.concatenate([s1, np.concatenate([s3, s2], 0)], axis=1)


def test_jitted_projection_gives_the_eager_state_bit_for_bit():
    """``core/state.project`` runs the PCA projection as one jit; the
    agent's state is the same bits as the op-by-op path, after the reset
    and after each step."""
    env = _tiny_env()
    states = [env.reset()]
    eager = [_eager_state(env)]
    for a in ([1, 3, 2, 3], [3, 3, 1, 1], [2, 1, 3, 2]):
        states.append(env.step(np.array(a))[0])
        eager.append(_eager_state(env))
    for got, want in zip(states, eager):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert len({s.tobytes() for s in states}) == len(states)
