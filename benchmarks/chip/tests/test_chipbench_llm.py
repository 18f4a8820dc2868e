"""The ``llm_train`` runner at a tiny size on the CPU, through the same
``bench.run`` that a chip run takes (the look for a chip skipped): a
sound run is correct, the control and each fault the cell can have are
not."""
import jax
import jax.numpy as jnp
import pytest

import _chipbench
import bench
import compare
import drive_llm

SEED = _chipbench.BIG_SEED
TINY = dict(seq_len=32, attn_chunk=32)
LLM_CELLS = [w["name"] for w in _chipbench.spec.load_benchmark()["workloads"]
             if _chipbench.spec.resolve(_chipbench.spec.load_benchmark(),
                                        w["name"]).traffic["kind"]
             == "llm_train"]


def _run(cell, **runner_kw):
    return bench.run(cell, SEED, 0.5, False, jax.devices(),
                     runner_kw=runner_kw)


@pytest.mark.parametrize("workload", LLM_CELLS)
def test_tiny_run_is_correct(workload):
    cell = _chipbench.tiny_cell(workload, **TINY)
    r = _run(cell)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"delta1_gap", "delta3_gap"}


def test_control_is_not_correct():
    """The reference held in bfloat16, put in the program's place."""
    cell = _chipbench.tiny_cell("qwen3-g22", **TINY)
    d = drive_llm.LLMCell(cell, SEED, jax.devices())
    d.setup()
    d.release()
    ref = d.reference_norms()
    assert compare.judge(d.check(ref))
    control = d.reference_norms(dtype=jnp.bfloat16)
    checks = drive_llm.judge_numbers(control, ref, cell.limits,
                                     cell.traffic["check_steps"])
    assert not compare.judge(checks), checks


def _faulty_trainer(fault):
    from repro.launch import train

    def make(cfg, replicas, **kw):
        if fault == "half_batch":
            kw = dict(kw, mb_per_epoch=max(1, kw["mb_per_epoch"] // 2))
        step, params, mesh = train.make_trainer(cfg, replicas, **kw)
        if fault == "unchanged":
            return (lambda p, b: p), params, mesh
        if fault == "half_batch":
            def half(p, b):
                n = b["tokens"].shape[0]
                per = n // (replicas[0] * replicas[1] * replicas[2])
                keep = jnp.concatenate([jnp.arange(r, r + per // 2)
                                        for r in range(0, n, per)])
                return step(p, {k: v[keep] for k, v in b.items()})
            return half, params, mesh
        if fault == "altered":
            def altered(p, b):
                out = step(p, b)
                return dict(out, final_norm=out["final_norm"] * 1.01)
            return altered, params, mesh
        return step, params, mesh

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_aggregation", "altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged; half of each replica's rows left out; the edge and cloud
    means left out; the state altered where the step produces it."""
    from repro.launch import train
    if fault == "no_aggregation":
        monkeypatch.setattr(train, "_edge_mean", lambda p: p)
        monkeypatch.setattr(train, "_cloud_mean", lambda p: p)
    cell = _chipbench.tiny_cell("qwen3-g22", **TINY)
    r = _run(cell, make_trainer=_faulty_trainer(fault))
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("tied", [True, False])
def test_reference_builds_the_programs_weights(tied):
    """The reference's initializer, written from the configuration, gives
    the weights the program starts from, with the output head tied to the
    embedding or apart: the compared changes start from one point."""
    import numpy as np
    import compare
    import ref_qwen3
    from repro.models import build_model
    cell = _chipbench.tiny_cell("qwen3-g22", **TINY)
    cell.config["tie_word_embeddings"] = tied
    key = jax.random.PRNGKey(1234)
    mine = compare.flat_leaves(ref_qwen3.init(key, cell.config))
    assert ("unembed" in mine) is not tied
    prog = compare.flat_leaves(build_model(
        drive_llm.arch_config(cell.config)).init(key))
    assert sorted(mine) == sorted(prog)
    for k in mine:
        np.testing.assert_allclose(np.asarray(mine[k]), np.asarray(prog[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


def test_window_waits_for_every_round_it_sent():
    """The window keeps at most ``ahead`` rounds in flight, stops sending
    once the rounds sent would fill its time, and counts the tokens of
    every round sent over the time until the last of them has ended."""
    cell = _chipbench.tiny_cell("qwen3-g22", **TINY)
    d = drive_llm.LLMCell(cell, SEED, jax.devices())
    d.setup()
    assert d.round_est > 0 and d.ahead >= 1
    d.ahead = 2
    before = d.rounds_done
    counts = d.window(0.5)
    assert counts["rounds"] >= 1
    assert d.rounds_done - before == counts["rounds"]
    assert len(counts["round_s"]) == counts["rounds"]
    assert counts["work"] == counts["rounds"] * drive_llm.tokens_per_round(
        cell.traffic)
    assert abs(counts["elapsed_s"] - sum(counts["round_s"])) < 1e-9
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in jax.tree.leaves(d.params))
