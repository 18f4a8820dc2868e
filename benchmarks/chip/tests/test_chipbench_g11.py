"""The four-chip cell ``qwen3-4chip-g11`` at a tiny size on the CPU: its
control and each fault make ``correct`` false under the cell's own
limits, the cloud mean left out among them (each chip would then keep
its own edge's model); ``readings_apart.py`` reads the same faults from
the reference; and on four virtual devices the cell resolves by name,
lays one edge of 4 replicas on each device and is correct through
``bench.run``."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

import _chipbench
import compare
import drive_llm
import readings_apart
from test_chipbench_llm import TINY, _faulty_trainer, _run

CELL = "qwen3-4chip-g11"


def test_control_is_not_correct():
    """The reference held in bfloat16, put in the program's place."""
    cell = _chipbench.tiny_cell(CELL, **TINY)
    d = drive_llm.LLMCell(cell, _chipbench.BIG_SEED, jax.devices())
    d.setup()
    d.release()
    ref = d.reference_norms()
    assert compare.judge(d.check(ref))
    control = d.reference_norms(dtype=jnp.bfloat16)
    checks = drive_llm.judge_numbers(control, ref, cell.limits,
                                     cell.traffic["check_steps"])
    assert not compare.judge(checks), checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_aggregation", "no_cloud_mean",
                                   "altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    from repro.launch import train
    if fault in ("no_aggregation", "no_cloud_mean"):
        monkeypatch.setattr(train, "_cloud_mean", lambda p: p)
    if fault == "no_aggregation":
        monkeypatch.setattr(train, "_edge_mean", lambda p: p)
    cell = _chipbench.tiny_cell(CELL, **TINY)
    r = _run(cell, make_trainer=_faulty_trainer(fault))
    assert r["correct"] is False, r["checks"]


def test_the_reference_replays_the_cloud_mean_left_out(monkeypatch):
    """With the cloud mean left out, replica (0, 0, 0) follows the
    reference of edge 0 alone, within the cell's limits: the upper
    reading ``readings_apart.py`` takes on one chip is the program's."""
    from repro.launch import train
    monkeypatch.setattr(train, "_cloud_mean", lambda p: p)
    cell = _chipbench.tiny_cell(CELL, **TINY)
    d = drive_llm.LLMCell(cell, _chipbench.BIG_SEED, jax.devices())
    d.setup()
    d.release()
    edge0 = readings_apart.edge0_reference(d.c, d.t, d.init_seed,
                                           d.host_batches)
    assert compare.judge(d.check(edge0)), d.check(edge0)
    assert not compare.judge(d.check())


def test_readings_apart_judges_its_two_parts():
    """The program's part and the reference's part, through the JSON
    their files carry, give the sound run within the limits and the
    control and every fault outside them."""
    cell = _chipbench.tiny_cell(CELL, **TINY)
    seed = _chipbench.BIG_SEED
    prog = {seed: readings_apart.program_norms(cell, seed, jax.devices())}
    ref = {seed: readings_apart.reference_norms(cell, seed, True)}
    prog, ref = json.loads(json.dumps(prog)), json.loads(json.dumps(ref))
    rows = readings_apart.judge(cell, prog, ref)
    assert [r["kind"] for r in rows] == [
        "program", "control", "half_batch", "no_aggregation",
        readings_apart.NO_CLOUD]
    for r in rows:
        within = all(r[k] <= cell.limits[k] for k in cell.limits)
        assert within == (r["kind"] == "program"), r
    s = readings_apart.summary(rows, sorted(cell.limits))
    assert s["delta1_gap"]["sound_runs"] == 1
    assert s["delta1_gap"]["sound_max"] == rows[0]["delta1_gap"]
    assert s["delta1_gap"]["upper_min"] == min(
        r["delta1_gap"] for r in rows[1:])


def test_runs_on_four_devices():
    src = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{_chipbench.TESTS!r}, {_chipbench.HERE!r}]
        import jax
        import _chipbench, bench, spec
        cell = spec.resolve(spec.load_benchmark(), {CELL!r})
        assert cell.chips == 4 and len(jax.devices()) == 4
        names = [m["name"] for m in cell.per_layer]
        tiny = _chipbench.tiny_cell({CELL!r}, **{TINY!r})
        seen = {{}}
        import drive_llm
        setup = drive_llm.LLMCell.setup

        def spy(self):
            setup(self)
            seen["mesh"] = dict(self.params["embed"].sharding.mesh.shape)
            seen["text"] = self.step.lower(
                self.params, self.batches[0]).compile().as_text()
        drive_llm.LLMCell.setup = spy
        r = bench.run(tiny, {_chipbench.BIG_SEED}, 0.5, False,
                      jax.devices())
        print(json.dumps({{"correct": r["correct"], "checks": r["checks"],
                          "mesh": seen["mesh"], "per_layer": names,
                          "all_reduce": "all-reduce" in seen["text"]}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(_chipbench.ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, r["checks"]
    assert r["mesh"] == {"pod": 1, "edge": 4, "fl": 1, "fsdp": 1, "tp": 1}
    assert r["all_reduce"]
    assert set(r["per_layer"]) == {"mfu.llm", "idle_share.llm",
                                   "allreduce_exposed_share.llm"}
