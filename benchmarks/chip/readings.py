"""The readings the limits of a cell's check are set from, on the chip.

    python3 benchmarks/chip/readings.py --workload <name> --seeds 12 \\
        --controls 3 [--first-seed N] [--out FILE]

For each of ``--seeds`` seeds: the program's sound run through set-up
(its first checked steps) against the reference (the lower readings).
For the first ``--controls`` seeds also: the control (the reference held
in bfloat16, put in the program's place) and the faults planted in the
reference put in the program's place (half of each batch left out; the
aggregation left out); a state left unchanged reads 1 and needs no run.
Prints one JSON line per reading and writes them all to ``--out``.
Not part of a benchmark run; the limits files under ``limits/`` cite
what it printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import bench  # noqa: E402
import spec  # noqa: E402

FAULTS = ("half_batch", "no_aggregation")


def _numbers(checks: dict) -> dict:
    out = {k: v["value"] for k, v in checks.items()}
    out.update({k + "_leaf": v["leaf"] for k, v in checks.items()
                if "leaf" in v})
    return out


def readings_llm(cell, seed, devices, controls: bool) -> list:
    import jax.numpy as jnp
    import drive_llm
    d = drive_llm.LLMCell(cell, seed, devices)
    d.setup()
    d.release()
    ref = d.reference_norms()
    out = [("program", _numbers(d.check(ref)))]
    if controls:
        steps = cell.traffic["check_steps"]
        out.append(("control", _numbers(drive_llm.judge_numbers(
            d.reference_norms(dtype=jnp.bfloat16), ref, cell.limits,
            steps))))
        for f in FAULTS:
            out.append((f, _numbers(drive_llm.judge_numbers(
                d.reference_norms(fault=f), ref, cell.limits, steps))))
    return out


def readings_arena(cell, seed, devices, controls: bool) -> list:
    import jax.numpy as jnp
    import drive_arena
    d = drive_arena.ArenaCell(cell, seed, devices)
    d.setup()
    d.release()
    ref = d.reference_models()
    out = [("program", _numbers(d.check(ref)))]
    if controls:
        import jax
        import ref_cnn
        steps = cell.traffic["check_steps"]
        tx, ty = d.inputs["test_x"], d.inputs["test_y"]
        with jax.default_matmul_precision("highest"):
            acc_fn = jax.jit(ref_cnn.accuracy)

        def tree(m, dt):
            return {n: jnp.asarray(v, dt) for n, v in m.items()}
        for name, kw in [("control", dict(dtype=jnp.bfloat16))] + [
                (f, dict(fault=f)) for f in FAULTS]:
            alt = d.reference_models(**kw)["models"]
            # each variant reports the accuracy its own arithmetic gives
            dt = kw.get("dtype", jnp.float32)
            accs = {k: float(acc_fn(tree(alt[k], dt), tx, ty))
                    for k in d.accs}
            models = {k: tree(m, jnp.float32) for k, m in alt.items()}
            out.append((name, _numbers(drive_arena.judge_numbers(
                models, accs, ref, d.inputs, cell.limits, steps))))
        # a stale answer: each step reports the accuracy of the model
        # before it
        stale = {k: float(acc_fn(tree(d.models[k - 1], jnp.float32), tx, ty))
                 for k in d.accs}
        out.append(("stale_answer", _numbers(drive_arena.judge_numbers(
            d.models, stale, ref, d.inputs, cell.limits, steps))))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    bench.use_compile_cache()
    devices = bench.find_chips(cell.chips)[:cell.chips]
    fn = {"llm_train": readings_llm,
          "arena_env": readings_arena}[cell.traffic["kind"]]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        for kind, nums in fn(cell, seed, devices, i < args.controls):
            row = {"workload": cell.name, "seed": seed, "kind": kind,
                   **nums}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
