"""The readings of an ``llm_train`` cell's limits, taken in two parts.

    python3 benchmarks/chip/readings_apart.py program --workload <name> \\
        --seeds 8 [--first-seed N] --out prog.json
    python3 benchmarks/chip/readings_apart.py reference --workload <name> \\
        --seeds 8 --controls 3 [--first-seed N] --out ref.json
    python3 benchmarks/chip/readings_apart.py judge --workload <name> \\
        prog.json ref.json

The reference replays the replicas one at a time on one chip, so only
the program needs the cell's chips: ``program`` runs set-up on them
(the program's first checked steps) and keeps the change norms;
``reference`` runs on one chip and keeps the reference's norms and, for
the first ``--controls`` seeds, those of the control and the faults
that ``readings.py`` plants, and of one more where a round has more than
one edge: the cloud mean left out. Replica (0, 0, 0) then holds the
mean of its own edge, the reference replaying edge 0 alone on its rows.
``judge`` reads both files on any host and prints one JSON line per
reading, as ``readings.py`` does, then one line with, per compared
number, the sound runs' range and median and the least upper reading.
Seeds are ``first_seed + 7919 * i``, as in ``readings.py``. Not part of
a benchmark run; the limits files under ``limits/`` cite what it printed.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import readings  # noqa: E402
import spec  # noqa: E402

NO_CLOUD = "no_cloud_mean"


def seeds(first: int, n: int) -> list:
    return [first + 7919 * i for i in range(n)]


def program_norms(cell, seed: int, devices) -> dict:
    """The program's change norms of replica (0, 0, 0) after its first
    checked steps."""
    import drive_llm
    d = drive_llm.LLMCell(cell, seed, devices)
    d.setup()
    d.release()
    return d.prog_norms


def edge0_reference(c, t, init_seed, host_batches) -> dict:
    """The reference of edge 0 alone (the cloud mean left out): its
    replicas' rows, its replicas as one edge."""
    import drive_llm
    n_fl = t["replicas"][2]
    rows = n_fl * t["seqs_per_replica"]
    batches = [{k: v[:rows] for k, v in b.items()} for b in host_batches]
    return drive_llm.reference_norms(
        c, dict(t, replicas=[1, 1, n_fl]), init_seed, batches)


def reference_norms(cell, seed: int, controls: bool) -> dict:
    """``{kind: norms}``: the reference's, and with ``controls`` the
    control's and each fault's."""
    import jax.numpy as jnp
    import drive_llm
    import traffic as traffic_gen
    c, t = cell.config, cell.traffic
    init_seed = traffic_gen.sub_seed(seed, 0)
    batches = traffic_gen.token_batches(seed, t, c["vocab_size"])
    batches = batches[:t["check_steps"]]

    def ref(**kw):
        return drive_llm.reference_norms(c, t, init_seed, batches, **kw)
    out = {"reference": ref()}
    if controls:
        out["control"] = ref(dtype=jnp.bfloat16)
        for f in readings.FAULTS:
            out[f] = ref(fault=f)
        if t["replicas"][0] * t["replicas"][1] > 1:
            out[NO_CLOUD] = edge0_reference(c, t, init_seed, batches)
    return out


def _keyed(norms: dict) -> dict:
    """Norms read back from JSON: step keys to ints."""
    return {int(k): v for k, v in norms.items()}


def judge(cell, prog: dict, ref: dict) -> list:
    """Rows of ``readings.py``'s form from the two parts' files."""
    import drive_llm
    steps = cell.traffic["check_steps"]
    rows = []
    for seed in sorted(ref, key=int):
        norms = {k: _keyed(v) for k, v in ref[seed].items()}
        base = norms.pop("reference")
        if seed in prog:
            norms = {"program": _keyed(prog[seed]), **norms}
        for kind, n in norms.items():
            checks = drive_llm.judge_numbers(n, base, cell.limits, steps)
            rows.append({"workload": cell.name, "seed": int(seed),
                         "kind": kind, **readings._numbers(checks)})
    return rows


def summary(rows: list, names) -> dict:
    """Per number: the sound runs' least, median and largest reading,
    and the least reading of a control or fault."""
    out = {}
    for name in names:
        sound = [r[name] for r in rows if r["kind"] == "program"]
        upper = [r[name] for r in rows if r["kind"] != "program"]
        out[name] = {"sound_runs": len(sound),
                     "sound_min": min(sound, default=None),
                     "sound_median": (statistics.median(sound)
                                      if sound else None),
                     "sound_max": max(sound, default=None),
                     "upper_min": min(upper, default=None)}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("part", choices=("program", "reference", "judge"))
    ap.add_argument("files", nargs="*")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    if cell.traffic["kind"] != "llm_train":
        sys.exit("readings_apart: an llm_train cell only")
    if args.part == "judge":
        prog, ref = ({str(k): v for k, v in json.load(open(p)).items()}
                     for p in args.files)
        rows = judge(cell, prog, ref)
        for row in rows:
            print(json.dumps(row), flush=True)
        print(json.dumps(summary(rows, sorted(cell.limits))), flush=True)
        return
    import bench
    bench.use_compile_cache()
    if args.part == "program":
        devices = bench.find_chips(cell.chips)[:cell.chips]
    else:
        bench.find_chips(1)
    got = {}
    for i, seed in enumerate(seeds(args.first_seed, args.seeds)):
        if args.part == "program":
            got[seed] = program_norms(cell, seed, devices)
        else:
            got[seed] = reference_norms(cell, seed, i < args.controls)
        print(json.dumps({"seed": seed, "read": sorted(map(str, got[seed]))}),
              flush=True)
        if args.out:          # after every seed: a cut run keeps its part
            with open(args.out, "w") as f:
                json.dump(got, f)


if __name__ == "__main__":
    main()
