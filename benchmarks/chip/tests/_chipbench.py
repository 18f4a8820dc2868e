"""Shared set-up of the chip benchmark's tests: the harness's modules and
the program importable, and the tiny cells the CPU runs stand in with."""
from __future__ import annotations

import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
HERE = os.path.dirname(TESTS)
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def tiny_cell(workload: str, **traffic_over) -> spec.Cell:
    """The cell ``workload`` of BENCHMARK.json with its configuration
    swapped for that configuration's tiny stand-in,
    ``tests/tiny-<config>.json``, the cell's own traffic (cut by
    ``traffic_over``) and the cell's own limits."""
    bench = spec.load_benchmark()
    cell = spec.resolve(bench, workload)
    config = {w["name"]: w["config"] for w in bench["workloads"]}[workload]
    cell.config = load(os.path.join(TESTS, f"tiny-{config}.json"))
    cell.traffic = dict(cell.traffic, **traffic_over)
    return cell
