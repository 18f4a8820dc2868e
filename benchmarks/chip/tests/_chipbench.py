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
    swapped for the tiny one of the same kind, the cell's own traffic
    (cut by ``traffic_over``) and the cell's own limits."""
    cell = spec.resolve(spec.load_benchmark(), workload)
    kind = cell.traffic["kind"]
    tiny = {"llm_train": "tiny-qwen3.json", "arena_env": "tiny-arena.json"}
    cell.config = load(os.path.join(TESTS, tiny[kind]))
    cell.traffic = dict(cell.traffic, **traffic_over)
    return cell
