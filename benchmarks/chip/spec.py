"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under this directory:

    configs/<config>.json        the configuration as it is run
    traffic/<traffic>.json       the parameters of one traffic mix
    limits/<workload>.json       the limits of the cell's correctness check
    metrics/<metric>.py          the reader of one per-layer metric
    <reference>.py               the plain reference a configuration names

so a cell or a metric is added with new files and new entries alone.
An LLM configuration names its reference module under ``"reference"``;
the module gives ``init(key, c)``, ``loss(params, tokens, labels, c,
dtype)`` and ``train_flops_per_token(c, seq_len)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# a module's path under this directory, without ".py": ref_x, tests/ref_y
REFERENCE_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*(/[A-Za-z_][A-Za-z0-9_]*)*")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(metric: str, here: str = HERE):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(here, "metrics", metric + ".py")
    mod_name = "chipbench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name):
    """The reference module ``<name>.py`` under this directory that a
    configuration names under ``"reference"``. ValueError for a name
    that is no module's path (None, where the configuration names
    none), FileNotFoundError for one with no file; both say the name."""
    if not isinstance(name, str) or not REFERENCE_RE.fullmatch(name):
        raise ValueError(f"reference {name!r}: not the path of a module "
                         f"under {HERE}")
    path = os.path.join(HERE, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"reference {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # metric entries the cell reports with --trace 0
    per_layer: list       # metric entries the cell reports with --trace 1


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(bench: dict, workload: str, root: str = ROOT,
            here: str = HERE) -> Cell:
    """The cell ``workload`` with its config, traffic and limits loaded
    from the files named after them. KeyError for an unknown cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(here, "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(here, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)
