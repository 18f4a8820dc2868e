"""BENCHMARK.json against the contract's shape, and every cell resolved
by name: configuration, traffic, limits and metric readers."""
import os

import pytest

import _chipbench
import spec

BENCH = spec.load_benchmark()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_shape():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["command"][1] == "benchmarks/chip/bench.py"
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(_chipbench.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            yield e["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert spec.NAME_RE.match(name), name


def test_units_metrics_and_unique_names():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(workload):
    cell = spec.resolve(BENCH, workload)
    assert cell.chips in (1, 4)
    assert cell.traffic["kind"] in ("llm_train", "arena_env")
    assert set(cell.limits) >= {"delta1_gap"}
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
        assert m["moves"] in e2e


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_under_paths(entry):
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    c = _chipbench.load(os.path.join(_chipbench.ROOT, entry["file"]))
    assert c["name"] == entry["name"] and c["source"] == entry["source"]
    assert sorted(c.get("reduced", {})) == sorted(entry["reduced"])


def test_a_new_cell_needs_only_files_and_entries(tmp_path):
    """A cell and a metric added as data: new files next to the
    benchmark's own and new entries, no change to the harness."""
    here = tmp_path / "chip"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "c.json").write_text('{"name": "c"}')
    (here / "traffic" / "t2.json").write_text('{"kind": "llm_train"}')
    (here / "limits" / "c.t2.json").write_text('{"delta1_gap": 1}')
    (here / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx['x']\n")
    bench = {
        "configs": [{"name": "c", "file": "chip/configs/c.json"}],
        "workloads": [{"name": "c.t2", "config": "c", "traffic": "t2",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s"}, {"name": "rate"}],
        "per_layer": [{"name": "new.metric", "moves": "rate"}],
    }
    cell = spec.resolve(bench, "c.t2", root=str(tmp_path), here=str(here))
    assert cell.config == {"name": "c"} and cell.limits == {"delta1_gap": 1}
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert spec.load_reader("new.metric", here=str(here))({"x": 3}) == 3
