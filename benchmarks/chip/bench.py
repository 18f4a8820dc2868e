"""Run one cell of the chip benchmark once, in this process.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic, limits and metric readers are
found by name from ``BENCHMARK.json`` (see ``spec.py``). The run needs a
TPU with at least the cell's chips and fails on anything else, with
nothing on standard output. It builds the program and its inputs from
``--seed`` (set-up, reported as ``setup_s``), measures for ``--seconds``,
frees the program's state, checks what the window's path produced
against the plain reference, and prints one JSON line last on standard
output. With ``--trace 1`` the window runs under the profiler and the
line holds the cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WINDOW_SPAN = "bench.window"


class CompileCounter:
    """Counts compilations, and programs fetched from the persistent
    cache, from the moment it is armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout; every program is kept, however quickly it compiled, and
    none is evicted (a cell's programs take tens of MB)."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_chips(chips: int):
    """The first ``chips`` TPU devices; exits non-zero on anything else."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} devices)")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    return devs


def make_runner(cell, seed: int, devices, **kw):
    kind = cell.traffic["kind"]
    if kind == "llm_train":
        import drive_llm
        return drive_llm.LLMCell(cell, seed, devices, **kw)
    if kind == "arena_env":
        import drive_arena
        return drive_arena.ArenaCell(cell, seed, devices, **kw)
    raise ValueError(f"unknown traffic kind {kind!r}")


def read_trace(trace_dir: str) -> dict:
    import tracereduce
    tr = tracereduce.extract(tracereduce.find_xplane(trace_dir))
    return tracereduce.summarize(tr, WINDOW_SPAN)


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float = None, runner_kw=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    import jax
    import compare
    import peaks as peaks_mod
    t_start = T_START if t_start is None else t_start
    counter = CompileCounter()
    chips = devices[:cell.chips]
    runner = make_runner(cell, seed, chips, **(runner_kw or {}))
    runner.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    counter.armed = True
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            counts = runner.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        counter.armed = False
        failed = runner.failed_rounds(counts["rounds"])
        summary = read_trace(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"bench: compilations inside the window: {counter.count}",
          flush=True)
    rs = counts["round_s"]
    print(f"bench: seconds per round in the window: median "
          f"{sorted(rs)[len(rs) // 2]!r}, slowest {max(rs)!r} "
          f"(round {rs.index(max(rs)) + 1} of {len(rs)})",
          file=sys.stderr, flush=True)
    if "useful_share" in counts:
        print(f"bench: useful share of the window's computed epochs: "
              f"{counts['useful_share']!r}", file=sys.stderr, flush=True)
    mem = [d.memory_stats() or {} for d in chips]
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)
    runner.release()
    checks = runner.check()

    rates = runner.rates(counts)
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        metrics = {}
        ctx = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
               "counts": counts, "rates": rates, "chips": len(chips),
               "peaks": peaks_mod.peaks(chips[0].device_kind),
               "trace": summary}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else rates[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": compare.judge(checks),
              "attempted": counts["rounds"], "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    # which leaf each worst-leaf gap came from, for standard error only
    result["_worst_leaf"] = {k: v["leaf"] for k, v in checks.items()
                             if "leaf" in v}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    use_compile_cache()
    devices = find_chips(cell.chips)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    leaves = result.pop("_worst_leaf")
    for k, v in result["checks"].items():
        where = f" (worst leaf {leaves[k]})" if k in leaves else ""
        print(f"check {k} {v['value']!r} limit {v['limit']!r}{where}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
