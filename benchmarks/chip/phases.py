"""How a cell's rounds split into their phases, read from a chip trace.

    python3 benchmarks/chip/phases.py --workload <name> --seed <n> \\
        --seconds <s> [--keep DIR]

Runs the cell's set-up and one traced window as ``bench.py`` does, with
no correctness check, and prints one JSON line: the share of the
window each ``hfl.*`` device scope of the program took (self time: a
nested scope's operations count in that scope alone), the device time no
scope claims, and, as the benchmark's own reduction reads them
(``tracereduce.summarize`` and the metric readers), the idle share, the
exposed all-reduce share; and the idle gaps, each named by the host
span of the program (``hfl.*``) or of the benchmark (``bench.*``) that
was the innermost open one for most of it.
For an environment cell it adds the device-epochs the program counted in
the window (``HFLEnv.epoch_counts``). ``--keep DIR`` keeps the trace.
Not part of a benchmark run; PERF.md cites what it printed.

Each device operation takes the innermost ``hfl.*`` component of its
``op_name``, which the TPU trace carries in the operation's ``tf_op``
stat (a fusion carries its root instruction's). An operation with none,
such as a copy XLA put in for a loop's carry, takes the scope of the
innermost loop, call or conditional that encloses it on the chip's
operation line. Times are in seconds.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import spec  # noqa: E402
import tracereduce  # noqa: E402

SCOPE_RE = re.compile(r"hfl\.[A-Za-z_]+")
SCOPE_STAT = "tf_op"
SPAN_PREFIXES = (tracereduce.HOST_SPAN_PREFIX, "hfl.")
UNSCOPED = "unscoped"
EXPOSED_READER = "allreduce_exposed_share.llm"


def scope_path(op_name: str) -> tuple:
    """The ``hfl.*`` components of an ``op_name``, outermost first."""
    return tuple(SCOPE_RE.findall(op_name or ""))


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``all-reduce-start.3`` ->
    ``all-reduce-start``."""
    return name.rsplit(".", 1)[0] if name[-1:].isdigit() else name


def is_container(name: str) -> bool:
    return op_kind(name) in tracereduce.CONTAINER_OPS


def find_trace(trace_dir: str) -> str:
    """The trace-viewer export the profiler writes beside the
    ``.xplane.pb``: ``<host>.trace.json.gz``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**",
                                          "*.trace.json.gz"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    return paths[-1]


def extract(path: str,
            plane_prefix: str = tracereduce.DEVICE_PLANE_PREFIX,
            line: str = tracereduce.DEVICE_OP_LINE) -> dict:
    """Device operations per chip with their scopes, and the host spans,
    from the trace-viewer export (``find_trace``), which gives each
    operation its ``tf_op`` stat (``jax.profiler.ProfileData`` reads the
    same events from the ``.xplane.pb``, but not that stat).

    Returns ``{"ops": {plane: [(name, start, end, path), ...]},
    "spans": [(name, start, end), ...]}``; ``path`` holds the ``hfl.*``
    components of the operation's ``op_name`` (``scope_path``)."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, lines = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            lines[e["pid"], e["tid"]] = e["args"]["name"]
    ops, spans = {}, []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        s = e["ts"] * 1e-6
        end = s + e.get("dur", 0.0) * 1e-6
        if (proc.startswith(plane_prefix)
                and lines.get((e["pid"], e.get("tid"))) == line):
            ops.setdefault(proc, []).append(
                (e["name"], s, end,
                 scope_path(e.get("args", {}).get(SCOPE_STAT))))
        elif (proc == tracereduce.HOST_PLANE
              and e["name"].startswith(SPAN_PREFIXES)):
            spans.append((e["name"], s, end))
    for evs in ops.values():
        evs.sort(key=lambda v: (v[1], -v[2]))
    spans.sort(key=lambda v: v[1])
    return {"ops": ops, "spans": spans}


def _common(a: tuple, b: tuple) -> tuple:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def attribute(events) -> list:
    """Leaf operations of one chip's line as ``(name, start, end,
    scope)``, ``scope`` the innermost of the operation's ``path``.

    An operation with none takes the scope of the innermost enclosing
    container op that has one, else ``UNSCOPED``. The trace gives loops
    no ``op_name``, so a container's scope is the deepest scope that
    every scoped operation inside it shares: a loop whose body is all
    local training is ``hfl.local``, and the round's loop, which holds
    training and aggregation both, has none."""
    evs = sorted(events, key=lambda v: (v[1], -v[2]))
    shared = {}                   # container index -> common path
    open_ = []                    # (index, end), outermost first
    for i, (name, s, e, path) in enumerate(evs):
        while open_ and open_[-1][1] <= s:
            open_.pop()
        if is_container(name):
            open_.append((i, e))
        elif path:
            for ci, ce in open_:
                if ce >= e:
                    shared[ci] = _common(shared.get(ci, path), path)
    out, open_ = [], []           # (scope or None, end)
    for i, (name, s, e, path) in enumerate(evs):
        while open_ and open_[-1][1] <= s:
            open_.pop()
        if is_container(name):
            open_.append(((shared.get(i) or (None,))[-1], e))
            continue
        scope = path[-1] if path else next(
            (c for c, ce in reversed(open_) if c is not None and ce >= e),
            UNSCOPED)
        out.append((name, s, e, scope))
    return out


def scope_time(leaves, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi] each scope's leaf operations ran (self time:
    every leaf counts in its innermost scope only)."""
    out = collections.Counter()
    for _, s, e, scope in leaves:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[scope] += e - s
    return dict(out)


def idle_gaps(events, spans, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest gaps in [lo, hi] with no operation running, as
    ``[name, seconds]``: the name is that of the host span that was the
    innermost one open for the longest part of the gap (ties to the
    earlier), or ``"no host span"``. ``tracereduce.idle_gaps`` takes the
    span that covers most of a gap instead; a gap that runs from one
    phase of an ``HFLEnv.step`` into the next is covered most by the
    step's outer span, so that rule names it ``bench.step`` whichever
    phase held it."""
    busy_iv = tracereduce.merge(tracereduce.clip(
        [(ev[1], ev[2]) for ev in events], lo, hi))
    gaps = tracereduce.subtract([(lo, hi)], busy_iv)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:top]:
        cuts = sorted({gs, ge} | {t for _, s, e in spans for t in (s, e)
                                  if gs < t < ge})
        held = collections.Counter()
        for a, b in zip(cuts, cuts[1:]):
            m = 0.5 * (a + b)
            held[min(((e - s, name) for name, s, e in spans if s <= m < e),
                     default=(0.0, "no host span"))[1]] += b - a
        out.append([max(held, key=held.get), ge - gs])
    return out


def split(trace: dict, window_span: str, top: int = 10) -> dict:
    """The window's split, in % of the window averaged over the chips:
    each scope's self time and the unscoped rest; busy time and the
    exposed all-reduce as ``tracereduce.summarize`` and the all-reduce
    metric's reader take them; the longest idle gaps (``idle_gaps``)."""
    summary = tracereduce.summarize(
        {"ops": {p: [(n, s, e) for n, s, e, _ in evs]
                 for p, evs in trace["ops"].items()},
         "spans": trace["spans"]}, window_span, top)
    _, lo, hi = next(sp for sp in trace["spans"] if sp[0] == window_span)
    n, w = summary["chips"], summary["window_s"]
    host = [sp for sp in trace["spans"] if sp[0] != window_span]
    shares = collections.Counter()
    gaps = []
    for evs in trace["ops"].values():
        for k, v in scope_time(attribute(evs), lo, hi).items():
            shares[k] += 100.0 * v / w / n
        gaps += idle_gaps(evs, host, lo, hi, top)
    gaps.sort(key=lambda g: -g[1])
    idle = 100.0 * (1.0 - summary["busy_s"] / w)
    return {"window_s": w, "chips": n, "idle_share": idle,
            "shares": dict(sorted(shares.items())),
            "accounted": sum(shares.values()) + idle,
            "allreduce_exposed_share": spec.load_reader(EXPOSED_READER)(
                {"trace": summary}),
            "idle_gaps": gaps[:top]}


def main(argv=None) -> None:
    import jax
    import bench
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="",
                    help="directory to keep the trace in")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    bench.use_compile_cache()
    devices = bench.find_chips(cell.chips)[:cell.chips]
    runner = bench.make_runner(cell, args.seed, devices)
    runner.setup()
    env = getattr(runner, "env", None)
    before = dict(env.epoch_counts) if env is not None else None
    trace_dir = args.keep or tempfile.mkdtemp(prefix="phases-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(bench.WINDOW_SPAN):
            counts = runner.window(args.seconds)
        jax.profiler.stop_trace()
        out = split(extract(find_trace(trace_dir)), bench.WINDOW_SPAN)
    finally:
        if not args.keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    out.update(workload=cell.name, seed=args.seed, rounds=counts["rounds"],
               device=devices[0].device_kind)
    if env is not None:
        epochs = {k: env.epoch_counts[k] - before[k] for k in before}
        out["epochs"] = epochs
        out["useful_epoch_share"] = (100.0 * epochs["useful"]
                                     / epochs["computed"])
        out["drive_arena_useful_share"] = counts.get("useful_share")
    print(f"phases: unscoped {out['shares'].get(UNSCOPED, 0.0)!r}% of the "
          f"window; scopes + unscoped + idle = {out['accounted']!r}%",
          file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
