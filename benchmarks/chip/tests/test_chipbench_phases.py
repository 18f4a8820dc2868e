"""The phase split of ``phases.py`` and the exposed all-reduce reader:
on hand-made intervals and on a small trace recorded on a TPU v5e
(``data/tiny_arena_tpu.trace.json.gz``: the profiler's trace-viewer
export of six ``HFLEnv.step`` calls of the tiny Arena cell, 4 devices on
2 edges, γ ≤ 2, under ``bench.window``; the event arguments the
reduction does not read are left out)."""
import os

import pytest

import _chipbench
import phases
import spec
import tracereduce

DATA = os.path.join(_chipbench.TESTS, "data")
TPU_TRACE = os.path.join(DATA, "tiny_arena_tpu.trace.json.gz")
CPU_TRACE = os.path.join(DATA, "cpu_window.xplane.pb")


def test_scope_path_of_an_op_name():
    f = phases.scope_path
    assert f("jit(step)/while/body/hfl.local/while/body/"
             "transpose(jvp(hfl.update))/mul:") == ("hfl.local", "hfl.update")
    assert f("jit(train_step)/hfl.cloud_agg/reduce_sum:") == (
        "hfl.cloud_agg",)
    assert f("jit(step)/while/body/dot_general") == ()
    assert f(None) == () and f("") == ()


def test_op_kinds():
    assert phases.op_kind("fusion.12") == "fusion"
    assert phases.op_kind("all-reduce-start.3") == "all-reduce-start"
    assert phases.op_kind("all-reduce") == "all-reduce"
    assert phases.is_container("while.118") and not phases.is_container(
        "fusion.1")


def test_an_unscoped_op_takes_its_innermost_scoped_container():
    """The trace gives loops no ``op_name``: a loop's scope is the one all
    the scoped operations inside it share."""
    L, U, E = ("hfl.local",), ("hfl.local", "hfl.update"), ("hfl.edge_agg",)
    evs = [("while.1", 0.0, 10.0, ()),              # the round's loop
           ("while.2", 1.0, 6.0, ()),               # an epoch loop
           ("while.3", 2.0, 5.0, ()),               # a minibatch loop
           ("copy.1", 2.5, 3.0, ()),                # inside all three
           ("fusion.1", 3.0, 4.0, U),
           ("fusion.3", 4.0, 4.5, L),
           ("copy.4", 5.2, 5.5, ()),                # in the epoch loop
           ("fusion.4", 5.5, 6.0, L),
           ("copy.2", 7.0, 8.0, ()),                # in the round only
           ("fusion.2", 8.0, 9.0, E),
           ("copy.3", 11.0, 12.0, ())]              # in no container
    leaves = phases.attribute(evs)
    assert [(n, sc) for n, _, _, sc in leaves] == [
        ("copy.1", "hfl.local"), ("fusion.1", "hfl.update"),
        ("fusion.3", "hfl.local"), ("copy.4", "hfl.local"),
        ("fusion.4", "hfl.local"), ("copy.2", phases.UNSCOPED),
        ("fusion.2", "hfl.edge_agg"), ("copy.3", phases.UNSCOPED)]
    # a loop holding only update ops is an update loop
    only = [("while.9", 0.0, 3.0, ()), ("fusion.9", 0.0, 1.0, U),
            ("copy.9", 1.0, 2.0, ())]
    assert phases.attribute(only)[-1][3] == "hfl.update"


def test_scope_time_is_self_time_within_the_window():
    leaves = [("fusion.1", 0.0, 2.0, "hfl.local"),
              ("fusion.2", 2.0, 3.0, "hfl.update"),     # nested in local
              ("fusion.3", 3.0, 5.0, "hfl.local"),
              ("copy.1", 5.0, 7.0, phases.UNSCOPED)]
    assert phases.scope_time(leaves, 1.0, 6.0) == {
        "hfl.local": pytest.approx(3.0), "hfl.update": pytest.approx(1.0),
        phases.UNSCOPED: pytest.approx(1.0)}


def test_split_accounts_for_the_window_on_every_chip():
    chip0 = [("while.1", 0.0, 8.0, ()),
             ("fusion.1", 0.0, 4.0, ("hfl.local",)),
             ("fusion.2", 4.0, 5.0, ("hfl.local", "hfl.update")),
             ("copy.1", 5.0, 6.0, ()),
             ("all-reduce.1", 6.0, 8.0, ("hfl.cloud_agg",))]
    chip1 = [("fusion.1", 0.0, 6.0, ("hfl.local",))]
    trace = {"ops": {"/device:TPU:0": chip0, "/device:TPU:1": chip1},
             "spans": [("bench.window", 0.0, 10.0),
                       ("bench.step", 0.0, 10.0),
                       ("hfl.acc", 6.0, 10.0),
                       ("hfl.state", 8.5, 9.5)]}
    s = phases.split(trace, "bench.window")
    assert s["chips"] == 2
    # copy.1's loop holds local, update and cloud ops: it has no scope
    assert s["shares"] == {
        "hfl.local": pytest.approx(50.0), "hfl.update": pytest.approx(5.0),
        "hfl.cloud_agg": pytest.approx(10.0),
        phases.UNSCOPED: pytest.approx(5.0)}
    assert s["idle_share"] == pytest.approx(30.0)
    assert s["accounted"] == pytest.approx(100.0)
    # the all-reduce metric's reading: its op time, over the chips
    assert s["allreduce_exposed_share"] == pytest.approx(10.0)
    # a gap is named by the host span that was innermost for most of it,
    # the program's included; hfl.acc and hfl.state hold chip 0's gap
    # 1 s each, and the tie goes to the earlier
    assert s["idle_gaps"] == [["hfl.acc", pytest.approx(4.0)],
                              ["hfl.acc", pytest.approx(2.0)]]


def test_a_gap_across_a_step_s_phases_takes_the_phase_that_held_it():
    """A gap that runs from one phase of an ``HFLEnv.step`` into the next
    is named by the phase the host spent most of it in, not by
    ``bench.step``, which covers all of it; a gap inside one phase takes
    that phase."""
    chip = [("fusion.1", 0.0, 1.0, ("hfl.eval",)),
            ("fusion.2", 3.0, 6.0, ("hfl.local",)),
            ("fusion.3", 6.5, 7.0, ("hfl.eval",)),
            ("fusion.4", 7.5, 10.0, ("hfl.eval",))]
    trace = {"ops": {"/device:TPU:0": chip},
             "spans": [("bench.window", 0.0, 10.0),
                       ("bench.step", 0.5, 9.5),
                       ("hfl.step", 0.6, 9.4),
                       ("hfl.costs", 0.7, 1.5),
                       ("hfl.round", 1.5, 3.2),
                       ("hfl.acc", 3.2, 6.4),
                       ("hfl.state", 6.4, 9.3)]}
    gaps = phases.split(trace, "bench.window")["idle_gaps"]
    assert gaps == [["hfl.round", pytest.approx(2.0)],
                    ["hfl.acc", pytest.approx(0.5)],
                    ["hfl.state", pytest.approx(0.5)]]
    # the benchmark's own rule takes the span that covers most of a gap:
    # the whole step, whichever phase held it
    bench_rule = tracereduce.idle_gaps(
        [(n, s, e) for n, s, e, _ in chip], trace["spans"][1:], 0.0, 10.0)
    assert bench_rule[0] == ["hfl.step", pytest.approx(2.0)]


def test_summarize_keeps_its_keys_on_the_cpu_trace():
    """The benchmark's own reduction is untouched: the recorded CPU
    window still reduces to the keys its readers take."""
    tr = tracereduce.extract(CPU_TRACE, plane_prefix="/host:CPU",
                             line_pred=lambda n: n.startswith("tf_XLA"))
    assert set(tracereduce.summarize(tr, "bench.window")) == {
        "window_s", "busy_s", "chips", "op_time", "device_ops",
        "idle_gaps"}


@pytest.fixture(scope="module")
def tpu_trace():
    return phases.extract(TPU_TRACE)


@pytest.fixture(scope="module")
def tpu_split(tpu_trace):
    return phases.split(tpu_trace, "bench.window")


def test_tpu_trace_names_the_round_s_phases(tpu_split):
    s = tpu_split
    assert {"hfl.local", "hfl.update", "hfl.edge_agg", "hfl.cloud_agg",
            "hfl.eval"} <= set(s["shares"])
    assert s["shares"]["hfl.local"] > s["shares"]["hfl.edge_agg"]
    assert s["accounted"] == pytest.approx(100.0, abs=0.5)
    assert s["shares"].get(phases.UNSCOPED, 0.0) < 5.0
    assert s["allreduce_exposed_share"] is None     # one chip
    assert s["idle_gaps"] and all(
        name.startswith(("hfl.", "bench.")) for name, _ in s["idle_gaps"])
    # the trace predates the hfl.step span: the longest gaps, which
    # tracereduce's rule names bench.step, are held by the step's phases
    assert [n for n, _ in s["idle_gaps"][:6]] == [
        "hfl.state"] + ["hfl.acc"] * 5


def test_tpu_trace_holds_the_named_kernels(tpu_trace):
    (plane, evs), = tpu_trace["ops"].items()
    assert plane == "/device:TPU:0"
    kernels = {}
    for n, _, _, path in evs:
        if phases.op_kind(n) in ("segment_agg", "segment_broadcast"):
            kernels.setdefault(phases.op_kind(n), set()).add(path)
    # the edge means and the cloud mean launch segment_agg; the resync
    # is segment_broadcast
    assert kernels == {"segment_agg": {("hfl.edge_agg",), ("hfl.cloud_agg",)},
                       "segment_broadcast": {("hfl.edge_agg",)}}
    spans = {n for n, _, _ in tpu_trace["spans"]}
    assert {"bench.window", "bench.step", "hfl.costs", "hfl.round",
            "hfl.acc", "hfl.state"} <= spans


def _reader_ctx(op_time, window_s=10.0):
    return {"trace": {"op_time": op_time, "window_s": window_s}}


def test_allreduce_exposed_share_reader():
    read = spec.load_reader("allreduce_exposed_share.llm")
    assert read(_reader_ctx({"fusion.1": 6.0, "all-reduce.3": 0.5,
                             "all-reduce": 0.25, "while.2": 9.0})) == \
        pytest.approx(7.5)
    assert read(_reader_ctx({"all-reduce-start.1": 0.1,
                             "all-reduce-done.1": 0.4})) == \
        pytest.approx(5.0)
    # nothing to read: one chip, or no trace
    assert read(_reader_ctx({"fusion.1": 6.0})) is None
    assert read({"trace": None}) is None


def test_allreduce_exposed_share_is_the_four_chip_cell_s():
    bench = spec.load_benchmark()
    m, = [m for m in bench["per_layer"]
          if m["name"] == "allreduce_exposed_share.llm"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert m["workloads"] == ["qwen3-4chip-g11"]
    assert cells["qwen3-4chip-g11"]["chips"] == 4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
