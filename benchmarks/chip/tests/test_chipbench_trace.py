"""The reduction from a profiler trace to busy time, time by operation
and named idle gaps: on a small trace recorded
from a CPU run (``data/cpu_window.xplane.pb``: a jitted sin-then-matmul
called three times inside ``bench.window``, each call under
``bench.dispatch`` and its wait under ``bench.wait``, then a cosine sum)
and on hand-made intervals."""
import os

import pytest

import _chipbench
import tracereduce as tr

TRACE = os.path.join(_chipbench.TESTS, "data", "cpu_window.xplane.pb")


@pytest.fixture(scope="module")
def cpu_trace():
    # on the CPU the XLA operations run on the host's XLA threads
    return tr.extract(TRACE, plane_prefix="/host:CPU",
                      line_pred=lambda n: n.startswith("tf_XLA"),
                      op_pred=lambda n: "::" not in n
                      and not n.startswith("end: "))


def test_recorded_trace_reduces(cpu_trace):
    s = tr.summarize(cpu_trace, "bench.window")
    assert s["chips"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    names = dict(s["device_ops"])
    assert names["dot_general.1"] > 0 and names["wrapped_sine"] > 0
    evs = cpu_trace["ops"]["/host:CPU"]
    assert sum(1 for n, _, _ in evs if n == "dot_general.1") == 3
    assert sum(s["op_time"].values()) == pytest.approx(
        sum(e - b for _, b, e in evs), rel=1e-6)
    # the longest idle gaps are named by the benchmark's host spans
    assert s["idle_gaps"] and all(
        name in ("bench.dispatch", "bench.wait", "no host span")
        for name, _ in s["idle_gaps"])
    assert s["window_s"] - s["busy_s"] >= max(g for _, g in s["idle_gaps"])
    spans = [n for n, _, _ in cpu_trace["spans"]]
    assert spans.count("bench.dispatch") == 3 and spans.count("bench.wait") == 3


def test_busy_is_the_union():
    evs = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0)]
    assert tr.busy(evs, 0.0, 10.0) == pytest.approx(4.0)
    assert tr.busy(evs, 1.5, 5.5) == pytest.approx(2.0)
    assert tr.time_by_name(evs, 0.0, 10.0) == {"a": 2.0, "b": 2.0, "c": 1.0}


def test_busy_is_averaged_over_chips():
    evs = [("fusion.1", 0.0, 4.0), ("all-reduce.2", 3.0, 6.0),
           ("fusion.3", 8.0, 10.0)]
    s = tr.summarize({"ops": {"/device:TPU:0": evs, "/device:TPU:1": []},
                      "spans": [("bench.window", 0.0, 10.0)]},
                     "bench.window")
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx(8.0 / 2)     # 0..6 and 8..10
    assert s["op_time"]["all-reduce.2"] == pytest.approx(3.0 / 2)


def test_idle_gaps_named_by_innermost_span():
    evs = [("x", 0.0, 1.0), ("y", 3.0, 4.0), ("z", 4.5, 5.0)]
    spans = [("bench.step", 0.0, 5.0), ("bench.reset", 1.0, 3.0)]
    gaps = tr.idle_gaps(evs, spans, 0.0, 6.0)
    assert gaps[0] == ["bench.reset", pytest.approx(2.0)]
    assert ["no host span", pytest.approx(1.0)] in gaps
    assert ["bench.step", pytest.approx(0.5)] in gaps


def test_tpu_op_names_and_containers():
    """A TPU trace names an op by its whole HLO instruction, and a while
    loop's event encloses its body's: the top list names leaf ops."""
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    evs = [("while.3", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
           ("convolution.2", 4.0, 9.0)]
    s = tr.summarize({"ops": {"/device:TPU:0": evs},
                      "spans": [("bench.window", 0.0, 10.0)]},
                     "bench.window")
    assert s["busy_s"] == pytest.approx(10.0)
    assert [n for n, _ in s["device_ops"]] == ["convolution.2", "fusion.1"]
