"""Observability layer (``repro.telemetry``, DESIGN.md §7):

* metrics-registry + trace-recorder units (emission vocabulary, open-
  span bookkeeping, state round-trips);
* **the no-perturbation guarantee** — a telemetry-enabled AsyncHFLEnv
  episode (faults, outages, churn included) reproduces the disabled
  trajectory bitwise, single-chip in-process and on a 2-shard mesh via
  the tests/telemetry_driver.py subprocess;
* a disabled facade is inert: no events, ``None`` queue observer, no
  ``info["telemetry"]``;
* exported Chrome-trace JSON validates against the Trace Event Format
  schema (``chrome://tracing`` / Perfetto compatible);
* telemetry state rides checkpoints: save/restore mid-episode and the
  finished run emits the same trace as an uninterrupted one.
"""
import json
import os

import numpy as np
import pytest

from repro.checkpoint import store
from repro.runtime import AsyncConfig, ChurnEvent, FaultSpec, Outage
from repro.sim.env import AsyncHFLEnv, EnvConfig
from repro.telemetry import MetricsRegistry, Telemetry, TraceRecorder

import _subproc

ANALYTIC_CFG = dict(task="mnist", mode="analytic", n_devices=20,
                    n_edges=4, threshold_time=400.0, seed=0)
REAL_CFG = dict(task="mnist", mode="real", n_devices=8, n_edges=2,
                n_local=32, batch_size=16, threshold_time=150.0,
                gamma_max=2, seed=0)
# exercises every hook family: drops, transients, an outage window,
# leave/join churn — all deterministic under the spec seed
FAULTY = FaultSpec(drop_prob=0.2, transient_prob=0.25,
                   outages=(Outage(1, 50.0, 40.0),),
                   churn=(ChurnEvent(80.0, 2, "leave"),
                          ChurnEvent(160.0, 2, "join")),
                   seed=5)
ACFG = AsyncConfig(buffer_k=2, flush_deadline=45.0)
ACTION = np.array([2.0, 2.0])


def _episode(cfg_dict, spec, telemetry, max_steps=10_000):
    env = AsyncHFLEnv(EnvConfig(**cfg_dict, telemetry=telemetry), ACFG,
                      faults=spec)
    env.reset()
    traj, done = [], False
    for _ in range(max_steps):
        _, r, done, info = env.step(ACTION)
        traj.append((float(r), float(info["acc"]), info["edge"],
                     info["flushed"]))
        if done:
            break
    # final-state fingerprint: the flattened global model (real mode)
    # or the full accuracy history (analytic mode has no weight vector)
    fp = (np.asarray(env._global_vec) if cfg_dict["mode"] == "real"
          else np.asarray(env.acc_hist, np.float64))
    return traj, fp, env


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------

def test_metrics_registry_counters_gauges_hists():
    m = MetricsRegistry()
    m.inc("flushes")
    m.inc("flushes")
    m.inc("retries", 3)
    m.set_gauge("queue_depth", 4)
    m.set_gauge("queue_depth", 2)        # gauges keep the last value
    for v in (1.0, 3.0, 2.0):
        m.observe("staleness_at_flush", v)
    snap = m.snapshot()
    assert snap["counters"] == {"flushes": 2, "retries": 3}
    assert snap["gauges"] == {"queue_depth": 2.0}
    h = snap["histograms"]["staleness_at_flush"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["mean"] == 2.0 and h["p50"] == 2.0
    # brief() is the per-step view: no histogram material
    assert "histograms" not in m.brief()
    assert m.brief()["counters"]["flushes"] == 2
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {},
                            "histograms": {}}


def test_metrics_state_roundtrip():
    m = MetricsRegistry()
    m.inc("a", 2)
    m.set_gauge("g", 1.5)
    m.observe("h", 0.25)
    st = json.loads(json.dumps(m.state()))     # must survive JSON
    m2 = MetricsRegistry()
    m2.set_state(st)
    assert m2.snapshot() == m.snapshot()
    m2.observe("h", 1.0)                       # restored lists are live
    assert len(m2.hists["h"]) == 2 and len(m.hists["h"]) == 1


# ---------------------------------------------------------------------------
# trace recorder units
# ---------------------------------------------------------------------------

def test_recorder_emission_vocabulary():
    r = TraceRecorder()
    r.thread_name(0, "edge-0")
    r.span("round", "compute", 0, 1.5, 2.0, g1=2)
    r.instant("flush", "cloud", 1, 3.0, degraded=False)
    r.counter("queue_depth", 4.0, depth=np.int64(7))
    m, x, i, c = r.events
    assert m["ph"] == "M" and m["args"]["name"] == "edge-0"
    assert x["ph"] == "X" and x["ts"] == 1.5e6 and x["dur"] == 0.5e6
    assert x["tid"] == 0 and x["args"] == {"g1": 2}
    assert i["ph"] == "i" and i["s"] == "t" and i["ts"] == 3.0e6
    assert c["ph"] == "C" and c["args"] == {"depth": 7}   # numpy -> int
    assert type(c["args"]["depth"]) is int
    json.dumps(r.events)                       # fully JSON-serializable


def test_recorder_open_span_bookkeeping():
    r = TraceRecorder()
    r.begin("up/0", "upload", "comm", 0, 10.0, version=3)
    assert r.open_t0("up/0") == 10.0
    t0 = r.end("up/0", 14.0, landed=True)
    assert t0 == 10.0
    (sp,) = r.events
    assert sp["ts"] == 10.0e6 and sp["dur"] == 4.0e6
    assert sp["args"] == {"version": 3, "landed": True}   # args merge
    assert r.end("up/0", 20.0) is None         # already closed
    r.begin("up/1", "upload", "comm", 1, 0.0)
    r.discard("up/1")                          # voided: nothing emitted
    assert len(r.events) == 1 and r.open_t0("up/1") is None


def test_recorder_state_roundtrip_closes_open_spans():
    r = TraceRecorder()
    r.span("round", "compute", 0, 0.0, 1.0)
    r.begin("up/0", "upload", "comm", 0, 2.0)
    r2 = TraceRecorder()
    r2.set_state(json.loads(json.dumps(r.state())))
    assert r2.events == r.events
    # the restored recorder closes the span at the *original* t0
    assert r2.end("up/0", 5.0) == 2.0
    assert r2.events[-1]["ts"] == 2.0e6 and r2.events[-1]["dur"] == 3.0e6


# ---------------------------------------------------------------------------
# disabled facade: zero-cost no-op
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_inert():
    env = AsyncHFLEnv(EnvConfig(**ANALYTIC_CFG), ACFG, faults=FAULTY)
    env.reset()
    assert env.telemetry.enabled is False
    assert env.queue.observer is None          # pop/schedule untouched
    for _ in range(5):
        _, _, _, info = env.step(ACTION)
        assert "telemetry" not in info
    assert len(env.telemetry.recorder) == 0
    assert env.telemetry.metrics.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# enabled episode: hooks fire, info carries the brief view
# ---------------------------------------------------------------------------

def test_enabled_episode_records_activity():
    traj, _, env = _episode(ANALYTIC_CFG, FAULTY, telemetry=True,
                            max_steps=60)
    tm = env.telemetry
    assert env.queue.observer is tm
    c = tm.metrics.counters
    assert c["events_popped"] >= len(traj)
    assert c["flushes"] >= 1 and c["uploads_landed"] >= 1
    assert c["churn_leave"] == 1 and c["churn_join"] == 1
    assert c["outages"] >= 1
    assert "staleness_at_flush" in tm.metrics.hists
    lanes = tm.span_counts()
    assert "cloud" in lanes and any(k.startswith("edge-") for k in lanes)
    # the per-step brief view rides info["telemetry"]
    env2 = AsyncHFLEnv(EnvConfig(**ANALYTIC_CFG, telemetry=True), ACFG,
                       faults=FAULTY)
    env2.reset()
    _, _, _, info = env2.step(ACTION)
    assert info["telemetry"]["counters"]["events_popped"] >= 1


# ---------------------------------------------------------------------------
# THE invariant: telemetry on == telemetry off, bitwise
# ---------------------------------------------------------------------------

def test_no_perturbation_analytic_bitwise():
    """Faults, an outage window, and leave/join churn — the enabled
    episode reproduces the disabled one bitwise (rewards, accuracies,
    edge order, flush flags, final global vector)."""
    t_on, g_on, env = _episode(ANALYTIC_CFG, FAULTY, telemetry=True)
    t_off, g_off, _ = _episode(ANALYTIC_CFG, FAULTY, telemetry=False)
    assert len(env.telemetry.recorder) > 0     # it really recorded
    assert t_on == t_off
    assert g_on.tobytes() == g_off.tobytes()


def test_no_perturbation_real_mode_bitwise():
    """Same contract on the real-training path (SGD on jax arrays):
    the single-chip half of the ISSUE acceptance criterion."""
    spec = FaultSpec(drop_prob=0.25, transient_prob=0.2, seed=11)
    t_on, g_on, env = _episode(REAL_CFG, spec, telemetry=True)
    t_off, g_off, _ = _episode(REAL_CFG, spec, telemetry=False)
    assert len(env.telemetry.recorder) > 0
    assert t_on == t_off
    assert g_on.tobytes() == g_off.tobytes()


def test_no_perturbation_two_shard_subprocess():
    """The sharded half: tests/telemetry_driver.py runs the faulty
    real-mode episode telemetry-on and -off over a 2-shard AggContext
    (2 forced host devices) and must report bitwise identity."""
    driver = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "telemetry_driver.py")
    out = _subproc.run_script(driver, 2, device_count=2, timeout=1800)
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["shards"] == 2 and rep["steps"] > 0
    assert rep["trace_events"] > 0 and rep["flushes"] >= 1
    assert rep["bitwise_identical"] is True, rep


# ---------------------------------------------------------------------------
# Chrome-trace export schema
# ---------------------------------------------------------------------------

_PH = {"X", "i", "C", "M"}


def test_chrome_trace_schema(tmp_path):
    """The exported JSON is valid Chrome Trace Event Format: required
    top-level keys, and every event row typed so chrome://tracing /
    Perfetto accept the file."""
    _, _, env = _episode(ANALYTIC_CFG, FAULTY, telemetry=True,
                         max_steps=60)
    path = str(tmp_path / "trace.json")
    env.telemetry.export_chrome(path, task="mnist", seed=0)
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {"task": "mnist", "seed": 0}
    events = doc["traceEvents"]
    assert len(events) == len(env.telemetry.recorder)
    names = set()
    for ev in events:
        assert isinstance(ev["name"], str) and ev["ph"] in _PH
        assert ev["pid"] == 0 and isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
        if ev["ph"] != "M":
            assert 0 <= ev["tid"] <= ANALYTIC_CFG["n_edges"]
        names.add(ev["name"])
    # the vocabulary the walkthrough (README Observability) promises
    assert {"thread_name", "round", "upload", "flush",
            "queue_depth"} <= names


def test_jsonl_export_streams_every_event(tmp_path):
    _, _, env = _episode(ANALYTIC_CFG, FAULTY, telemetry=True,
                         max_steps=30)
    path = str(tmp_path / "trace.jsonl")
    env.telemetry.export_jsonl(path)
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert lines == env.telemetry.recorder.events


# ---------------------------------------------------------------------------
# telemetry state rides checkpoints (seamless trace across a resume)
# ---------------------------------------------------------------------------

def test_trace_checkpoint_roundtrip_in_process(tmp_path):
    """Snapshot a traced episode mid-flight, restore into a fresh env,
    finish both — the resumed run's recorder and counters must equal
    the uninterrupted run's exactly (open spans close at their original
    begin times)."""
    cfg = EnvConfig(**ANALYTIC_CFG, telemetry=True)
    env = AsyncHFLEnv(cfg, ACFG, faults=FAULTY)
    env.reset()
    for _ in range(8):
        env.step(ACTION)
    path = str(tmp_path / "rt")
    store.save_runtime(env, path)
    mid_events = len(env.telemetry.recorder)
    for _ in range(12):
        env.step(ACTION)

    env2 = AsyncHFLEnv(EnvConfig(**ANALYTIC_CFG, telemetry=True), ACFG,
                       faults=FAULTY)
    store.load_runtime(env2, path)
    assert len(env2.telemetry.recorder) == mid_events
    for _ in range(12):
        env2.step(ACTION)
    assert env2.telemetry.recorder.events == env.telemetry.recorder.events
    assert env2.telemetry.metrics.counters == env.telemetry.metrics.counters
    assert env2.telemetry.metrics.hists == env.telemetry.metrics.hists
