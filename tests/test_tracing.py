"""Request-level tracing contracts (ISSUE 10).

* **Tracing-equivalence pin** — a tracing-enabled run is frame-for-frame
  identical (per-quantum stats, summaries modulo the tracer-only
  ``critical_path`` key, telemetry JSON, ledger events) to a tracing-off
  run, across default / greedy-bridge / learned-bridge placement, under
  both scheduling modes, and under an injected fault trace with recovery —
  the same standing-invariant pattern as the zero-fault pin.
* **Per-request conservation** — the critical-path decomposition
  (queueing + transmission + compute + retry) sums to each completed
  request's measured end-to-end latency exactly, and the tracer's transfer
  spans reconcile with the ``TransferLedger`` event for event.
* Exports: the schema-validated trace doc round-trips, the Chrome
  trace-event JSON is structurally valid (ph/ts/dur/pid/tid), and the
  metrics registry's percentiles are exact.
"""
import copy
import json

import numpy as np
import pytest

from repro.serving import (RecoveryConfig, TelemetryLog, TransferLedger,
                           cluster_from_scenario, serve_fleet)
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import SchedulerConfig
from repro.serving.tracing import (NO_PHASE, SEGMENTS, TRACE_SCHEMA_VERSION,
                                   Histogram, MetricsRegistry, Tracer,
                                   latency_summary, phase, validate_trace)
from repro.sim.faults import fault_trace
from repro.sim.scenarios import get_scenario
from repro.sim.workloads import fleet_trace

from test_cluster import _services
from test_resilience import _POLICY_FACTORIES

CELLS = 2
FRAMES = 14


def _run_fleet(policy_factory=None, *, tracing=False, workload="flash-crowd",
               faults=None, recovery=None, engine_cfg=None, sched=None,
               frames=FRAMES, seed=5, handover_rate=0.1):
    cfg = get_scenario("smoke")
    services = _services(cfg)
    telemetry, ledger = TelemetryLog(), TransferLedger()
    tracer = Tracer() if tracing else None
    cluster = cluster_from_scenario(
        cfg, CELLS, services, policy_factory=policy_factory,
        engine_cfg=engine_cfg, telemetry=telemetry, ledger=ledger,
        recovery=recovery, sched=sched, tracer=tracer)
    fleet = fleet_trace(cfg, frames, CELLS, workload=workload, seed=seed,
                        handover_rate=handover_rate)
    out = serve_fleet(cluster, fleet, services, seed=0, collect_steps=True,
                      faults=faults)
    return out, telemetry, ledger, tracer, cluster


def _strip(summary):
    """Drop the tracer-only critical_path key (top level + per cell)."""
    s = copy.deepcopy(summary)
    s.pop("critical_path", None)
    for c in s.get("per_cell", ()):
        c.pop("critical_path", None)
    return s


# -- the tracing-equivalence pin -----------------------------------------------


@pytest.mark.parametrize("policy_name", sorted(_POLICY_FACTORIES),
                         ids=sorted(_POLICY_FACTORIES))
def test_tracing_run_identical_to_untraced(policy_name):
    ref_out, ref_tel, ref_led, _, _ = _run_fleet(
        _POLICY_FACTORIES[policy_name]())
    out, tel, led, tracer, _ = _run_fleet(
        _POLICY_FACTORIES[policy_name](), tracing=True)
    for t in range(FRAMES):
        assert out["steps"][t] == ref_out["steps"][t], t
    assert "critical_path" in out and "critical_path" not in ref_out
    assert _strip(out) == _strip(ref_out)
    assert tel.to_json() == ref_tel.to_json()
    assert [vars(e) for e in led.events] == [vars(e) for e in ref_led.events]
    assert tracer.compute, "traced run recorded no compute spans"


def test_tracing_pin_under_fault_trace():
    cfg = get_scenario("smoke")
    faults = fault_trace(cfg, 40, CELLS, "node-churn", seed=11,
                         mttf=8.0, mttr=4.0)
    assert faults.any_fault
    kw = dict(workload="stationary", frames=40, seed=11, faults=faults,
              recovery=RecoveryConfig(mode="failover", deadline_frames=10))
    ref_out, ref_tel, ref_led, _, _ = _run_fleet(**kw)
    out, tel, led, tracer, _ = _run_fleet(tracing=True, **kw)
    assert _strip(out) == _strip(ref_out)
    assert tel.to_json() == ref_tel.to_json()
    assert [vars(e) for e in led.events] == [vars(e) for e in ref_led.events]
    # the fault machinery left its marks in the span tree too
    assert any(t.kind == "failover" for t in tracer.transfers)


def test_tracing_pin_continuous_scheduling():
    kw = dict(engine_cfg=EngineConfig(scheduling="continuous", seed=0),
              sched=SchedulerConfig(join_leave=True))
    ref_out, ref_tel, ref_led, _, _ = _run_fleet(**kw)
    out, tel, led, tracer, _ = _run_fleet(tracing=True, **kw)
    assert _strip(out) == _strip(ref_out)
    assert tel.to_json() == ref_tel.to_json()
    assert [vars(e) for e in led.events] == [vars(e) for e in ref_led.events]
    # continuous quanta run several micro-steps: spans carry step > 0
    assert any(s.step > 0 for s in tracer.compute)


def test_engine_cfg_tracing_creates_own_tracer():
    out, _, _, _, cluster = _run_fleet(
        engine_cfg=EngineConfig(tracing=True, seed=0))
    assert cluster.tracer is not None
    assert all(e.tracer is cluster.tracer for e in cluster.engines), \
        "cells must share ONE tracer"
    assert "critical_path" in out


# -- per-request conservation --------------------------------------------------


@pytest.mark.parametrize("mode", ["quantum", "continuous"])
def test_per_request_conservation(mode):
    kw = {}
    if mode == "continuous":
        kw = dict(engine_cfg=EngineConfig(scheduling="continuous", seed=0),
                  sched=SchedulerConfig(join_leave=True))
    out, _, ledger, tracer, _ = _run_fleet(tracing=True, **kw)
    completed = [r for r in tracer.requests.values()
                 if r.outcome == "completed"]
    assert len(completed) == out["completed"] > 0
    for rec in completed:
        segs = tracer.request_segments(rec.rid)
        latency = rec.end_frame - rec.arrival_frame + 1
        assert set(segs) == set(SEGMENTS)
        assert sum(segs.values()) == latency, (rec.rid, segs, latency)
    # transfer spans reconcile with the ledger, event for event: every
    # ledger row the engines/cluster recorded has a matching span
    led = ledger.per_request()
    for rid, kinds in led.items():
        spans = [t for t in tracer.transfers if t.rid == rid]
        for kind, agg in kinds.items():
            mine = [t for t in spans if t.kind == kind]
            assert len(mine) == agg["count"], (rid, kind)
            assert sum(t.nbytes for t in mine) == agg["nbytes"]
            assert sum(t.cost for t in mine) == pytest.approx(agg["cost"])


def test_retry_segment_under_backoff():
    cfg = get_scenario("smoke")
    faults = fault_trace(cfg, 40, CELLS, "node-churn", seed=11,
                         mttf=8.0, mttr=4.0)
    out, _, _, tracer, _ = _run_fleet(
        tracing=True, workload="stationary", frames=40, seed=11,
        faults=faults, recovery=RecoveryConfig(mode="failover"))
    assert out["retries"] > 0, "churn produced no admission retries"
    assert tracer.backoffs, "retries recorded no backoff spans"
    report = tracer.critical_path_report()
    assert report["requests"] == out["completed"]
    # conservation still holds with retry intervals in the mix
    for rec in tracer.requests.values():
        if rec.outcome != "completed":
            continue
        segs = tracer.request_segments(rec.rid)
        assert sum(segs.values()) == rec.end_frame - rec.arrival_frame + 1


def test_critical_path_report_rollup():
    out, _, _, tracer, cluster = _run_fleet(tracing=True)
    report = out["critical_path"]
    assert report["requests"] == out["completed"]
    assert report["latency_frames"] == sum(report["segments"].values())
    assert sum(report["fractions"].values()) == pytest.approx(1.0)
    assert report["dominant"] == max(SEGMENTS,
                                     key=lambda k: report["segments"][k])
    # per-cell reports partition the fleet total
    per_cell = [c["critical_path"] for c in out["per_cell"]]
    assert sum(r["requests"] for r in per_cell) == report["requests"]
    for k in SEGMENTS:
        assert sum(r["segments"][k] for r in per_cell) \
            == report["segments"][k]


# -- exports -------------------------------------------------------------------


def test_trace_doc_round_trip():
    _, _, _, tracer, _ = _run_fleet(tracing=True)
    doc = tracer.to_json()
    validate_trace(doc)
    assert doc["schema_version"] == TRACE_SCHEMA_VERSION
    # through real JSON text, like the artifact path
    doc2 = json.loads(json.dumps(doc))
    rt = Tracer.from_json(doc2)
    assert rt.to_json() == doc
    assert len(rt.requests) == len(tracer.requests)
    assert rt.critical_path_report() == tracer.critical_path_report()


def test_trace_doc_round_trip_with_populated_metrics():
    # the serve_fleet path instruments GDMService, so real captured traces
    # carry non-empty histograms — the round-trip must re-emit them exactly
    # (regression: from_json used to silently drop histogram snapshots)
    _, _, _, tracer, _ = _run_fleet(tracing=True, frames=4)
    tracer.metrics.counter("gdm_runner_calls").inc(3)
    h = tracer.metrics.histogram("launch_ms")
    for v in (0.7, 2.5, 40.0, 900.0):
        h.observe(v)
    doc = json.loads(json.dumps(tracer.to_json()))
    assert doc["metrics"]["histograms"]["launch_ms"]["count"] == 4
    rt = Tracer.from_json(doc)
    assert rt.to_json() == doc
    # the restored histogram is a frozen summary: stored stats answer
    # exactly, and observing into it resumes live mode from empty
    frozen = rt.metrics.histogram("launch_ms")
    assert frozen.count == 4 and frozen.max == 900.0
    assert frozen.percentile(95) == h.percentile(95)
    with pytest.raises(ValueError):
        frozen.percentile(90)
    frozen.observe(5.0)
    assert frozen.count == 1 and frozen.total == 5.0


def test_trace_doc_rejects_bad_version_and_shape():
    _, _, _, tracer, _ = _run_fleet(tracing=True, frames=4)
    doc = tracer.to_json()
    bad = dict(doc, schema_version=TRACE_SCHEMA_VERSION + 1)
    with pytest.raises(ValueError):
        Tracer.from_json(bad)
    with pytest.raises(ValueError):
        validate_trace({k: v for k, v in doc.items() if k != "requests"})
    mangled = json.loads(json.dumps(doc))
    mangled["compute"][0]["frame"] = "not-an-int"
    with pytest.raises(ValueError):
        validate_trace(mangled)


def test_chrome_trace_structurally_valid():
    _, _, _, tracer, _ = _run_fleet(tracing=True)
    chrome = tracer.to_chrome_trace()
    events = chrome["traceEvents"]
    assert events
    slices = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert slices and metas
    assert {e["ph"] for e in events} == {"X", "M"}
    for e in slices:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] > 0
        assert e["name"] and e["cat"]
    # one process per cell with a name, threads named for the node tracks
    cells = {e["pid"] for e in slices}
    named = {e["pid"] for e in metas if e["name"] == "process_name"}
    assert cells <= named
    cats = {e["cat"] for e in slices}
    assert "compute" in cats and "transfer" in cats
    # JSON-serializable as-is (what --trace-perfetto writes)
    json.dumps(chrome)


# -- metrics registry ----------------------------------------------------------


def test_histogram_exact_percentiles():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    vals = [0.5, 3.0, 7.0, 42.0, 99.0, 250.0, 8.0, 12.0]
    for v in vals:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    assert h.count == len(vals)
    assert h.mean == pytest.approx(np.mean(vals))
    assert h.max == max(vals)
    assert sum(h.counts) == len(vals)
    # bucket counts: (-inf,1], (1,10], (10,100], (100,inf) with side="left"
    assert h.counts == [1, 3, 3, 1]
    j = h.to_json()
    assert j["p99"] == h.percentile(99) and j["bucket_counts"] == h.counts


def test_metrics_registry_accessors_and_json():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(2)
    m.gauge("g").set(1.5)
    m.histogram("h").observe(3.0)
    assert m.counter("a").value == 3
    j = m.to_json()
    assert j["counters"]["a"] == 3
    assert j["gauges"]["g"] == 1.5
    assert j["histograms"]["h"]["count"] == 1
    json.dumps(j)


def test_latency_summary_matches_numpy():
    lat = [3, 1, 7, 2, 9, 4]
    s = latency_summary(lat)
    assert s["p50_latency_frames"] == pytest.approx(np.percentile(lat, 50))
    assert s["p99_latency_frames"] == pytest.approx(np.percentile(lat, 99))
    assert s["max_latency_frames"] == 9.0
    empty = latency_summary([])
    assert set(empty.values()) == {0.0}


def test_policy_bridge_decision_metrics_recorded():
    out, _, _, tracer, _ = _run_fleet(
        _POLICY_FACTORIES["greedy-bridge"](), tracing=True)
    doc = tracer.metrics.to_json()
    hist = doc["histograms"]
    # one observation phase per cell and quantum; the cells share one
    # pool, so one decision per quantum decides all of them
    assert hist["policy_act_batch_ms"]["count"] == FRAMES
    assert doc["counters"]["policy_act_cells"] == CELLS * FRAMES
    assert hist["policy_obs_ms"]["count"] == CELLS * FRAMES
    assert hist["policy_act_batch_ms"]["total"] > 0


@pytest.mark.slow
def test_gdm_service_compile_and_call_metrics():
    import jax

    from repro.serving.gdm_service import GDMService

    svc = GDMService(jax.random.PRNGKey(0), num_blocks=2, ref_prompts=2)
    m = MetricsRegistry()
    svc.instrument(m, 0)
    rng = np.random.default_rng(0)
    states = [svc.init_state(rng) for _ in range(2)]
    ks = np.zeros(2, dtype=int)
    svc.run_batch(states, ks)          # first call at bucket 2: compile
    svc.run_batch(states, ks)          # steady state
    assert m.counter("gdm_runner_calls").value == 2
    assert m.counter("gdm_compile_events").value == 1
    assert m.histogram("gdm_compile_ms").count == 1
    # every call is timed: its dispatch and the host's wait on the device
    assert m.histogram("launch_ms").count == 2
    assert m.histogram("device_wait_ms").count == 2
    svc.run_batch(states + [svc.init_state(rng)] * 2, np.zeros(4, dtype=int))
    assert m.counter("gdm_compile_events").value == 2   # new bucket = 4


# -- wall-clock phases ---------------------------------------------------------

ENGINE_PHASES = ("admission", "policy_obs", "policy_act_batch", "placement",
                 "accounting")
SERVICE_PHASES = ("stage_in", "launch", "device_wait", "readback")
PHASES = ENGINE_PHASES + ("fleet",) + SERVICE_PHASES


def test_phase_without_registry_is_the_shared_noop():
    assert phase(None, "admission", frame=3, cell=1) is NO_PHASE
    assert phase(None, "launch") is NO_PHASE
    with phase(None, "admission", frame=3, cell=1):
        pass


def test_phase_observes_once_per_exit_also_by_exception():
    m = MetricsRegistry()
    with phase(m, "admission", frame=0, cell=0):
        pass
    with pytest.raises(KeyError):
        with phase(m, "admission", frame=1, cell=0):
            raise KeyError("raised inside the phase")
    h = m.histogram("admission_ms")
    assert h.count == 2
    assert all(v > 0 for v in h.values)
    assert set(m.histograms) == {"admission_ms"}
    assert not m.counters and not m.gauges


def _states(svc, rng, n):
    return [svc.init_state(rng) for _ in range(n)]


def test_instrumented_service_phases_and_bit_identical_outputs(monkeypatch):
    import jax

    from repro.serving.gdm_service import GDMService

    syncs = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or block(x))

    key = jax.random.PRNGKey(1)
    traced = GDMService(key, num_blocks=2, ref_prompts=2)
    plain = GDMService(key, num_blocks=2, ref_prompts=2)
    m = MetricsRegistry()
    traced.instrument(m, 2)
    rng = np.random.default_rng(0)
    states = _states(traced, rng, 3)
    ks = np.asarray([0, 1, 0])
    calls = 0
    for n in (3, 3, 2):                    # a new bucket, then steady calls
        out_t, q_t = traced.run_batch(states[:n], ks[:n])
        synced = len(syncs)
        out_p, q_p = plain.run_batch(states[:n], ks[:n])
        assert len(syncs) == synced, "the untraced call forced a sync"
        calls += 1
        assert np.array_equal(q_t, q_p)
        for a, b in zip(out_t, out_p):
            assert np.array_equal(a["latent"], b["latent"])
            assert np.array_equal(a["x0"], b["x0"])
    assert traced.run_batch([], np.zeros(0, dtype=int))[0] == []
    # the slot-resident path: a join, a continuing row, a leave
    items = [(7, states[0], 0), (8, states[1], 1)]
    for step in range(2):
        out_t, _ = traced.slot_batch().step(items)
        synced = len(syncs)
        out_p, _ = plain.slot_batch().step(items)
        assert len(syncs) == synced, "the untraced step forced a sync"
        calls += 1
        for a, b in zip(out_t, out_p):
            assert np.array_equal(a["latent"], b["latent"])
            assert np.array_equal(a["x0"], b["x0"])
        items = [(7, out_t[0], 1)]
    for name in SERVICE_PHASES:
        assert m.histogram(f"{name}_ms").count == calls, name
    assert plain.metrics is None and plain.batch_calls == traced.batch_calls


@pytest.mark.parametrize("mode", ["quantum", "continuous"])
def test_phase_counts_on_traced_fleet(mode):
    kw = {}
    if mode == "continuous":
        kw = dict(engine_cfg=EngineConfig(scheduling="continuous", seed=0),
                  sched=SchedulerConfig(join_leave=True))
    _, _, _, tracer, _ = _run_fleet(_POLICY_FACTORIES["greedy-bridge"](),
                                    tracing=True, **kw)
    hist = tracer.metrics.histograms
    acted = tracer.metrics.counter("policy_act_cells").value
    quanta = CELLS * FRAMES
    assert hist["admission_ms"].count == quanta
    if mode == "quantum":
        # one observation, placement pass and accounting phase per cell
        # and quantum; one act per quantum for the fleet's one pool
        for name in ("policy_obs", "placement", "accounting"):
            assert hist[f"{name}_ms"].count == quanta, name
        assert hist["policy_act_batch_ms"].count == FRAMES
        assert acted == quanta
    else:
        # a continuous quantum runs several block steps, each cell acting
        # alone at every one
        assert hist["policy_obs_ms"].count \
            == hist["policy_act_batch_ms"].count == acted
        assert hist["placement_ms"].count > quanta
        assert hist["accounting_ms"].count > quanta
    assert hist["fleet_ms"].count > 0
    for name in ENGINE_PHASES + ("fleet",):
        assert hist[f"{name}_ms"].total > 0, name


@pytest.mark.timeout(120)
def test_phases_land_on_the_profiler_host_plane(tmp_path):
    import jax

    from repro.serving.gdm_service import GDMService
    from repro.serving.tracing import PHASE_PREFIX

    from test_cluster import LinearService

    cfg = get_scenario("smoke")
    # the DiT serves service 2, which most of the trace's UEs request
    services = {0: LinearService(), 1: LinearService(),
                2: GDMService(jax.random.PRNGKey(2), ref_prompts=2)}
    cluster = cluster_from_scenario(
        cfg, CELLS, services, tracing=True,
        policy_factory=_POLICY_FACTORIES["greedy-bridge"]())
    fleet = fleet_trace(cfg, 6, CELLS, workload="flash-crowd", seed=5,
                        handover_rate=0.1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve_fleet(cluster, fleet, services, seed=0)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    stats = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PHASE_PREFIX):
                    stats.setdefault(e.name[len(PHASE_PREFIX):], set()).add(
                        tuple(sorted(k for k, _ in e.stats)))
    want = {**{p: ("cell", "frame") for p in ENGINE_PHASES},
            "policy_act_batch": ("cells", "frame"),      # one act per pool
            "fleet": ("frame",),
            **{p: ("bucket", "rows", "service") for p in SERVICE_PHASES}}
    assert set(stats) == set(PHASES)
    for name, keys in want.items():
        assert stats[name] == {keys}, name
