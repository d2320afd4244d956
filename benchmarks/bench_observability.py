"""Observability overhead benchmark: tracing-off vs tracing-on.

The ISSUE-10 contract: request-level tracing is *pure observation* — a
traced fleet run serves the byte-identical request stream and its only
cost is wall-clock.  This bench measures that cost on a 2-cell smoke
cluster with real (reduced) DiT services under a flash-crowd workload,
for both scheduling disciplines (quantum lockstep and the
iteration-level continuous scheduler):

1. serve the same fleet trace with tracing off and on, interleaved in
   off/on PAIRS (fresh cluster per run, warmup first so jit compiles are
   excluded); overhead is the MEDIAN of the per-pair on/off wall-clock
   ratios — pairing shares machine noise between the two sides, which an
   unpaired best-of-N cannot do on a sub-second row;
2. assert the tracing-on summary equals tracing-off after stripping the
   tracer-only ``critical_path`` key (the pure-observation pin, also
   enforced per-frame by ``tests/test_tracing.py``);
3. assert median overhead <= ``REPRO_BENCH_TRACE_OVERHEAD_MAX``
   (default 1.05, the <5%% claim; env-tunable because loaded CI runners
   stay noisy even under pairing);
4. export the captured trace both ways — schema-validated trace document
   and Chrome trace-event JSON — into ``RESULTS_DIR`` so the CI artifact
   upload ships an openable Perfetto trace next to the BENCH JSONs.

The services run ``steps_per_block=4`` (unlike the test suite's minimal
1-step blocks): per-span device work at least resembles a real denoise
block, so the ratio measures tracing against representative compute
instead of against an almost-free model.

Emits ``observability_<workload>_<scheduling>_{off,on}`` CSV rows and a
``BENCH_observability.json`` summary (via ``benchmarks.run``) with the
per-row overhead, the critical-path report, and tracer span counts.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks.common import RESULTS_DIR, emit, run_meta, scaled
from repro.core.policy import GreedyPoAPolicy
from repro.serving import validate_trace
from repro.serving.cluster import cluster_from_scenario, serve_fleet
from repro.serving.gdm_service import make_gdm_services
from repro.sim.scenarios import get_scenario
from repro.sim.workloads import fleet_trace

CELLS = int(os.environ.get("REPRO_BENCH_TRACE_CELLS", "2"))
WORKLOAD = os.environ.get("REPRO_BENCH_TRACE_WORKLOAD", "flash-crowd")
OVERHEAD_MAX = float(os.environ.get("REPRO_BENCH_TRACE_OVERHEAD_MAX", "1.05"))


def _strip(summary):
    """Drop the tracer-only key so off/on summaries are comparable."""
    out = {k: v for k, v in summary.items() if k != "critical_path"}
    if "per_cell" in out:
        out["per_cell"] = [
            {k: v for k, v in cell.items() if k != "critical_path"}
            for cell in out["per_cell"]]
    return out


def _serve_once(cfg, services, fleet, *, tracing, scheduling):
    engine_cfg = None
    sched = None
    if scheduling == "continuous":
        from repro.serving import EngineConfig, SchedulerConfig
        engine_cfg = EngineConfig(
            max_blocks=cfg.max_blocks, admission_slots=cfg.num_channels,
            alpha=cfg.alpha, beta=cfg.beta, early_exit=True, seed=cfg.seed,
            scheduling="continuous")
        sched = SchedulerConfig()
    cluster = cluster_from_scenario(
        cfg, CELLS, services, policy_factory=lambda c: GreedyPoAPolicy(),
        engine_cfg=engine_cfg, sched=sched, tracing=tracing)
    t0 = time.perf_counter()
    stats = serve_fleet(cluster, fleet, services, seed=0)
    wall = time.perf_counter() - t0
    tracer = cluster.tracer
    if tracing:
        # detach so the next tracing-off rep serves uninstrumented
        for svc in services.values():
            svc.metrics = None
            svc._compiled_keys = set()
    return stats, wall, tracer


def run(scenario: str = "") -> dict:
    name = scenario or os.environ.get("REPRO_BENCH_TRACE_SCENARIO", "smoke")
    cfg = get_scenario(name)
    frames = int(os.environ.get("REPRO_BENCH_TRACE_FRAMES", "0")) or \
        cfg.horizon * 4
    pairs = scaled(7, lo=5)

    services, _ = make_gdm_services(
        cfg.num_services, jax.random.PRNGKey(cfg.seed),
        num_blocks=cfg.max_blocks, steps_per_block=4)
    fleet = fleet_trace(cfg, frames, CELLS, workload=WORKLOAD, seed=0,
                        handover_rate=0.05)
    warm = fleet_trace(cfg, min(4, frames), CELLS, workload=WORKLOAD, seed=1)

    out = {"scenario": name, "cells": CELLS, "frames": frames,
           "workload": WORKLOAD, "pairs": pairs,
           "overhead_max": OVERHEAD_MAX, "meta": run_meta(), "rows": {}}
    os.makedirs(RESULTS_DIR, exist_ok=True)
    for scheduling in ("quantum", "continuous"):
        for tracing in (False, True):                # warm jit buckets
            _serve_once(cfg, services, warm, tracing=tracing,
                        scheduling=scheduling)
        point = {"off": {"wall_s": float("inf")},
                 "on": {"wall_s": float("inf")}}
        ratios = []
        tracer = None
        for _ in range(pairs):
            rep = {}
            for mode, tracing in (("off", False), ("on", True)):
                stats, wall, tr = _serve_once(cfg, services, fleet,
                                              tracing=tracing,
                                              scheduling=scheduling)
                rep[mode] = wall
                if wall < point[mode]["wall_s"]:
                    point[mode] = {"wall_s": wall, "summary": _strip(stats),
                                   "requests_per_s": stats["completed"] /
                                   wall}
                    if tracing:
                        tracer = tr
                        point["critical_path"] = stats.get(
                            "critical_path", {})
            ratios.append(rep["on"] / rep["off"])
        for mode in ("off", "on"):
            emit(f"observability_{WORKLOAD}_{scheduling}_{mode}",
                 point[mode]["wall_s"] * 1e6 / frames,
                 f"req/s={point[mode]['requests_per_s']:.1f}")

        # the pure-observation pin: identical serving, modulo critical_path
        assert point["on"]["summary"] == point["off"]["summary"], \
            f"tracing-on summary diverged from tracing-off ({scheduling})"
        overhead = float(np.median(ratios))
        point["overhead"] = overhead
        point["overhead_ratios"] = [round(r, 4) for r in ratios]
        emit(f"observability_{WORKLOAD}_{scheduling}_overhead", 0.0,
             f"{overhead:.3f}x median of {pairs} pairs "
             f"(ceiling {OVERHEAD_MAX}x)")
        assert overhead <= OVERHEAD_MAX, \
            f"tracing overhead {overhead:.3f}x (median of {pairs} paired " \
            f"runs) exceeds {OVERHEAD_MAX}x under {WORKLOAD}/{scheduling}"

        # export + validate the captured trace both ways; the files land
        # next to the BENCH JSONs so CI uploads an openable Perfetto trace
        doc = tracer.to_json()
        validate_trace(doc)
        chrome = tracer.to_chrome_trace()
        assert chrome["traceEvents"], "chrome export produced no events"
        trace_path = os.path.join(
            RESULTS_DIR, f"fleet_trace_{scheduling}.json")
        perfetto_path = os.path.join(
            RESULTS_DIR, f"fleet_trace_{scheduling}.perfetto.json")
        with open(trace_path, "w") as f:
            json.dump(doc, f)
        with open(perfetto_path, "w") as f:
            json.dump(chrome, f)
        point["trace"] = {
            "requests": len(doc["requests"]),
            "compute_spans": len(doc["compute"]),
            "transfer_spans": len(doc["transfers"]),
            "chrome_events": len(chrome["traceEvents"]),
            "trace_path": trace_path,
            "perfetto_path": perfetto_path,
        }
        out["rows"][scheduling] = point
    return out


if __name__ == "__main__":
    run()
