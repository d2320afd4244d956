"""One run of one cell: set-up, the measured window, the check, the line.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``) in ``BENCHMARK.json``; nothing here knows any
cell by name.  A configuration names its architecture's parts: its plain
reference (``reference/<reference>.py``, for the check) and its count of
work (``kernels/<work>.py``, for the readers).  The window drives the
program's own entry, ``repro.serving.cluster.serve_fleet``, on a fleet
that ``cluster_from_scenario`` builds, and times it from outside:

* the cluster's ``step`` is wrapped to close the window and to stamp the
  end of every quantum on the host clock.  A request's latency runs from
  the end of the quantum before the one it arrived in to the end of the
  quantum that delivered it;
* every service's ``run_batch`` is wrapped to keep each call's inputs and
  outputs (references, not copies) for the check, and its live and
  bucket rows;
* with ``--trace 1`` the engines' ``begin_step``/``end_step``, the
  cluster step and ``run_batch`` are also written as host spans into the
  profiler's trace, so they share the device trace's clock.

No file of the program is edited.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

from chipbench import check, parts, seeds, trace  # noqa: E402

# what a configuration names of its architecture: the check's reference
# module and the readers' count of work
PART_KEYS = ("reference", "work")


class WindowClosed(Exception):
    """Raised from the cluster's step once the window's seconds are up."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    missing = [k for k in PART_KEYS if k not in config]
    if missing:
        raise ValueError(f"configuration {w['config']!r} names no "
                         f"{' and no '.join(map(repr, missing))}")
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=config["name"], **config["model"])


# -- set-up ----------------------------------------------------------------------

def scenario(traffic: dict, omega: np.ndarray):
    """The traffic's scenario with its threshold rule applied.  A
    threshold given as ``"omega_max:k"`` is the largest Ω_s(k) over the
    services, as measured at set-up."""
    from repro.sim.scenarios import get_scenario

    def value(v):
        if isinstance(v, str):
            kind, k = v.split(":")
            if kind != "omega_max":
                raise ValueError(f"unknown threshold rule {v!r}")
            return float(omega[:, int(k)].max())
        return float(v)

    return get_scenario(traffic["scenario"],
                        **{k: value(v)
                           for k, v in traffic["thresholds"].items()})


def fleet(cfg, traffic: dict, seed: int):
    """The traffic's fleet trace, the same for every seed, with its cells
    in an order drawn from the seed (the cells share one world)."""
    from repro.sim.workloads import FleetTrace, fleet_trace
    ft = fleet_trace(cfg, traffic["frames"], traffic["cells"],
                     workload=traffic["workload"], seed=traffic["trace_seed"],
                     handover_rate=traffic["handover_rate"],
                     **traffic["workload_params"])
    perm = np.random.default_rng(seeds.stream(seed, "cells")).permutation(
        traffic["cells"])
    inv = np.argsort(perm)
    hand = ft.handovers.copy()
    if len(hand):
        hand[:, 2] = inv[hand[:, 2]]
        hand[:, 3] = inv[hand[:, 3]]
    return FleetTrace(cfg=ft.cfg, frames=ft.frames,
                      cells=[ft.cells[p] for p in perm], handovers=hand)


def max_rows(trace_fleet, service: int) -> int:
    """The most rows one fleet-stacked call of ``service`` can hold: one
    outstanding request per (cell, UE) of that service."""
    per_cell = int(np.sum(trace_fleet.cells[0].service_of == service))
    return per_cell * trace_fleet.num_cells


def buckets_upto(svc, rows: int) -> List[int]:
    return sorted({svc._bucket(b) for b in range(1, rows + 1)})


def warm_buckets(services: dict, trace_fleet, max_bucket: int,
                 seed: int) -> Dict[int, List[int]]:
    """Compile every bucket the traffic can reach: one ``run_batch`` per
    bucket and service, on full rows of requests the service makes itself
    (``init_state``), so the warm call has the shapes and types the
    window's calls have.  Returns the buckets warmed per service."""
    rng = np.random.default_rng(seeds.stream(seed, "warm-rows"))
    warmed = {}
    for sid, svc in services.items():
        rows = max_rows(trace_fleet, sid)
        if rows > max_bucket:
            raise ValueError(f"service {sid} can send {rows} rows a call, "
                             f"over the traffic's max_bucket {max_bucket}")
        warmed[sid] = buckets_upto(svc, rows)
        for b in warmed[sid]:
            svc.run_batch([svc.init_state(rng) for _ in range(b)],
                          np.zeros(b, np.int32))
    return warmed


# -- spans -----------------------------------------------------------------------

class Recorder:
    """The window's clock, its calls, and (traced) its host spans."""

    def __init__(self, seconds: float, traced: bool):
        self.seconds = seconds
        self.traced = traced
        self.t0 = 0.0
        self.quantum_end: List[float] = []
        self.calls: List[dict] = []
        self.capture = False

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(trace.SPAN + name)

    def wrap_step(self, cluster) -> None:
        inner = cluster.step

        def step(handovers=()):
            if time.perf_counter() - self.t0 >= self.seconds:
                raise WindowClosed
            if self.traced:
                with self.span("ClusterEngine.step"):
                    out = inner(handovers)
            else:
                out = inner(handovers)
            self.quantum_end.append(time.perf_counter())
            return out

        cluster.step = step

    def wrap_engine(self, eng) -> None:
        for name in ("begin_step", "end_step"):
            inner = getattr(eng, name)

            def phase(*a, _inner=inner, _name=name):
                with self.span(_name):
                    return _inner(*a)

            setattr(eng, name, phase)

    def wrap_service(self, sid: int, svc) -> None:
        inner = svc.run_batch

        def run_batch(states, block_idxs):
            if self.traced:
                with self.span("run_batch"):
                    out, q = inner(states, block_idxs)
            else:
                out, q = inner(states, block_idxs)
            if self.capture and states:
                self.calls.append({
                    "service": sid, "states": states,
                    "idx": np.asarray(block_idxs, np.int32), "out": out,
                    "rows": len(states), "bucket": svc._bucket(len(states))})
            return out, q

        svc.run_batch = run_batch


# -- the run ---------------------------------------------------------------------

def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found {dev.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def serve(cell: Cell, seed: int, seconds: float, traced: bool, *,
          started: float, require_tpu: bool, fault: Optional[Callable]):
    """Set-up and the window.  Returns the line without its check, the
    window's calls, and the weights' key.  Every array of the program is
    local here, so it is gone once this returns."""
    import jax
    from repro.core import LearnGDMController
    from repro.core.policy import LearnedPolicy
    from repro.serving.cluster import cluster_from_scenario, serve_fleet
    from repro.serving.gdm_service import make_gdm_services
    from repro.sim import EdgeSimulator

    device = device_info(jax, cell.chips, require_tpu)
    conf, traffic = cell.config, cell.traffic
    if traffic["scheduling"] != "quantum" or traffic["placement"] != "learned":
        raise ValueError("this harness drives quantum scheduling with "
                         "learned placement")
    mcfg = model_config(conf)
    blocks = mcfg.gdm_blocks
    spb = conf["steps_per_block"]

    # services at the configuration's widths, Ω measured on the device
    t = time.perf_counter()
    # the deployment (weights, hence Ω, and the policy trained on Ω) comes
    # from the configuration's and the traffic's own seeds, so that every
    # --seed serves the same chains; --seed draws the latents, the prompts
    # and the order of the cells
    weights_key = jax.random.PRNGKey(conf["weights_seed"])
    services, omega = make_gdm_services(
        conf["services"], weights_key, num_blocks=blocks,
        steps_per_block=spb, model_cfg=mcfg)
    impls = sorted({s.resolved_impl for s in services.values()})
    log(f"services: {len(services)} x {mcfg.name}, impl {impls}, Ω "
        f"{np.round(omega, 6).tolist()} ({time.perf_counter() - t:.3f} s)")
    if require_tpu and impls != ["pallas"]:
        raise RuntimeError(f"kernels resolved to {impls}, want ['pallas']")
    if fault is not None:
        for svc in services.values():
            svc._runner = fault(svc._runner)

    # the placement policy: D3QL trained on the measured Ω
    cfg = scenario(traffic, omega)
    t = time.perf_counter()
    ctrl = LearnGDMController(EdgeSimulator(cfg, quality=omega),
                              variant="learn-gdm",
                              seed=traffic["policy_seed"])
    ctrl.train_fused(traffic["train_episodes"],
                     num_envs=traffic["train_envs"],
                     seed=traffic["policy_seed"])
    log(f"policy: {traffic['train_episodes']} episodes "
        f"({time.perf_counter() - t:.3f} s)")

    def policy(_cell):
        return LearnedPolicy(ctrl.agent, "learn-gdm")

    trace_fleet = fleet(cfg, traffic, seed)

    # warm the serving path once on a short trace of its own (the policy's
    # act and the host paths), then every bucket the traffic can reach
    t = time.perf_counter()
    from repro.sim.workloads import fleet_trace
    warm = cluster_from_scenario(cfg, 2, services, policy_factory=policy)
    serve_fleet(warm, fleet_trace(cfg, 3, 2, workload="stationary", seed=0),
                services, seed=seeds.stream(seed, "warm"))
    del warm

    cluster = cluster_from_scenario(cfg, traffic["cells"], services,
                                    policy_factory=policy, tracing=traced)
    # before the Recorder wraps run_batch: no warm call is a window call
    warmed = warm_buckets(services, trace_fleet, traffic["max_bucket"], seed)
    log(f"warm-up: buckets {warmed} ({time.perf_counter() - t:.3f} s)")

    rec = Recorder(seconds, traced)
    rec.wrap_step(cluster)
    for sid, svc in services.items():
        rec.wrap_service(sid, svc)
    if traced:
        for eng in cluster.engines:
            rec.wrap_engine(eng)
    metrics = cluster.tracer.metrics if cluster.tracer is not None else None
    before = counters(metrics)

    compiles = {"n": 0}

    def on_compile(event, *_a, **_k):
        if rec.capture and ("backend_compile" in event
                            or "cache_hits" in event):
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_compile)

    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    setup_s = time.perf_counter() - started
    rec.capture = True
    rec.t0 = time.perf_counter()
    ran_out = False
    try:
        serve_fleet(cluster, trace_fleet, services,
                    seed=seeds.stream(seed, "serve"))
        ran_out = True
    except WindowClosed:
        pass
    rec.capture = False
    if traced:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    jax.monitoring.unregister_event_listener(on_compile)
    if ran_out:
        raise RuntimeError(f"the trace's {traffic['frames']} frames ran out "
                           f"before the {seconds} s window closed")

    frames = len(rec.quantum_end)
    window_s = rec.quantum_end[-1] - rec.t0
    starts = [rec.t0] + rec.quantum_end[:-1]
    reqs = [r for eng in cluster.engines
            for r in (list(eng.pending) + eng.active + eng.completed
                      + eng.failed)]
    attempted = sum(r.arrival_frame < frames for r in reqs)
    done = [r for eng in cluster.engines for r in eng.completed
            if r.delivered_frame < frames]
    failed = sum(len(eng.failed) for eng in cluster.engines)
    lat_ms = [(rec.quantum_end[r.delivered_frame] - starts[r.arrival_frame])
              * 1e3 for r in done]
    blocks_run = [r.blocks_done for r in done]
    log(f"window: {window_s:.3f} s, {frames} quanta, attempted {attempted}, "
        f"delivered {len(done)}, failed {failed}, blocks per image "
        f"{np.mean(blocks_run) if done else 0:.3f} "
        f"({min(blocks_run, default=0)}-{max(blocks_run, default=0)}), "
        f"calls {len(rec.calls)}")
    if not done:
        raise RuntimeError("no request was delivered in the window")
    after = counters(metrics)
    memory = peak_bytes(jax)

    result_metrics = {}
    breakdown = None
    layer_info = {}
    if traced:
        profile = trace.load(trace.find(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(profile=profile, rec=rec, cell=cell, device=device,
                      before=before, after=after, compiles=compiles["n"],
                      spb=spb)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        for m in cell.per_layer:
            value = read_metric(m["name"], ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        breakdown = ctx.breakdown()
        layer_info = ctx.notes
        del ctx, profile
    else:
        values = {
            "images_per_s": len(done) / window_s,
            "latency_p50_ms": percentile(lat_ms, 50),
            "latency_p95_ms": percentile(lat_ms, 95),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    device["memory_peak_bytes"] = memory
    device["memory_peak_bytes"] = memory
    line = {"attempted": int(attempted), "failed": int(failed),
            "metrics": result_metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    for name, note in layer_info.items():
        log(f"layer: {name}: {note}")
    return line, rec.calls, weights_key


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        started: float, require_tpu: bool = True,
        fault: Optional[Callable] = None, control: bool = False) -> dict:
    """One run; returns the result line as a dict.

    ``fault`` (tests only) wraps each service's block call, to plant a
    fault in the timed path.  ``control`` (``calibrate.py`` only) also
    reads the control, the reference in bfloat16 in the program's place,
    on the same rows, into the line's ``control``."""
    served, calls, weights_key = serve(
        cell, seed, seconds, traced, started=started,
        require_tpu=require_tpu, fault=fault)
    # the check: the program's state is gone before the reference runs
    gc.collect()
    conf = cell.config
    t = time.perf_counter()
    readings = check.compare(calls, conf, seed, weights_key)
    log(f"check: {readings['rows']} rows of {len(calls)} calls, buckets "
        f"{readings['buckets']} ({time.perf_counter() - t:.3f} s)")
    limits = conf["check"]["limits"]
    compared = {k: {"value": readings[k], "limit": limits[k]}
                for k in limits}
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in compared.values())
    line = {"correct": bool(correct), **served}
    if control:
        # the control, and both against a reference at the other precision
        line["control"] = {}
        for prec in ("highest", "default"):
            for ctl in (False, True):
                r = check.compare(calls, conf, seed, weights_key,
                                  control=ctl, precision=prec)
                name = f"{'control' if ctl else 'program'}@{prec}"
                line["control"][name] = {k: r[k] for k in limits}
    for k, v in compared.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    line["check"] = compared
    return line


def counters(metrics) -> Dict[str, float]:
    if metrics is None:
        return {}
    out = {}
    doc = metrics.to_json()
    for kind in ("counters", "gauges"):
        for k, v in doc.get(kind, {}).items():
            out[k] = float(v)
    for k, h in doc.get("histograms", {}).items():
        out[k + ".count"] = float(h.get("count", 0))
        out[k + ".total"] = float(h.get("total", 0.0))
    return out


# -- per-layer metrics -------------------------------------------------------------

class Context:
    """What a per-layer reader may read: the traced window's profile, the
    host spans, the calls, the program's counters, shapes and peaks, and
    the configuration's count of work (``work``, ``kernels/<work>.py``)."""

    def __init__(self, *, profile, rec, cell, device, before, after,
                 compiles, spb):
        self.profile = profile
        self.model = cell.config["model"]
        self.work = parts.load("kernels", cell.config["work"])
        self.steps_per_block = spb
        self.calls = rec.calls
        self.before, self.after = before, after
        self.monitored_compiles = compiles
        self.peaks = load_json(HERE / "peaks.json").get(device["kind"])
        self.device_kind = device["kind"]
        steps = [s for s in profile.spans
                 if s.name == trace.SPAN + "ClusterEngine.step"]
        if not steps:
            raise RuntimeError("the trace holds no ClusterEngine.step span")
        self.lo, self.hi = steps[0].start, steps[-1].end
        self.window_s = self.hi - self.lo
        self.steps = steps
        if not profile.ops:
            raise RuntimeError("the trace holds no device operation")
        # the device clock's lag behind the host's, from the block calls
        self.lag = {}
        self.ops = {}
        names = self.work.MODULES
        launches = [s for s in profile.spans
                    if s.name == trace.SPAN + "run_batch"]
        for chip, ops in profile.ops.items():
            runs = [m for m in profile.modules.get(chip, [])
                    if m.name.split("(")[0] in names]
            lag = trace.host_lag(runs, launches)
            self.lag[chip] = lag
            self.ops[chip] = trace.shift(ops, lag or 0.0)
        per_chip = [trace.busy_s(ev, self.lo, self.hi)
                    for ev in self.ops.values()]
        self.busy_s = float(sum(per_chip) / len(per_chip))
        self.notes: Dict[str, str] = {
            "device_clock_lag_ms": ", ".join(
                f"{chip} {'unpaired' if lag is None else f'{1e3 * lag:.4f}'}"
                for chip, lag in self.lag.items())}

    def peak(self, key: str) -> float:
        if self.peaks is None:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in chipbench/peaks.json")
        return float(self.peaks[key])

    def spans(self, name: str):
        return [s for s in self.profile.spans
                if s.name == trace.SPAN + name
                and s.start >= self.lo and s.end <= self.hi]

    def counter_delta(self, key: str) -> float:
        return self.after.get(key, 0.0) - self.before.get(key, 0.0)

    def chip_ops(self):
        """The operations of the first chip (the one a one-chip cell
        uses)."""
        return self.ops[sorted(self.ops)[0]]

    def breakdown(self) -> dict:
        ev = self.chip_ops()
        gaps = trace.idle_gaps(ev, self.lo, self.hi)
        by = trace.attribute_gaps(gaps, self.profile.spans,
                                  outside="between quanta (serve_fleet)")
        return {"device_ops": trace.top_ops(ev, self.lo, self.hi),
                "idle_gaps": [[k, v] for k, v in sorted(
                    by.items(), key=lambda kv: -kv[1])[:10]]}


def read_metric(name: str, ctx: Context) -> Optional[float]:
    return parts.load("metrics", name).read(ctx)


def kernel_module(name: str):
    return parts.load("kernels", name)
