"""Fleet-scale serving: the cluster engine and its pinning harnesses.

* **Cell equivalence** — with handover disabled and identical per-cell
  traces, every cell of a ``ClusterEngine`` (stacked execution ON)
  reproduces a standalone ``ServingEngine`` run frame-for-frame: identical
  per-quantum stats and identical end-of-run summaries.  This is the
  contract that lets fleet results stand in for N independent engine runs.
* **Stacked == sequential** — the one-call-per-service fleet execution path
  is bookkeeping-identical to per-cell per-node execution.
* **Pooled acts** — cells whose policies share a pool key are decided by
  one batched act per quantum; each cell's row equals its own one-env act
  bit for bit, and the act's one shape compiles when the cluster is built.
* **Handover** — in-flight latents migrate between cells with chain
  progress intact, the transfer is charged through the kv_manager ledger,
  and infeasible candidates (no in-flight request / destination slot busy)
  are skipped.
"""
import functools

import numpy as np
import pytest

from repro.core.learn_gdm import LearnGDMController
from repro.core.policy import GreedyPoAPolicy, LearnedPolicy, RandomPolicy
from repro.serving import (ClusterEngine, HandoverEvent, Request,
                           ServingPolicy, TelemetryLog, TransferLedger,
                           cluster_from_scenario, engine_from_scenario,
                           serve_fleet, serve_trace)
from repro.sim.env import EdgeSimulator
from repro.sim.scenarios import get_scenario
from repro.sim.workloads import fleet_trace


class LinearService:
    """Deterministic per-sample-independent service; counts batch calls."""

    def __init__(self, per_block=0.22):
        self.per_block = per_block
        self.omega = np.minimum(self.per_block * np.arange(5), 1.0)
        self.batch_calls = 0

    def block_fn(self, state, block_idx):
        states, qs = self.run_batch([state], np.asarray([block_idx]))
        return states[0], float(qs[0])

    def run_batch(self, states, block_idxs):
        self.batch_calls += 1
        return ([dict(s or {}) for s in states],
                np.minimum(self.per_block * (np.asarray(block_idxs) + 1), 1.0))

    def init_state(self, rng):
        return {"latent": np.zeros((8, 2), np.float32)}


def _services(cfg, per_block=0.22):
    return {s: LinearService(per_block) for s in range(cfg.num_services)}


def _record_steps(engine):
    log = []
    orig = engine.step

    def step():
        log.append(orig())
        return log[-1]

    engine.step = step
    return log


CELLS = 3
FRAMES = 12


def _fresh_agent(seed=0):
    """An untrained D3QL agent for the smoke world."""
    return LearnGDMController(EdgeSimulator(get_scenario("smoke")),
                              variant="learn-gdm", seed=seed).agent


# one agent per seed for the whole module, as a fleet shares its agent
_agent = functools.lru_cache(maxsize=None)(_fresh_agent)


def _standalone_runs(cfg, fleet, services, *, policy_factory=None):
    """Reference: each cell's trace served on its own ServingEngine."""
    outs = []
    for c in range(fleet.num_cells):
        engine, world = engine_from_scenario(cfg, services)
        if policy_factory is not None:
            engine.placement_fn = ServingPolicy(policy_factory(c), cfg,
                                                world=world)
        log = _record_steps(engine)
        out = serve_trace(engine, fleet.cells[c], services, seed=(0, c))
        outs.append((out, log, engine.summary(fleet.frames)))
    return outs


@pytest.mark.parametrize("policy_factory", [
    None,                                        # engine default placement
    lambda c: GreedyPoAPolicy(),                 # bridged GR per cell
    lambda c: RandomPolicy(seed=c),              # stochastic, per-cell seed
    lambda c: LearnedPolicy(_agent(), "learn-gdm"),   # one agent, one pool
    lambda c: LearnedPolicy(_agent(c % 2), "mp"),     # two agents, two pools
    lambda c: GreedyPoAPolicy() if c % 2 else RandomPolicy(seed=c),
], ids=["default", "greedy-bridge", "random-bridge", "learned-bridge",
        "learned-two-agents", "pooled-and-alone"])
def test_cluster_cells_match_standalone_engines(policy_factory):
    cfg = get_scenario("smoke")
    fleet = fleet_trace(cfg, FRAMES, CELLS, workload="stationary", seed=5)
    standalone = _standalone_runs(cfg, fleet, _services(cfg),
                                  policy_factory=policy_factory)

    cluster = cluster_from_scenario(cfg, CELLS, _services(cfg),
                                    policy_factory=policy_factory)
    out = serve_fleet(cluster, fleet, _services(cfg), seed=0,
                      collect_steps=True)
    # NB: serve_fleet passes the cluster's own shared services for state
    # init; re-passing fresh ones above would desync nothing for this
    # stateless service but the cluster must execute on ITS instances
    for c in range(CELLS):
        ref_out, ref_log, ref_summary = standalone[c]
        assert cluster.engines[c].summary(FRAMES) == ref_summary
        for t in range(FRAMES):
            assert out["steps"][t][c] == ref_log[t], (c, t)
    assert out["completed"] == sum(s[0]["completed"] for s in standalone)
    assert out["submitted"] == sum(s[0]["submitted"] for s in standalone)


def _learned_cluster(cfg, services, agents, cells, **kw):
    return cluster_from_scenario(
        cfg, cells, services, policy_factory=lambda c: LearnedPolicy(
            agents[c % len(agents)], "learn-gdm"), **kw)


def test_pooled_act_rows_equal_each_cells_own_act():
    """One act over 3 cells, one of them with a dead node (so the stacked
    node liveness is exercised), with the bridges' record on: every cell's
    actions equal its own one-env act on the same view and history."""
    cfg = get_scenario("smoke")
    services = _services(cfg)
    cluster = _learned_cluster(cfg, services, [_agent()], CELLS,
                               tracing=True)
    (pool,) = cluster.pools
    assert pool.engines == cluster.engines
    up = np.ones(cfg.num_bs, dtype=bool)
    up[1] = False
    cluster.engines[2].set_fault_state(up)      # held: serve_fleet sets none
    seen = {c: [] for c in range(CELLS)}
    for c, eng in enumerate(cluster.engines):
        bridge = eng.placement_fn
        bridge.record = True

        def observe(engine, _inner=bridge.observe, _c=c):
            seen[_c].append(_inner(engine))
            return seen[_c][-1]

        bridge.observe = observe
    serve_fleet(cluster, fleet_trace(cfg, FRAMES, CELLS,
                                     workload="flash-crowd", seed=5),
                services, seed=0)
    placed = 0
    for c, eng in enumerate(cluster.engines):
        bridge = eng.placement_fn
        assert len(bridge.trace) == len(seen[c]) == FRAMES
        for (frame, hist, actions), (view, obs_hist) in zip(bridge.trace,
                                                            seen[c]):
            assert view.num_envs == 1 and np.array_equal(hist, obs_hist)
            assert (view.node_up is None) == (c != 2)
            alone = bridge.policy.act_batch(view, obs_hist)[0]
            assert np.array_equal(actions, alone), (c, frame)
            if c == 2:
                assert not (actions == 2).any()  # never onto dead node 1
            placed += int((actions > 0).sum())
    assert placed > 0
    metrics = cluster.metrics
    assert metrics.histogram("policy_act_batch_ms").count == FRAMES
    assert metrics.counter("policy_act_cells").value == CELLS * FRAMES
    assert metrics.histogram("policy_obs_ms").count == CELLS * FRAMES


def test_random_policy_cells_act_one_by_one():
    # a per-instance rng: pooling would change which draws a cell sees
    cfg = get_scenario("smoke")
    services = _services(cfg)
    cluster = cluster_from_scenario(
        cfg, CELLS, services, policy_factory=lambda c: RandomPolicy(seed=c),
        tracing=True)
    assert cluster.pools == []
    serve_fleet(cluster, fleet_trace(cfg, FRAMES, CELLS, seed=5), services,
                seed=0)
    metrics = cluster.metrics
    assert metrics.histogram("policy_act_batch_ms").count == CELLS * FRAMES
    assert metrics.counter("policy_act_cells").value == CELLS * FRAMES


def test_distinct_agents_form_distinct_pools():
    cfg = get_scenario("smoke")
    agents = [_agent(0), _agent(1)]
    cluster = _learned_cluster(cfg, _services(cfg), agents, 5)
    pools = {id(p.policy.agent): [e.cell_id for e in p.engines]
             for p in cluster.pools}
    assert pools == {id(agents[0]): [0, 2, 4], id(agents[1]): [1, 3]}
    # the greedy baseline pools by its type; other variants apart
    mixed = cluster_from_scenario(
        cfg, 4, _services(cfg), policy_factory=lambda c: (
            GreedyPoAPolicy(), LearnedPolicy(agents[0], "learn-gdm"),
            LearnedPolicy(agents[0], "fp"), GreedyPoAPolicy())[c])
    assert sorted([e.cell_id for e in p.engines] for p in mixed.pools) \
        == [[0, 3], [1], [2]]


def test_pool_compiles_its_act_once_when_the_cluster_is_built():
    cfg = get_scenario("smoke")
    agent = _fresh_agent(seed=7)
    assert agent._qvals_rows._cache_size() == 0
    services = _services(cfg)
    cluster = _learned_cluster(cfg, services, [agent], CELLS)
    assert agent._qvals_rows._cache_size() == 1      # the pool's (3, H, D) act
    serve_fleet(cluster, fleet_trace(cfg, FRAMES, CELLS, seed=5), services,
                seed=0)
    assert agent._qvals_rows._cache_size() == 1      # nothing compiled serving


def test_cluster_serves_on_shared_service_instances():
    """Stacked execution must hit the cluster's shared services exactly once
    per (service, quantum) — not once per (cell, node, service)."""
    cfg = get_scenario("smoke")
    services = _services(cfg)
    cluster = cluster_from_scenario(cfg, CELLS, services)
    fleet = fleet_trace(cfg, FRAMES, CELLS, seed=5)
    serve_fleet(cluster, fleet, services, seed=0)
    calls_stacked = sum(s.batch_calls for s in services.values())
    # at most one call per (service, quantum); >= 1 quantum had work
    assert 0 < calls_stacked <= cfg.num_services * FRAMES

    services_seq = _services(cfg)
    cluster_seq = cluster_from_scenario(cfg, CELLS, services_seq,
                                        stacked=False)
    serve_fleet(cluster_seq, fleet, services_seq, seed=0)
    calls_seq = sum(s.batch_calls for s in services_seq.values())
    assert calls_seq > calls_stacked          # per-(cell, node) degradation


def test_stacked_equals_sequential_execution():
    cfg = get_scenario("smoke")
    fleet = fleet_trace(cfg, FRAMES, CELLS, workload="diurnal", seed=9)
    results = []
    for stacked in (True, False):
        services = _services(cfg)
        cluster = cluster_from_scenario(cfg, CELLS, services,
                                        stacked=stacked)
        out = serve_fleet(cluster, fleet, services, seed=0,
                          collect_steps=True)
        results.append(out)
    assert results[0] == results[1]


# -- handover ------------------------------------------------------------------

def _two_cell_cluster(cfg, services, **kw):
    return cluster_from_scenario(cfg, 2, services, **kw)


def test_handover_migrates_in_flight_latents():
    cfg = get_scenario("smoke", capacity_low=5, capacity_high=5)
    services = _services(cfg, per_block=0.2)
    ledger = TransferLedger()
    cluster = _two_cell_cluster(cfg, services, ledger=ledger,
                                handover_cost=0.4)
    req = Request(rid=0, service=0, arrival_frame=0, quality_threshold=0.75,
                  ue=2, origin=0, state=services[0].init_state(None))
    cluster.submit(0, req)
    cluster.step()                               # admit + first block
    assert req.blocks_done == 1 and not req.done

    applied = cluster.apply_handovers(
        [HandoverEvent(ue=2, src_cell=0, dst_cell=1, dst_origin=3)])
    assert len(applied) == 1
    assert req not in cluster.engines[0].active
    assert req in cluster.engines[1].active
    assert req.blocks_done == 1                  # latents travelled intact
    assert req.node == -1 and req.origin == 3    # placement restarts at PoA
    assert req.handover_cost == pytest.approx(0.4)
    totals = ledger.totals()
    assert totals["handover"]["count"] == 1
    assert totals["handover"]["nbytes"] > 0

    # the chain finishes in the destination cell under the one clock
    for _ in range(6):
        cluster.step()
    assert req.done and req in cluster.engines[1].completed
    assert req.quality >= req.quality_threshold


def test_handover_skips_infeasible_candidates():
    cfg = get_scenario("smoke")
    services = _services(cfg)
    cluster = _two_cell_cluster(cfg, services)
    # no in-flight request for UE 1 anywhere -> no-op
    assert cluster.apply_handovers(
        [HandoverEvent(ue=1, src_cell=0, dst_cell=1, dst_origin=0)]) == []

    # destination slot busy -> skipped, request stays home
    a = Request(rid=0, service=0, arrival_frame=0, quality_threshold=0.9,
                ue=1, origin=0, state={})
    b = Request(rid=1, service=0, arrival_frame=0, quality_threshold=0.9,
                ue=1, origin=0, state={})
    cluster.submit(0, a)
    cluster.submit(1, b)
    cluster.step()
    assert cluster.apply_handovers(
        [HandoverEvent(ue=1, src_cell=0, dst_cell=1, dst_origin=0)]) == []
    assert a in cluster.engines[0].active
    assert cluster.handovers_applied == 0


def test_fleet_handover_integration_conserves_requests():
    cfg = get_scenario("smoke", arrival_prob=0.08, qbar_low=0.4,
                       qbar_high=0.5)
    services = _services(cfg, per_block=0.12)
    ledger = TransferLedger()
    cluster = cluster_from_scenario(cfg, CELLS, services, ledger=ledger)
    fleet = fleet_trace(cfg, 30, CELLS, workload="mmpp", seed=2,
                        handover_rate=0.3, low=0.02, high=0.3)
    out = serve_fleet(cluster, fleet, services, seed=0)
    assert out["handovers"] > 0
    in_flight = sum(len(e.active) + len(e.pending)
                    for e in cluster.engines)
    assert out["completed"] + in_flight == out["submitted"]
    assert ledger.totals()["handover"]["count"] == cluster.handovers_applied
    # handed-over completed requests carry the charge in their trans_cost
    moved = [r for eng in cluster.engines for r in eng.completed
             if r.handover_cost > 0]
    assert moved, "no handed-over request completed"
    for r in moved:
        assert r.trans_cost >= r.handover_cost


def test_cluster_telemetry_stream():
    cfg = get_scenario("smoke")
    telemetry = TelemetryLog()
    services = _services(cfg)
    cluster = cluster_from_scenario(cfg, CELLS, services,
                                    telemetry=telemetry)
    fleet = fleet_trace(cfg, FRAMES, CELLS, seed=5)
    serve_fleet(cluster, fleet, services, seed=0)
    assert len(telemetry.events) == CELLS * FRAMES
    assert {ev.cell for ev in telemetry.events} == set(range(CELLS))
    summary = telemetry.summary()
    assert summary["delivered"] > 0
    assert 0.0 <= summary["mean_node_utilization"] <= 1.0
    # per-quantum loads never exceed capacity
    for ev in telemetry.events:
        assert all(l <= c for l, c in zip(ev.node_load, ev.node_capacity))
