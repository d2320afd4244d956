"""Unified policy/engine evaluation seam.

Three pins, mirroring the engine-equivalence strategy of PR 1/2 extended to
the *evaluation* path:

* ``evaluate_batched`` (and the controllers' default ``evaluate``) reproduces
  the legacy scalar ``evaluate()`` loop exactly — same EpisodeStats at any
  ``num_envs``, since episode seeds tile ``seed0 + ep`` and each stacked env
  replays the scalar stream bit-exactly;
* the fused eval scan (``jax_env.build_eval_round``) matches the numpy
  batched rollout under identical *injected* randomness (exact integer
  counters, 1e-9 float components, under x64);
* the three action-mask implementations (scalar ``variant_action_mask``,
  ``variant_action_mask_vec``, ``jax_env.action_mask``) agree for every
  variant across randomized mid-episode states.

Plus a ``slow``-marked tiny-grid Fig. 4 smoke sweep through the fused
training + batched eval path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (GreedyController, GreedyPoAPolicy,
                        LearnGDMController, LearnedPolicy, RandomPolicy,
                        greedy_mac, rollout_round, vec_greedy_mac,
                        variant_action_mask, variant_action_mask_vec)
from repro.sim import EdgeSimulator, SimConfig, VecEdgeSimulator, jax_env

CFG = SimConfig(num_ues=8, num_channels=2, horizon=16, seed=2)

COUNTER_KEYS = ("num_delivered", "collisions")


def assert_same_summary(a, b, *, atol=0.0):
    for k, v in a.items():
        if k in COUNTER_KEYS or atol == 0.0:
            assert b[k] == v, (k, v, b[k])
        else:
            np.testing.assert_allclose(b[k], v, atol=atol, err_msg=k)


# -- batched eval == scalar eval ----------------------------------------------

@pytest.mark.parametrize("variant", ["learn-gdm", "mp", "fp"])
def test_evaluate_batched_reproduces_scalar_evaluate(variant):
    ctrl = LearnGDMController(EdgeSimulator(CFG), variant=variant, seed=0)
    scalar = ctrl.evaluate(3, engine="scalar")
    for e in (1, 3):
        batched = ctrl.evaluate(3, engine="vectorized", num_envs=e)
        assert_same_summary(scalar, batched)


def test_evaluate_batched_reproduces_scalar_gr():
    gr = GreedyController(EdgeSimulator(CFG))
    scalar = gr.evaluate(4, engine="scalar")
    for e in (1, 3):
        assert_same_summary(scalar, gr.evaluate(4, engine="vectorized",
                                                num_envs=e))


def test_evaluate_fused_runs_and_is_statistically_sane():
    """Fused eval uses jax-native episode streams (not numpy-matched) — the
    API contract here is shape/keys plus finiteness; cross-engine logic is
    pinned under injected draws below."""
    ctrl = LearnGDMController(EdgeSimulator(CFG), variant="learn-gdm", seed=0)
    out = ctrl.evaluate(4, engine="fused", num_envs=2)
    ref = ctrl.evaluate(4, engine="scalar")
    assert set(out) == set(ref)
    assert all(np.isfinite(v) for v in out.values())


# -- fused eval scan == numpy rollout under injected draws --------------------

def _policies(cfg):
    agent = LearnGDMController(EdgeSimulator(cfg), variant="mp",
                               seed=0).agent
    return [LearnedPolicy(agent, "mp"), LearnedPolicy(agent, "learn-gdm"),
            GreedyPoAPolicy(), RandomPolicy("fp", seed=1)]


def test_eval_fused_matches_batched_rollout_under_injected_draws():
    with jax.enable_x64(True):
        cfg = SimConfig(num_ues=8, num_channels=2, horizon=16, seed=3)
        e, u, t = 3, cfg.num_ues, cfg.horizon
        rng = np.random.default_rng(5)
        for policy in _policies(cfg):
            venv = VecEdgeSimulator(cfg, e, seeds=np.full(e, cfg.seed))
            venv.reset(seeds=[11, 12, 13])
            world = jax_env.world_from_sim(venv)
            state0 = jax_env.state_from_numpy(venv)

            arrival = rng.random((t, e, u))
            waypoint = rng.uniform(0, cfg.side, size=(t, e, u, 2))
            pol_draws = rng.random((t, e, u, cfg.num_bs + 1)) \
                if policy.needs_draws else None

            stats_np = rollout_round(policy, venv, arrival_draws=arrival,
                                     waypoint_draws=waypoint,
                                     policy_draws=pol_draws)

            params, act_fn = policy.fused_spec(cfg)
            round_fn = jax_env.build_eval_round(cfg, act_fn,
                                                history=policy.history)
            draws = {"arrival": jnp.asarray(arrival),
                     "waypoint": jnp.asarray(waypoint)}
            if pol_draws is not None:
                draws["policy"] = jnp.asarray(pol_draws)
            _, out = round_fn(params, world, state0, draws)
            out = {k: np.asarray(v) for k, v in out.items()}

            for i in range(e):
                s = stats_np[i]
                assert out["num_delivered"][i] == s.num_delivered, policy.name
                assert out["collisions"][i] == s.collisions, policy.name
                for k, v in (("reward", s.reward),
                             ("quality_gain", s.quality_gain),
                             ("exec_cost", s.exec_cost),
                             ("trans_cost", s.trans_cost),
                             ("delivered_quality", s.delivered_quality)):
                    np.testing.assert_allclose(
                        out[k][i], v, atol=1e-9,
                        err_msg=f"{policy.name}: env {i} {k}")


# -- action-mask parity across all three engines ------------------------------

@pytest.mark.parametrize("variant", ["learn-gdm", "mp", "fp"])
def test_action_mask_parity_scalar_vec_jax(variant):
    """Scalar env and E=1 venv step in lockstep (bit-exact engines, shared
    placements) — at every frame the three mask implementations must agree
    on the randomized mid-episode state."""
    cfg = SimConfig(num_ues=7, num_channels=2, horizon=30, seed=5)
    env = EdgeSimulator(cfg)
    env.reset(seed=77)
    venv = VecEdgeSimulator(cfg, 1, seeds=np.full(1, cfg.seed))
    venv.reset(seeds=[77])
    rng = np.random.default_rng(9)
    saw_mid_chain = False
    for t in range(cfg.horizon):
        m_scalar = variant_action_mask(env, variant)
        m_vec = variant_action_mask_vec(venv, variant)
        m_jax = np.asarray(jax_env.action_mask(
            cfg, jax_env.state_from_numpy(venv), variant))
        assert np.array_equal(m_scalar, m_vec[0]), f"frame {t}: scalar/vec"
        assert np.array_equal(m_vec, m_jax), f"frame {t}: vec/jax"
        saw_mid_chain |= bool(((venv.blocks_done > 0)
                               & (venv.blocks_done < cfg.max_blocks)).any())
        pl = rng.integers(-1, cfg.num_bs, size=(1, cfg.num_ues))
        env.step(greedy_mac(env), pl[0])
        venv.step(vec_greedy_mac(venv), pl)
    assert saw_mid_chain      # the mp/fp branches were actually exercised


def test_action_mask_parity_batched_random_states():
    """E>1: vec and jax masks agree on states randomized per env."""
    cfg = SimConfig(num_ues=6, num_channels=2, horizon=12, seed=8)
    venv = VecEdgeSimulator(cfg, 4, seeds=np.full(4, cfg.seed))
    venv.reset(seeds=[1, 2, 3, 4])
    rng = np.random.default_rng(3)
    for _ in range(cfg.horizon):
        venv.step(vec_greedy_mac(venv),
                  rng.integers(-1, cfg.num_bs, size=(4, cfg.num_ues)))
        state = jax_env.state_from_numpy(venv)
        for variant in ("learn-gdm", "mp", "fp"):
            assert np.array_equal(
                variant_action_mask_vec(venv, variant),
                np.asarray(jax_env.action_mask(cfg, state, variant))), variant


def test_random_policy_respects_variant_mask_on_both_engines():
    cfg = SimConfig(num_ues=6, num_channels=2, horizon=10, seed=4)
    venv = VecEdgeSimulator(cfg, 2, seeds=np.full(2, cfg.seed))
    venv.reset(seeds=[5, 6])
    rng = np.random.default_rng(0)
    policy = RandomPolicy("mp", seed=2)
    _, act_fn = policy.fused_spec(cfg)
    for _ in range(cfg.horizon):
        venv.step(vec_greedy_mac(venv),
                  rng.integers(-1, cfg.num_bs, size=(2, cfg.num_ues)))
        mask = variant_action_mask_vec(venv, "mp")
        a_np = policy.act_batch(venv, None)
        assert mask[np.arange(2)[:, None], np.arange(cfg.num_ues), a_np].all()
        draw = jnp.asarray(rng.random((2, cfg.num_ues, cfg.num_bs + 1)))
        a_jx = np.asarray(act_fn((), jax_env.state_from_numpy(venv),
                                 None, draw))
        assert mask[np.arange(2)[:, None], np.arange(cfg.num_ues), a_jx].all()


# -- slow smoke sweep (Fig. 4 regression canary) ------------------------------

@pytest.mark.slow
def test_fig4_smoke_sweep_through_fused_path(tmp_path, monkeypatch):
    """Tiny U/C grid end-to-end through fused training + batched eval —
    catches Fig. 4 bench-path regressions without paper-scale wall clock."""
    import benchmarks.common as common
    from benchmarks.bench_channels import run as run_channels
    from benchmarks.bench_users import run as run_users
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_BENCH_ENGINE", "fused")
    monkeypatch.setenv("REPRO_BENCH_NUM_ENVS", "4")

    users = run_users(ue_counts=(4, 6), eval_eps=2, train_eps=8,
                      scenario="smoke")
    channels = run_channels(channel_counts=(1, 2), eval_eps=2, train_eps=8,
                            scenario="smoke")
    for summary in (users, channels):
        for key, point in summary.items():
            for m in ("learn-gdm", "mp", "fp", "gr", "opt"):
                assert np.isfinite(point[m]), (key, m)
            # OPT bounds the same evaluation episodes — a hard invariant
            assert point["ordering"]["opt_upper"], (key, point)
    assert (tmp_path / "fig4a_users.csv").exists()
    assert (tmp_path / "fig4b_channels.csv").exists()
