"""Jax-native env engine: logic equivalence with the numpy VecEdgeSimulator
under identical injected randomness, plus unit pins for the jnp primitives.

The harness drives both engines from the *same* imported state
(``state_from_numpy``) with the *same* per-frame draws (arrivals, waypoint
redraws, exploration placements) and asserts matching integer state
(poa / blocks_done / chain / collisions ...) and float-tolerance rewards.
It runs under ``jax.enable_x64(True)`` so both engines compute the
RWP kinematics and priorities in float64 — trajectories then agree exactly,
not just statistically.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import LearnGDMController, vec_greedy_mac
from repro.sim import EdgeSimulator, SimConfig, VecEdgeSimulator, jax_env

TABLE2 = dict(num_ues=15, num_channels=2, horizon=40)


def drive_pair(world_seed, ep_seeds, frames, *, placement_fn, rng):
    """Step the numpy and jax engines in lockstep with injected randomness;
    assert equivalence each frame.  Returns the final (venv, state)."""
    cfg = SimConfig(**TABLE2, seed=world_seed)
    e = len(ep_seeds)
    venv = VecEdgeSimulator(cfg, e)
    venv.reset(seeds=ep_seeds)
    world = jax_env.world_from_sim(venv)
    state = jax_env.state_from_numpy(venv)
    step = jax.jit(functools.partial(jax_env.env_step, cfg, world))
    jmac = jax.jit(functools.partial(jax_env.greedy_mac, cfg, world))

    for t in range(frames):
        mac_np = vec_greedy_mac(venv)
        assert np.array_equal(mac_np, np.asarray(jmac(state))), \
            f"frame {t}: greedy MAC diverged"
        pl = placement_fn(t)
        arrival = rng.random((e, cfg.num_ues))
        redraw = rng.uniform(0, cfg.side, size=(e, cfg.num_ues, 2))
        res = venv.step(mac_np, pl, arrival_draws=arrival,
                        waypoint_redraw=redraw)
        state, info = step(state, jnp.asarray(mac_np), jnp.asarray(pl),
                           arrival_draws=jnp.asarray(arrival),
                           waypoint_draws=jnp.asarray(redraw))
        for field in ("poa", "prev_poa", "blocks_done", "chain_state",
                      "cur_node", "has_request", "uploaded"):
            assert np.array_equal(getattr(venv, field),
                                  np.asarray(getattr(state, field))), \
                f"frame {t}: {field}"
        for k in ("bs_load", "delivered", "executed", "uploaded"):
            assert np.array_equal(res[k], np.asarray(info[k])), \
                f"frame {t}: {k}"
        for k in ("rewards", "quality_gain", "exec_cost", "trans_cost"):
            np.testing.assert_allclose(
                res[k], np.asarray(info[k]), atol=1e-9,
                err_msg=f"frame {t}: {k}")
        np.testing.assert_allclose(
            venv.observation(res["bs_load"]),
            np.asarray(jax_env.observe(cfg, world, state, info["bs_load"])),
            atol=1e-6)
        assert bool(info["done"]) == bool(res["done"])
    assert np.array_equal(venv.num_collisions, np.asarray(state.num_collisions))
    assert np.array_equal(venv.num_delivered, np.asarray(state.num_delivered))
    np.testing.assert_allclose(venv.total_delivered,
                               np.asarray(state.total_delivered), atol=1e-9)
    np.testing.assert_allclose(venv.delivered_quality,
                               np.asarray(state.delivered_quality), atol=1e-9)
    return venv, state


@pytest.mark.parametrize("world_seed,ep0", [(0, 101), (7, 900)])
def test_jax_engine_matches_numpy_random_placements(world_seed, ep0):
    with jax.enable_x64(True):
        cfg = SimConfig(**TABLE2, seed=world_seed)
        rng = np.random.default_rng(42 + world_seed)
        drive_pair(world_seed, [ep0 + i for i in range(3)], cfg.horizon,
                   placement_fn=lambda t: rng.integers(
                       -1, cfg.num_bs, size=(3, cfg.num_ues)),
                   rng=rng)


def test_jax_engine_matches_numpy_hotspot_placements():
    """Concentrated load (only BS 0..2) forces C3 capacity blocking — the
    rank/tie-break-sensitive path must still agree."""
    with jax.enable_x64(True):
        cfg = SimConfig(**TABLE2, seed=3)
        rng = np.random.default_rng(5)
        drive_pair(3, [55, 56], cfg.horizon,
                   placement_fn=lambda t: rng.integers(
                       -1, 3, size=(2, cfg.num_ues)),
                   rng=rng)


def test_segment_positions_matches_numpy_primitive():
    from repro.sim.vec_env import segment_positions as np_segpos
    rng = np.random.default_rng(0)
    groups = rng.integers(0, 7, size=64)
    ranks = rng.permutation(64)
    sel_np, pos_np = np_segpos(groups, ranks)
    sel_jx, pos_jx = jax_env.segment_positions(jnp.asarray(groups),
                                               jnp.asarray(ranks))
    assert np.array_equal(sel_np, np.asarray(sel_jx))
    assert np.array_equal(pos_np, np.asarray(pos_jx))


def test_action_mask_matches_controller_masks():
    """jax variant masks == action_mask_vec on a state imported mid-episode
    (blocks_done / cur_node populated)."""
    cfg = SimConfig(num_ues=8, num_channels=2, horizon=20, seed=4)
    env = EdgeSimulator(cfg)
    venv = VecEdgeSimulator(cfg, 2, seeds=np.full(2, cfg.seed))
    venv.reset(seeds=[3, 9])
    rng = np.random.default_rng(7)
    for _ in range(8):
        venv.step(vec_greedy_mac(venv),
                  rng.integers(-1, cfg.num_bs, size=(2, cfg.num_ues)))
    assert (venv.blocks_done > 0).any()          # mid-chain states exist
    world = jax_env.world_from_sim(venv)
    state = jax_env.state_from_numpy(venv)
    for variant in ("learn-gdm", "mp", "fp"):
        ctrl = LearnGDMController(env, variant=variant, seed=0)
        assert np.array_equal(
            ctrl.action_mask_vec(venv),
            np.asarray(jax_env.action_mask(cfg, state, variant))), variant


def test_reset_env_is_well_formed():
    cfg = SimConfig(**TABLE2, seed=0)
    world = jax_env.world_from_sim(EdgeSimulator(cfg), 16)
    state = jax_env.reset_env(cfg, world, jax.random.PRNGKey(0))
    poa = np.asarray(state.poa)
    assert poa.shape == (16, cfg.num_ues)
    assert poa.min() >= 0 and poa.max() < cfg.num_bs
    assert np.all(np.asarray(state.blocks_done) == 0)
    # request probability 0.9 at reset, as in the numpy engines
    assert 0.75 < np.asarray(state.has_request).mean() < 1.0
    assert int(state.frame) == 0


def test_f32_rollout_respects_capacity_and_ranges():
    """Default-dtype (float32) engine: C3 capacity and state-range
    invariants over a full episode with hotspot load."""
    cfg = SimConfig(**TABLE2, seed=1)
    e = 8
    world = jax_env.world_from_sim(EdgeSimulator(cfg), e)
    state = jax_env.reset_env(cfg, world, jax.random.PRNGKey(1))
    step = jax.jit(functools.partial(jax_env.env_step, cfg, world))
    jmac = jax.jit(functools.partial(jax_env.greedy_mac, cfg, world))
    rng = np.random.default_rng(2)
    w_hat = np.asarray(world.w_hat)
    for t in range(cfg.horizon):
        pl = jnp.asarray(np.zeros((e, cfg.num_ues), int))    # hammer BS 0
        state, info = step(state, jmac(state), pl)
        assert np.all(np.asarray(info["bs_load"]) <= w_hat)
        blocks = np.asarray(state.blocks_done)
        assert blocks.min() >= 0 and blocks.max() <= cfg.max_blocks
        assert np.all(np.isfinite(np.asarray(info["rewards"])))
    assert int(state.frame) == cfg.horizon
