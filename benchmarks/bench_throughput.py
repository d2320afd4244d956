"""Rollout-engine throughput: scalar loop vs vectorized vs fused-jax engine.

Measures pure environment frames/sec at Table II scale (15 UEs, 16 BS,
2 channels) — greedy MAC + seeded random placements, no agent in the loop —
for the scalar ``EdgeSimulator``, the numpy ``VecEdgeSimulator`` and the
jax-native ``repro.sim.jax_env`` engine (one jitted ``lax.scan`` chunk per
timed call, auto-reset in-scan) at E ∈ {1, 8, 32}.

Pass criteria: vectorized E=32 ≥ 5× scalar (ISSUE 1) and fused-jax E=32 ≥
3× the numpy vectorized engine at the same E (ISSUE 2) — the fused engine
pays one XLA dispatch per CHUNK frames instead of a Python interpreter
round-trip per frame.

Devices axis (ISSUE 6): the fused E=32 chunk is re-run shard_map-sharded
over the env dim for every device count in {1, 2, 4} that the host exposes
(``REPRO_BENCH_DEVICES`` + fake host devices on CPU CI), emitting one
``fused_sharded_e32_d<N>`` row per count — the documented single-device
plateau (fused E=32 below E=8) is visible in BENCH_throughput.json, and
the best sharded row must hold the single-device fused E=32 baseline.
Measured (4 fake CPU devices, REPRO_BENCH_SCALE=1):

    engine              frames/s   vs single-device fused E=32
    fused_e32            356,206       1.00x
    fused_sharded_e32_d1 455,759       1.28x
    fused_sharded_e32_d2 378,329       1.06x
    fused_sharded_e32_d4 277,638       0.78x

Fake devices share the host's cores, so parity (not speedup) is the CI
bar; the d=1 gain is shard_map's tighter lowering of the same program.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks.common import emit, save_csv, scaled
from repro.core.mac import greedy_mac, vec_greedy_mac
from repro.sim import EdgeSimulator, SimConfig, VecEdgeSimulator

ENV_COUNTS = (1, 8, 32)
FUSED_CHUNK = 64          # frames per jitted scan chunk (ISSUE 2: >= 16)
DEVICE_COUNTS = (1, 2, 4)  # sharded rows, clipped to visible devices


def _scalar_fps(cfg: SimConfig, frames: int) -> float:
    env = EdgeSimulator(cfg)
    env.reset(seed=5)
    rng = np.random.default_rng(2)
    placements = rng.integers(-1, cfg.num_bs, size=(frames, cfg.num_ues))
    t0 = time.perf_counter()
    for t in range(frames):
        if env.frame >= cfg.horizon:
            env.reset(seed=5 + t)
        env.step(greedy_mac(env), placements[t])
    return frames / (time.perf_counter() - t0)


def _vec_fps(cfg: SimConfig, num_envs: int, frames: int) -> float:
    venv = VecEdgeSimulator(cfg, num_envs)
    venv.reset(seeds=5 + np.arange(num_envs))
    rng = np.random.default_rng(2)
    steps = max(frames // num_envs, 1)
    placements = rng.integers(-1, cfg.num_bs,
                              size=(steps, num_envs, cfg.num_ues))
    t0 = time.perf_counter()
    for t in range(steps):
        if venv.frame >= cfg.horizon:
            venv.reset(seeds=5 + t + np.arange(num_envs))
        venv.step(vec_greedy_mac(venv), placements[t])
    return steps * num_envs / (time.perf_counter() - t0)


def _fused_fps(cfg: SimConfig, num_envs: int, frames: int,
               chunk: int = FUSED_CHUNK, mesh=None,
               axis: str = "env") -> float:
    """Fused-jax engine: CHUNK frames of greedy MAC + random placement +
    env step per jitted ``lax.scan`` call, episode auto-reset in-scan.
    With ``mesh``, the whole chunk runs shard_map-sharded over the env dim
    (zero cross-shard communication — every frame quantity is per-env)."""
    import jax
    import jax.numpy as jnp

    from repro.sim import jax_env

    env = EdgeSimulator(cfg)
    world = jax_env.world_from_sim(env, num_envs)
    u = cfg.num_ues

    def body(world, state, xs):
        placement, arrivals, redraws = xs
        mac = jax_env.greedy_mac(cfg, world, state)
        state, _ = jax_env.env_step(cfg, world, state, mac, placement,
                                    arrival_draws=arrivals,
                                    waypoint_draws=redraws)
        state = jax.lax.cond(
            state.frame >= cfg.horizon,
            lambda s: jax_env.reset_env(cfg, world, s.key),
            lambda s: s, state)
        return state, None

    def chunk_body(world, state, placement, arrivals, redraws):
        state, _ = jax.lax.scan(functools.partial(body, world), state,
                                (placement, arrivals, redraws))
        return state

    if mesh is not None:
        from jax import P, shard_map
        chunk_exec = shard_map(
            chunk_body, mesh=mesh,
            in_specs=(jax_env.world_specs(axis), jax_env.state_specs(axis),
                      P(None, axis), P(None, axis), P(None, axis)),
            out_specs=jax_env.state_specs(axis), check_vma=False)
    else:
        chunk_exec = chunk_body

    @jax.jit
    def run_chunk(state, key):
        # per-frame threefry inside the scan is an XLA:CPU hot spot — draw
        # the whole chunk's randomness in three batched calls instead
        k1, k2, k3 = jax.random.split(key, 3)
        placement = jax.random.randint(k1, (chunk, num_envs, u),
                                       -1, cfg.num_bs)
        arrivals = jax.random.uniform(k2, (chunk, num_envs, u))
        redraws = jax.random.uniform(k3, (chunk, num_envs, u, 2),
                                     jnp.float32, 0.0, cfg.side)
        return chunk_exec(world, state, placement, arrivals, redraws)

    state = jax_env.reset_env(cfg, world, jax.random.PRNGKey(5))
    key = jax.random.PRNGKey(2)
    # two warmup calls: the first compiles for single-device inputs, the
    # second for the sharded state the chunk feeds back to itself — timing
    # after one warmup would charge the second (~1 s) compile to the loop
    state = run_chunk(state, key)
    state = run_chunk(state, jax.random.fold_in(key, 2**31))
    state.poa.block_until_ready()
    n_chunks = max(max(frames // num_envs, 1) // chunk, 1)
    t0 = time.perf_counter()
    for i in range(n_chunks):
        state = run_chunk(state, jax.random.fold_in(key, i))
    state.poa.block_until_ready()
    return n_chunks * chunk * num_envs / (time.perf_counter() - t0)


def run(frames: int = 0, seed: int = 0) -> dict:
    frames = frames or scaled(20_000, lo=2_000)
    cfg = SimConfig(num_ues=15, num_channels=2, horizon=40, seed=seed)

    scalar = _scalar_fps(cfg, frames)
    rows = [("scalar", 1, scalar, 1.0)]
    result = {"scalar_fps": scalar}
    for e in ENV_COUNTS:
        fps = _vec_fps(cfg, e, frames)
        rows.append((f"vec_e{e}", e, fps, fps / scalar))
        result[f"vec_e{e}_fps"] = fps
        result[f"vec_e{e}_speedup"] = fps / scalar
    for e in ENV_COUNTS:
        fps = _fused_fps(cfg, e, frames)
        rows.append((f"fused_e{e}", e, fps, fps / scalar))
        result[f"fused_e{e}_fps"] = fps
        result[f"fused_e{e}_speedup"] = fps / scalar
        result[f"fused_e{e}_vs_vec"] = fps / result[f"vec_e{e}_fps"]

    # -- devices axis: shard the fused E=32 chunk over the env mesh ------------
    import jax

    from repro.launch.mesh import make_env_mesh

    counts = [d for d in DEVICE_COUNTS if d <= len(jax.devices())]
    result["devices"] = {}
    for d in counts:
        fps = _fused_fps(cfg, 32, frames, mesh=make_env_mesh(d))
        rows.append((f"fused_sharded_e32_d{d}", 32, fps, fps / scalar))
        result["devices"][str(d)] = {"fused_e32_fps": fps,
                                     "vs_single_device":
                                     fps / result["fused_e32_fps"]}
    emit("rollout_sharded", 0.0,
         "; ".join(f"d={d} {result['devices'][str(d)]['fused_e32_fps']:,.0f}"
                   f" f/s ({result['devices'][str(d)]['vs_single_device']:.2f}x)"
                   for d in counts))

    save_csv("throughput", ["engine", "num_envs", "frames_per_sec", "speedup"],
             rows)
    emit("rollout_throughput", 1e6 / scalar,
         "; ".join(f"E={e} vec {result[f'vec_e{e}_fps']:,.0f} "
                   f"fused {result[f'fused_e{e}_fps']:,.0f} f/s "
                   f"({result[f'fused_e{e}_vs_vec']:.1f}x)"
                   for e in ENV_COUNTS))
    target = result["vec_e32_speedup"]
    assert target >= 5.0, \
        f"vectorized E=32 speedup {target:.1f}x below the 5x pass bar"
    fused_target = result["fused_e32_vs_vec"]
    assert fused_target >= 3.0, \
        f"fused E=32 only {fused_target:.1f}x the numpy vec engine (< 3x bar)"
    # the plateau guard (ISSUE 6): the best sharded fused E=32 row must
    # hold the single-device fused E=32 baseline — on fake CPU devices the
    # shards share the same cores, so "no regression from sharding" is the
    # meaningful bar (real multi-device scaling needs real devices)
    sharded = [result["devices"][str(d)]["fused_e32_fps"] for d in counts]
    if sharded:
        best = max(sharded)
        assert best >= result["fused_e32_fps"], \
            f"sharded fused E=32 peaked at {best:,.0f} f/s, below the " \
            f"single-device {result['fused_e32_fps']:,.0f} f/s baseline"
    return result


if __name__ == "__main__":
    run()
