"""Drive the served gdm-dit fleet once on a TPU at full width, and check it.

    python chip_smoke.py             # one chip: the whole main path
    python chip_smoke.py --chips 4   # the two mesh paths beside their twins

One chip, all in this one process:

1. services — ``make_gdm_services`` with ``get_config("gdm-dit")`` at its
   published width (12 layers, d=768, 256 latent tokens), Ω measured on
   the chip; every service must resolve its kernels to compiled Pallas;
2. training — ``train_fused`` rounds of the learned placement policy (the
   donated fused round), first round (compile) and later rounds apart;
3. serving — two ``paper-fig3`` cells with quality thresholds drawn above
   every service's measured one-block Ω, so chains run 2-4 blocks unless
   the learned policy ends them, under quantum and continuous scheduling;
   requests must complete under both;
4. reference — the served block call on one bucket of live latents
   against ``run_block_batched(impl="xla")`` under
   ``jax.default_matmul_precision("highest")``.

``--chips 4`` runs only the two mesh paths on a 4-device mesh, each beside
its single-device twin: the fleet's stacked DiT batch sharded over
``"batch"`` (completions and frame metrics exact, latents within the
tolerance) and ``train_fused`` sharded over ``"env"`` (the pin of
``tests/test_mesh_sharding.py``).

Errors are measured against the step itself: max |served - ref| over
max |ref - input latent|, for the new latent and for the x0 estimate.
A phase that fails raises; nothing falls back to the CPU or to the XLA
path.  The last line printed is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

TOL = 5e-2                  # update-relative error bound, both comparisons
SEED = 0
CELLS, FRAMES = 2, 24
NUM_ENVS = 8
KERNEL_NAMES = ("adaln_norm", "adaln_norm_epilogue", "flash_attention",
                "gdm_block")
FRAME_METRICS = ("completed", "submitted", "handovers", "mean_quality",
                 "mean_latency_frames", "p95_latency_frames", "objective")


def log(msg: str) -> None:
    print(msg, flush=True)


def deep_scenario(omega=None):
    """``paper-fig3``; given Ω, with thresholds in [max_s Ω_s(1), 1): no
    chain clears its threshold after one block, and Ω(B) = 1 ends all."""
    from repro.sim.scenarios import get_scenario
    if omega is None:
        return get_scenario("paper-fig3")
    return get_scenario("paper-fig3", qbar_low=float(omega[:, 1].max()),
                        qbar_high=1.0)


def build_services(cfg, model_cfg, mesh=None):
    from repro.serving.gdm_service import make_gdm_services
    t0 = time.perf_counter()
    services, omega = make_gdm_services(
        cfg.num_services, jax.random.PRNGKey(SEED),
        num_blocks=cfg.max_blocks, steps_per_block=1, model_cfg=model_cfg,
        mesh=mesh)
    log(f"services: {len(services)} built in "
        f"{time.perf_counter() - t0:.3f} s (Ω measured, compile included)")
    return services, omega


def check_impl(services, want: str) -> None:
    impls = sorted({svc.resolved_impl for svc in services.values()})
    log(f"resolved impl: {','.join(impls)}")
    if impls != [want]:
        raise RuntimeError(f"kernels resolved to {impls}, want [{want!r}]")


def train(cfg, omega, mesh=None):
    """Two ``train_fused`` calls on one controller: the first compiles the
    round, the second reuses it (steady)."""
    from repro.core import LearnGDMController
    from repro.sim import EdgeSimulator
    ctrl = LearnGDMController(EdgeSimulator(cfg, quality=omega),
                              variant="learn-gdm", seed=SEED)
    t0 = time.perf_counter()
    first = ctrl.train_fused(NUM_ENVS, num_envs=NUM_ENVS, seed=SEED,
                             mesh=mesh)
    t1 = time.perf_counter()
    later = ctrl.train_fused(2 * NUM_ENVS, num_envs=NUM_ENVS, seed=SEED + 1,
                             mesh=mesh)
    t2 = time.perf_counter()
    hist = {k: first[k] + later[k] for k in first}
    if not np.all(np.isfinite(hist["reward"])):
        raise RuntimeError("train_fused produced non-finite rewards")
    for leaf in jax.tree_util.tree_leaves(ctrl.agent.params):
        if not np.all(np.isfinite(np.asarray(leaf))):
            raise RuntimeError("train_fused produced non-finite params")
    return ctrl, hist, t1 - t0, t2 - t1


def serve(cfg, services, scheduling, *, cells=CELLS, policy_factory=None,
          mesh=None):
    from repro.serving import EngineConfig, SchedulerConfig
    from repro.serving.cluster import cluster_from_scenario, serve_fleet
    from repro.sim.workloads import fleet_trace
    engine_cfg = sched = None
    if scheduling == "continuous":
        engine_cfg = EngineConfig(
            max_blocks=cfg.max_blocks, admission_slots=cfg.num_channels,
            alpha=cfg.alpha, beta=cfg.beta, early_exit=True, seed=cfg.seed,
            scheduling="continuous")
        sched = SchedulerConfig()
    fleet = fleet_trace(cfg, FRAMES, cells, workload="flash-crowd",
                        seed=SEED, handover_rate=0.02)
    cluster = cluster_from_scenario(cfg, cells, services,
                                    policy_factory=policy_factory,
                                    engine_cfg=engine_cfg, sched=sched,
                                    mesh=mesh)
    t0 = time.perf_counter()
    stats = serve_fleet(cluster, fleet, services, seed=SEED)
    return cluster, stats, time.perf_counter() - t0


def live_states(cluster, service: int, limit: int = 8):
    """Latents the fleet produced for ``service`` (completed requests)."""
    reqs = [r for eng in cluster.engines for r in eng.completed
            if r.service == service and r.state is not None]
    if not reqs:
        raise RuntimeError(f"no completed request of service {service}")
    return [dict(r.state) for r in reqs[:limit]]


def update_error(out, ref, base) -> float:
    out, ref, base = (np.asarray(a, np.float64) for a in (out, ref, base))
    return float(np.max(np.abs(out - ref))
                 / max(np.max(np.abs(ref - base)), 1e-30))


def served_errors(svc, ref_svc, states, idxs):
    """Update-relative errors of ``svc.run_batch`` against ``ref_svc``'s."""
    base = np.stack([s["latent"] for s in states])
    out, _ = svc.run_batch([dict(s) for s in states], idxs)
    want, _ = ref_svc.run_batch([dict(s) for s in states], idxs)
    return tuple(update_error(np.stack([o[k] for o in out]),
                              np.stack([w[k] for w in want]), base)
                 for k in ("latent", "x0"))


def reference_errors(svc, states, idxs, precision):
    """Update-relative errors of the served call against the XLA path of
    the same block call at ``precision``."""
    from repro.models.gdm import run_block_batched
    latent = np.stack([s["latent"] for s in states])
    prompt = np.stack([s["prompt"] for s in states])
    out, _ = svc.run_batch([dict(s) for s in states], idxs)

    def ref_call(params, lat, pr, idx):
        return run_block_batched(
            params, lat, pr, svc.cfg, svc.schedule, idx,
            steps_per_block=svc.steps_per_block,
            total_steps=svc.num_blocks * svc.steps_per_block, impl="xla")

    with jax.default_matmul_precision(precision):
        lat_r, x0_r = jax.jit(ref_call)(svc.params, latent, prompt,
                                        np.asarray(idxs, np.int32))
    return (update_error(np.stack([o["latent"] for o in out]), lat_r, latent),
            update_error(np.stack([o["x0"] for o in out]), x0_r, latent))


def runner_hlo(svc, bucket: int) -> str:
    """The compiled HLO of the service's served block call at ``bucket``."""
    latent, prompt, idx = svc._buffers[bucket]
    return svc._runner.lower(svc.params, latent, prompt, idx) \
        .compile().as_text()


def one_chip(model_cfg, want_impl: str = "pallas") -> None:
    from repro.core.policy import LearnedPolicy
    from repro.serving.tracing import MetricsRegistry
    base = deep_scenario()
    log(f"model: {model_cfg.name} layers={model_cfg.num_layers} "
        f"d={model_cfg.d_model} heads={model_cfg.num_heads}x"
        f"{model_cfg.head_dim} tokens={model_cfg.latent_hw ** 2}")

    services, omega = build_services(base, model_cfg)
    check_impl(services, want_impl)
    cfg = deep_scenario(omega)
    log(f"omega: {omega.tolist()}; thresholds in "
        f"[{cfg.qbar_low}, {cfg.qbar_high})")

    ctrl, hist, first_s, steady_s = train(cfg, omega)
    log(f"train_fused: {len(hist['reward'])} episodes, first round "
        f"(compile) {first_s:.3f} s, next 2 rounds {steady_s:.3f} s, "
        f"mean reward {np.mean(hist['reward']):.4f}")

    metrics = MetricsRegistry()
    for sid, svc in services.items():
        svc.instrument(metrics, sid)
    factory = lambda c: LearnedPolicy(ctrl.agent, "learn-gdm")  # noqa: E731
    clusters = {}
    for mode in ("quantum", "continuous"):
        cluster, stats, wall = serve(cfg, services, mode,
                                     policy_factory=factory)
        blocks = [r.blocks_done for eng in cluster.engines
                  for r in eng.completed]
        log(f"serve {mode}: submitted={stats['submitted']} "
            f"completed={stats['completed']} blocks="
            f"{min(blocks, default=0)}-{max(blocks, default=0)} "
            f"(mean {np.mean(blocks) if blocks else 0.0:.3f}) "
            f"lat={stats['mean_latency_frames']:.4f}f "
            f"quality={stats['mean_quality']:.4f} wall={wall:.3f} s")
        if stats["completed"] <= 0:
            raise RuntimeError(f"no request completed under {mode}")
        if max(blocks) < 2:
            raise RuntimeError(f"no chain ran past one block under {mode}")
        clusters[mode] = cluster
    compile_h = metrics.histogram("gdm_compile_ms")
    launch_h = metrics.histogram("launch_ms")
    wait_h = metrics.histogram("device_wait_ms")
    log(f"block calls: {compile_h.count} first-at-bucket (compile) "
        f"{compile_h.total / 1e3:.3f} s; {launch_h.count} launches "
        f"{launch_h.total / 1e3:.3f} s, device wait {wait_h.total / 1e3:.3f}"
        f" s (p50 {wait_h.percentile(50):.3f} ms)")

    svc = services[0]
    states = live_states(clusters["continuous"], 0)
    idxs = np.arange(len(states)) % svc.num_blocks
    errs = reference_errors(svc, states, idxs, "highest")
    floor = reference_errors(svc, states, idxs, "default")
    log(f"reference: {len(states)} live latents, update-relative error vs "
        f"f32 highest: latent {errs[0]:.6g} x0 {errs[1]:.6g} "
        f"(tolerance {TOL}); xla default precision vs served: "
        f"latent {floor[0]:.6g} x0 {floor[1]:.6g}")
    if max(errs) > TOL:
        raise RuntimeError(f"served block call is {max(errs):.3g} off the "
                           f"f32 reference (tolerance {TOL})")

    text = runner_hlo(svc, svc._bucket(len(states)))
    customs = text.count('custom_call_target="tpu_custom_call"')
    found = [n for n in KERNEL_NAMES if n in text]
    log(f"compiled runner: tpu_custom_call={customs} names={found}")
    if want_impl == "pallas" and customs <= 0:
        raise RuntimeError("no Pallas kernel in the compiled block call")


def four_chips(model_cfg) -> None:
    from repro.launch.mesh import make_env_mesh
    if len(jax.devices()) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, "
                           f"found {len(jax.devices())}")
    cells = 4

    # the fleet's stacked DiT batch over "batch"
    batch_mesh = make_env_mesh(4, axis="batch")
    if batch_mesh.devices.size != 4:
        raise RuntimeError(f"batch mesh has {batch_mesh.devices.size} "
                           f"devices, want 4")
    ref, omega = build_services(deep_scenario(), model_cfg)
    sharded, omega_sh = build_services(deep_scenario(), model_cfg,
                                       mesh=batch_mesh)
    if not np.array_equal(omega, omega_sh):
        raise RuntimeError("Ω differs between sharded and single-device")
    cfg = deep_scenario(omega)
    want_cluster, want, wall_ref = serve(cfg, ref, "quantum", cells=cells)
    _, got, wall_sh = serve(cfg, sharded, "quantum", cells=cells,
                            mesh=batch_mesh)
    diff = [k for k in FRAME_METRICS if got[k] != want[k]]
    log(f"fleet on 4 devices: completed {got['completed']} vs "
        f"{want['completed']} single-device, frame metrics "
        f"{'equal' if not diff else 'differ: ' + ','.join(diff)} "
        f"(wall {wall_sh:.3f} s vs {wall_ref:.3f} s)")
    if diff or got["completed"] <= 0:
        raise RuntimeError(f"sharded fleet differs from single device in "
                           f"{diff} or completed nothing")
    states = live_states(want_cluster, 0)
    idxs = np.arange(len(states)) % ref[0].num_blocks
    errs = served_errors(sharded[0], ref[0], states, idxs)
    log(f"fleet latents: update-relative error sharded vs single-device: "
        f"latent {errs[0]:.6g} x0 {errs[1]:.6g} (tolerance {TOL})")
    if max(errs) > TOL:
        raise RuntimeError(f"sharded latents {max(errs):.3g} off")

    # fused training over "env"
    env_mesh = make_env_mesh(4, axis="env")
    if env_mesh.devices.size != 4:
        raise RuntimeError(f"env mesh has {env_mesh.devices.size} devices")
    ctrl_ref, h_ref, _, _ = train(cfg, omega)
    ctrl_sh, h_sh, first_s, steady_s = train(cfg, omega, mesh=env_mesh)
    np.testing.assert_allclose(h_sh["reward"], h_ref["reward"], atol=1e-9,
                               rtol=0)
    np.testing.assert_array_equal(h_sh["delivered"], h_ref["delivered"])
    for name in ("params", "target_params"):
        for a, b in zip(
                jax.tree_util.tree_leaves(getattr(ctrl_sh.agent, name)),
                jax.tree_util.tree_leaves(getattr(ctrl_ref.agent, name))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-9, rtol=0)
    if (ctrl_sh.agent.epsilon, ctrl_sh.agent.steps) != \
            (ctrl_ref.agent.epsilon, ctrl_ref.agent.steps):
        raise RuntimeError("sharded training epsilon/steps differ")
    log(f"train_fused on 4 devices: {len(h_sh['reward'])} episodes equal "
        f"to single-device (rewards 1e-9, params 1e-9), first round "
        f"{first_s:.3f} s, next rounds {steady_s:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.jax_cache import use_compile_cache
    log(f"device_kind: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {use_compile_cache()}")

    t0 = time.perf_counter()
    model_cfg = get_config("gdm-dit")
    if args.chips == 4:
        four_chips(model_cfg)
    else:
        one_chip(model_cfg)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}  "
        f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
