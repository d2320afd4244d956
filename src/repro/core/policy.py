"""Unified policy/engine seam: every controller runs on every engine.

A :class:`Policy` is a placement decision rule exposed twice:

* ``act_batch(venv, obs_hist, draw)`` — numpy batched acting against a
  :class:`~repro.sim.vec_env.VecEdgeSimulator` (the host-loop engine);
* ``fused_spec(cfg)`` — a ``(params, act_fn)`` pair where
  ``act_fn(params, state, obs_hist, draw)`` is pure jax, suitable for the
  jitted evaluation scan on the device-resident engine
  (:func:`repro.sim.jax_env.build_eval_round`).

Both paths emit (E, U) int actions in the controller convention (0 = null,
n+1 = BS n) and both apply the variant mask *after* any stochastic merge —
the same invariant the training paths enforce via ``masked_argmax`` /
``fused_act``.

The shared batched rollout (:func:`evaluate_batched`) reproduces the legacy
scalar ``evaluate()`` loop exactly: at any ``num_envs`` the stacked envs
replay the scalar per-episode streams (seeds ``seed0 + episode``), obs
history padding matches ``LearnGDMController._obs_hist``, and episode
totals accumulate in the scalar frame order — pinned by
``tests/test_policy_eval.py``.  :func:`evaluate_fused` runs the same policy
through one jitted scan per round; its episode randomness is jax-native, and
its logic is pinned to the numpy rollout under injected draws by the same
test module (the PR 2 equivalence-harness pattern, extended to eval).
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.learn_gdm import (EpisodeStats, obs_history_window,
                                  summarize, variant_action_mask_vec)
from repro.core.mac import vec_greedy_mac, vec_random_access
from repro.rl.d3ql import greedy_act, masked_argmax
from repro.sim import jax_env
from repro.sim.env import IDLE, EdgeSimulator, SimConfig
from repro.sim.vec_env import VecEdgeSimulator


class Policy:
    """Base policy: one decision rule, runnable on every engine.

    Subclasses set ``name`` and override :meth:`act_batch` +
    :meth:`fused_spec`.  ``needs_obs``/``history`` tell the rollouts whether
    (and how deep) an observation history must be maintained; ``needs_draws``
    requests a per-frame (E, U, A) uniform block (stochastic policies must
    take randomness through it to stay scan-pure on the fused engine).

    :meth:`fused_spec` returns ``(params, act_fn)`` where ``act_fn`` must
    be pure and must NOT capture device arrays — anything world- or
    agent-derived goes through ``params`` (a traced argument), so the
    compiled eval round is reusable across worlds and params.
    :meth:`fused_key` is the hashable identity of that ``act_fn``'s trace
    (everything baked into it besides ``cfg``) — the compile-cache key in
    :func:`evaluate_fused`.

    :meth:`pool_key` says which policies may decide together: instances
    with the same non-None key give every env of one stacked
    :meth:`act_pooled` call the action it would get alone, so a serving
    fleet decides all their cells in one call (``None``: act alone).
    """

    name: str = "policy"
    needs_obs: bool = False
    history: int = 1
    needs_draws: bool = False

    def act_batch(self, venv: VecEdgeSimulator,
                  obs_hist: Optional[np.ndarray],
                  draw: Optional[np.ndarray] = None) -> np.ndarray:
        raise NotImplementedError

    def fused_spec(self, cfg: SimConfig) -> Tuple:
        raise NotImplementedError

    def fused_key(self) -> Tuple:
        return (type(self).__name__, getattr(self, "variant", None))

    def pool_key(self) -> Optional[Hashable]:
        return None

    def act_pooled(self, venv: VecEdgeSimulator,
                   obs_hist: Optional[np.ndarray]) -> np.ndarray:
        """:meth:`act_batch` for a pool of envs that share a
        :meth:`pool_key`: each env gets the action it would get alone."""
        return self.act_batch(venv, obs_hist)


class LearnedPolicy(Policy):
    """Greedy-eval D3QL placement under the variant's action mask
    (learn-gdm / mp / fp)."""

    needs_obs = True

    def __init__(self, agent, variant: str = "learn-gdm"):
        assert variant in ("learn-gdm", "mp", "fp")
        self.agent = agent
        self.variant = variant
        self.name = variant
        self.history = agent.cfg.history

    def act_batch(self, venv, obs_hist, draw=None):
        mask = variant_action_mask_vec(venv, self.variant)
        return self.agent.act_batch(obs_hist, greedy=True, mask=mask)

    def fused_spec(self, cfg):
        acfg = self.agent.cfg
        variant = self.variant

        def act_fn(params, state, obs_hist, draw):
            mask = jax_env.action_mask(cfg, state, variant)
            return greedy_act(params, obs_hist, mask=mask,
                              num_ues=acfg.num_ues,
                              num_actions=acfg.num_actions)

        return self.agent.params, act_fn

    def fused_key(self):
        acfg = self.agent.cfg
        return (type(self).__name__, self.variant, acfg.num_ues,
                acfg.num_actions, acfg.history)

    def pool_key(self):
        # greedy acting is deterministic: one call over stacked envs gives
        # each env its own action (act_pooled)
        return (id(self.agent), self.variant)

    def act_pooled(self, venv, obs_hist):
        mask = variant_action_mask_vec(venv, self.variant)
        return self.agent.act_rows(obs_hist, mask=mask)


class GreedyPoAPolicy(Policy):
    """GR baseline: every block executes at the UE's current PoA; chains
    always run to full length (never the null action while active)."""

    name = "gr"

    def act_batch(self, venv, obs_hist, draw=None):
        return np.where(venv.chain_state != IDLE, venv.poa + 1, 0)

    def fused_spec(self, cfg):
        def act_fn(params, state, obs_hist, draw):
            return jnp.where(state.chain_state != IDLE, state.poa + 1,
                             0).astype(jnp.int32)

        return (), act_fn

    def pool_key(self):
        return type(self).__name__


class RandomPolicy(Policy):
    """Uniform over the variant's allowed actions (exploration floor
    baseline).  Randomness comes from the rollout's draw block, so numpy and
    fused runs given identical draws pick identical actions."""

    needs_draws = True

    def __init__(self, variant: str = "learn-gdm", seed: int = 0):
        self.variant = variant
        self.name = f"random-{variant}"
        self.seed = seed
        # fallback stream for direct act_batch calls; the evaluation
        # rollouts inject per-episode draw stacks instead (deterministic
        # and num_envs-independent)
        self.rng = np.random.default_rng(seed)

    def act_batch(self, venv, obs_hist, draw=None):
        cfg = venv.cfg
        if draw is None:
            draw = self.rng.random(
                (venv.num_envs, cfg.num_ues, cfg.num_bs + 1))
        mask = variant_action_mask_vec(venv, self.variant)
        return masked_argmax(draw, mask)

    def fused_spec(self, cfg):
        variant = self.variant

        def act_fn(params, state, obs_hist, draw):
            mask = jax_env.action_mask(cfg, state, variant)
            return jnp.argmax(jnp.where(mask, draw, -jnp.inf),
                              axis=-1).astype(jnp.int32)

        return (), act_fn


# -- shared batched rollout (numpy vectorized engine) --------------------------

def _obs_hist(history: deque, h: int) -> np.ndarray:
    """(E, H, obs_dim) window — the controller's shared eq. (7) rule."""
    return obs_history_window(history, h)


def rollout_round(policy: Policy, venv: VecEdgeSimulator, *,
                  mac_scheme: str = "greedy",
                  arrival_draws: Optional[np.ndarray] = None,
                  waypoint_draws: Optional[np.ndarray] = None,
                  policy_draws: Optional[np.ndarray] = None,
                  ) -> List[EpisodeStats]:
    """One evaluation round: one episode per stacked env, any policy.

    ``venv`` must be freshly reset (episode counters zero).  The optional
    (T, ...) draw stacks replace the native per-env streams — the injection
    hooks the fused-vs-numpy equivalence harness drives both engines with.
    Returns one :class:`EpisodeStats` per env.
    """
    e = venv.num_envs
    history: deque = deque(maxlen=policy.history)
    if policy.needs_obs:
        history.append(venv.observation())
    totals = {k: np.zeros(e) for k in ("reward", "quality_gain",
                                       "exec_cost", "trans_cost")}
    done, t = False, 0
    while not done:
        obs_hist = _obs_hist(history, policy.history) \
            if policy.needs_obs else None
        mac = vec_greedy_mac(venv) if mac_scheme == "greedy" \
            else vec_random_access(venv)
        draw = None if policy_draws is None else policy_draws[t]
        actions = policy.act_batch(venv, obs_hist, draw)
        res = venv.step(
            mac, actions.astype(int) - 1,
            arrival_draws=None if arrival_draws is None else arrival_draws[t],
            waypoint_redraw=None if waypoint_draws is None
            else waypoint_draws[t])
        done = res["done"]
        if policy.needs_obs:
            history.append(venv.observation(res["bs_load"]))
        totals["reward"] += res["rewards"]
        for k in ("quality_gain", "exec_cost", "trans_cost"):
            totals[k] += res[k]
        t += 1
    return [EpisodeStats(
        reward=float(totals["reward"][i]),
        quality_gain=float(totals["quality_gain"][i]),
        exec_cost=float(totals["exec_cost"][i]),
        trans_cost=float(totals["trans_cost"][i]),
        delivered_quality=float(venv.total_delivered[i]),
        num_delivered=int(venv.num_delivered[i]),
        collisions=int(venv.num_collisions[i]),
        losses=[]) for i in range(e)]


def evaluate_batched(policy: Policy, env_or_cfg, episodes: int, *,
                     num_envs: Optional[int] = None, seed0: int = 9_000,
                     mac_scheme: str = "greedy",
                     venv: Optional[VecEdgeSimulator] = None,
                     ) -> Dict[str, float]:
    """Evaluate ``policy`` over ``episodes`` on the vectorized engine.

    Episode seeds tile ``seed0 + round * E + e``, so episode ``ep`` runs
    under seed ``seed0 + ep`` regardless of ``num_envs`` — per-episode
    results are numerically identical to the legacy scalar loop (each
    stacked env replays the scalar stream bit-exactly).  The stacked envs
    share the static world of ``env_or_cfg`` (an :class:`EdgeSimulator` or
    a :class:`SimConfig`): evaluation measures on the world that was
    trained on.
    """
    cfg = env_or_cfg.cfg if isinstance(env_or_cfg, EdgeSimulator) \
        else env_or_cfg
    if venv is None:
        e = num_envs or min(max(episodes, 1), 8)
        venv = VecEdgeSimulator(cfg, e, seeds=np.full(e, cfg.seed))
    e = venv.num_envs
    stats: List[EpisodeStats] = []
    for rd in range(-(-episodes // e)):
        ep_seeds = seed0 + rd * e + np.arange(e)
        venv.reset(seeds=ep_seeds)
        pol_draws = _policy_draw_stack(policy, cfg, ep_seeds) \
            if policy.needs_draws else None
        stats.extend(rollout_round(policy, venv, mac_scheme=mac_scheme,
                                   policy_draws=pol_draws))
    return summarize(stats[:episodes])


def _policy_draw_stack(policy: Policy, cfg: SimConfig,
                       ep_seeds) -> np.ndarray:
    """(T, E, U, A) uniforms for a ``needs_draws`` policy, one stream per
    episode keyed by (policy seed, episode seed) — results are identical at
    any ``num_envs`` and reproducible across calls, matching the rest of
    the batched-eval determinism contract."""
    t, u, a = cfg.horizon, cfg.num_ues, cfg.num_bs + 1
    seed = getattr(policy, "seed", 0)
    return np.stack([np.random.default_rng((seed, int(s))).random((t, u, a))
                     for s in ep_seeds], axis=1)


# -- fused evaluation (device-resident jax engine) -----------------------------

def make_eval_draws(cfg: SimConfig, num_envs: int, key: jax.Array, *,
                    fdtype=jnp.float32, mac_random: bool = False,
                    policy_draws: bool = False) -> Dict[str, jax.Array]:
    """Whole-round randomness for the eval scan in a few batched draws
    (same chunk-hoisting rationale as ``train_fused``: per-frame threefry
    inside a scan is an XLA:CPU hot spot)."""
    t, e, u = cfg.horizon, num_envs, cfg.num_ues
    keys = jax.random.split(key, 5)
    draws = {
        "arrival": jax.random.uniform(keys[0], (t, e, u), fdtype),
        "waypoint": jax.random.uniform(keys[1], (t, e, u, 2), fdtype,
                                       0.0, cfg.side),
    }
    if mac_random:
        draws["mac_attempt"] = jax.random.uniform(keys[2], (t, e, u))
        draws["mac_channel"] = jax.random.uniform(keys[3], (t, e, u))
    if policy_draws:
        draws["policy"] = jax.random.uniform(
            keys[4], (t, e, u, cfg.num_bs + 1))
    return draws


# compiled eval rounds, reused across calls/worlds: the world is a traced
# argument of round_fn, so one compile serves every same-shape sweep point
# (cfg carries the shapes; policy.fused_key() pins the act_fn trace)
_EVAL_ROUNDS: Dict[Tuple, object] = {}


def evaluate_fused(policy: Policy, env: EdgeSimulator, episodes: int, *,
                   num_envs: Optional[int] = None, seed: int = 0,
                   mac_scheme: str = "greedy", mesh=None,
                   mesh_axis: str = "env") -> Dict[str, float]:
    """Evaluate ``policy`` through one jitted ``lax.scan`` per round on the
    jax-native engine (zero host round-trips inside an episode).

    The stacked envs share ``env``'s static world; episode randomness is
    jax-native (``jax.random`` streams keyed by ``seed``), so per-episode
    trajectories are not numpy-matched — cross-engine logic equivalence is
    pinned separately under injected draws (``tests/test_policy_eval.py``).

    ``mesh`` (e.g. ``repro.launch.mesh.make_env_mesh``) shards the round
    over the env dim.  ``state0`` and the draws are built host-side either
    way, so the sharded round consumes the exact same inputs as the
    single-device one and the results are identical (pinned in
    ``tests/test_mesh_sharding.py``); ``num_envs`` must divide evenly.
    """
    cfg = env.cfg
    e = num_envs or min(max(episodes, 1), 8)
    world = jax_env.world_from_sim(env, e)
    params, act_fn = policy.fused_spec(cfg)
    mesh_key = None if mesh is None else \
        (mesh_axis, tuple(mesh.devices.shape))
    cache_key = (cfg, e, mac_scheme, policy.history, policy.needs_obs,
                 policy.fused_key(), mesh_key)
    round_fn = _EVAL_ROUNDS.get(cache_key)
    if round_fn is None:
        round_fn = _EVAL_ROUNDS[cache_key] = jax_env.build_eval_round(
            cfg, act_fn, mac_scheme=mac_scheme, history=policy.history,
            needs_obs=policy.needs_obs, mesh=mesh, axis=mesh_axis)
    base_key = jax.random.PRNGKey(seed)
    stats: List[EpisodeStats] = []
    for rd in range(-(-episodes // e)):
        k_reset, k_draw = jax.random.split(jax.random.fold_in(base_key, rd))
        state0 = jax_env.reset_env(cfg, world, k_reset)
        draws = make_eval_draws(cfg, e, k_draw, fdtype=world.qbar.dtype,
                                mac_random=(mac_scheme == "random"),
                                policy_draws=policy.needs_draws)
        _, out = round_fn(params, world, state0, draws)
        out = {k: np.asarray(v) for k, v in out.items()}
        stats.extend(EpisodeStats(
            reward=float(out["reward"][i]),
            quality_gain=float(out["quality_gain"][i]),
            exec_cost=float(out["exec_cost"][i]),
            trans_cost=float(out["trans_cost"][i]),
            delivered_quality=float(out["delivered_quality"][i]),
            num_delivered=int(out["num_delivered"][i]),
            collisions=int(out["collisions"][i]),
            losses=[]) for i in range(e))
    return summarize(stats[:episodes])


def evaluate_policy(policy: Policy, env: EdgeSimulator, episodes: int, *,
                    engine: str = "vectorized",
                    num_envs: Optional[int] = None, seed0: int = 9_000,
                    seed: int = 0, mac_scheme: str = "greedy",
                    mesh=None, scalar_episode=None) -> Dict[str, float]:
    """The one engine dispatcher behind every controller's ``evaluate``.

    ``scalar_episode(seed) -> EpisodeStats`` is the controller's legacy
    reference loop, used when ``engine="scalar"``; "vectorized" and "fused"
    route through the shared batched rollouts above.
    """
    if engine == "scalar":
        assert scalar_episode is not None, \
            "engine='scalar' needs the controller's reference episode loop"
        return summarize([scalar_episode(seed0 + ep)
                          for ep in range(episodes)])
    if engine == "fused":
        return evaluate_fused(policy, env, episodes, num_envs=num_envs,
                              seed=seed, mac_scheme=mac_scheme, mesh=mesh)
    assert engine == "vectorized", f"unknown eval engine {engine!r}"
    return evaluate_batched(policy, env, episodes, seed0=seed0,
                            num_envs=num_envs, mac_scheme=mac_scheme)
