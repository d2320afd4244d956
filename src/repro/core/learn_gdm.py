"""LEARN-GDM (Algorithm 1) and its D3QL-based variants MP / FP.

One controller class drives all three methods; the difference is purely the
*action mask* applied to the per-UE argmax:

  * LEARN-GDM  — unrestricted: any node each block (distributed chains) and
                 the null action any time (adaptive chain length).
  * MP         — monolithic: once a chain starts on node n, the mask allows
                 only {null, n} (single node per inference, variable length).
  * FP         — fixed chain: the null action is masked out while
                 0 < k < B (no early exit; nodes may still vary).

The controller owns the greedy MAC, the observation history (eq. 7), reward
bookkeeping (eq. 8 — computed by the env), the replay/train plumbing
(Algorithm 1 steps 23–28), and optional trace recording for the C1–C9
checkers.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constraints import TraceRecorder
from repro.core.mac import (greedy_mac, random_access, vec_greedy_mac,
                            vec_random_access)
from repro.rl.d3ql import D3QLAgent, D3QLConfig, fused_act
from repro.rl.replay import DeviceReplay
from repro.sim import jax_env
from repro.sim.env import IDLE, EdgeSimulator, SimConfig
from repro.sim.vec_env import VecEdgeSimulator


def variant_action_mask(env: EdgeSimulator, variant: str) -> np.ndarray:
    """(U, A) bool mask for one scalar env — the variant semantics in one
    place (see module docstring); shared by the controller and the scalar
    policy path."""
    cfg = env.cfg
    u, a = cfg.num_ues, cfg.num_bs + 1
    mask = np.ones((u, a), dtype=bool)
    if variant == "mp":
        started = env.blocks_done > 0
        for i in np.where(started)[0]:
            mask[i, :] = False
            mask[i, 0] = True                       # null (stop & deliver)
            mask[i, env.cur_node[i] + 1] = True     # stay on the same node
    elif variant == "fp":
        mid_chain = (env.blocks_done > 0) & (env.blocks_done < cfg.max_blocks)
        mask[mid_chain, 0] = False                  # no early exit
    return mask


def variant_action_mask_vec(venv: VecEdgeSimulator, variant: str) -> np.ndarray:
    """Batched action masks, (E, U, A) — same semantics as
    :func:`variant_action_mask` per env, no per-UE loops."""
    cfg = venv.cfg
    e, u, a = venv.num_envs, cfg.num_ues, cfg.num_bs + 1
    mask = np.ones((e, u, a), dtype=bool)
    if variant == "mp":
        started = venv.blocks_done.ravel() > 0
        rows = mask.reshape(e * u, a)
        rows[started] = False
        rows[started, 0] = True                     # null (stop & deliver)
        rows[started, venv.cur_node.ravel()[started] + 1] = True
    elif variant == "fp":
        mid_chain = (venv.blocks_done > 0) & \
            (venv.blocks_done < cfg.max_blocks)
        mask[..., 0][mid_chain] = False             # no early exit
    # duck-typed fault hook: a view carrying (E, N) node liveness (the
    # serving bridge's _SlotView under injected failures) masks placements
    # onto dead nodes for every variant; sim envs don't have the attribute
    up = getattr(venv, "node_up", None)
    if up is not None:
        mask[..., 1:] &= np.asarray(up, dtype=bool)[:, None, :]
    return mask


def obs_history_window(history, h: int, pad=None) -> np.ndarray:
    """Eq. (7) observation window: the last ``h`` frames stacked along a new
    axis -2, padded by repeating the oldest frame (or ``pad`` when the
    history is empty).  Works for scalar ((obs,) frames → (H, obs)) and
    batched ((E, obs) frames → (E, H, obs)) histories alike — the ONE
    windowing rule shared by the training loops and the evaluation rollouts
    (the batched-eval-equals-scalar-eval pin depends on it)."""
    pads = [history[0]] * (h - len(history)) if history else [pad] * h
    items = list(pads) + list(history)
    return np.stack(items[-h:], axis=-2)


@dataclasses.dataclass
class EpisodeStats:
    reward: float
    quality_gain: float
    exec_cost: float
    trans_cost: float
    delivered_quality: float
    num_delivered: int
    collisions: int
    losses: List[float]


class LearnGDMController:
    """Algorithm 1 driver.  ``variant`` in {"learn-gdm", "mp", "fp"}."""

    def __init__(self, env: EdgeSimulator, *, variant: str = "learn-gdm",
                 agent: Optional[D3QLAgent] = None, seed: int = 0,
                 mac_scheme: str = "greedy"):
        assert variant in ("learn-gdm", "mp", "fp")
        self.env = env
        self.variant = variant
        self.mac_scheme = mac_scheme
        cfg = env.cfg
        self.agent = agent or D3QLAgent(D3QLConfig(
            obs_dim=env.obs_dim,
            num_ues=cfg.num_ues,
            num_actions=cfg.num_bs + 1,
            seed=seed))
        self.history: deque = deque(maxlen=self.agent.cfg.history)

    # -- action masking ------------------------------------------------------

    def action_mask(self) -> np.ndarray:
        return variant_action_mask(self.env, self.variant)

    def action_mask_vec(self, venv: VecEdgeSimulator) -> np.ndarray:
        return variant_action_mask_vec(venv, self.variant)

    # -- episode loops ---------------------------------------------------------

    def _obs_hist(self) -> np.ndarray:
        return obs_history_window(self.history, self.agent.cfg.history,
                                  pad=np.zeros(self.env.obs_dim, np.float32))

    def run_episode(self, *, train: bool = True, seed: Optional[int] = None,
                    trace: Optional[TraceRecorder] = None) -> EpisodeStats:
        env, agent = self.env, self.agent
        env.reset(seed=seed)
        self.history.clear()
        self.history.append(env.observation())
        total = dict(reward=0.0, quality_gain=0.0, exec_cost=0.0, trans_cost=0.0)
        losses: List[float] = []
        done = False
        while not done:
            obs_hist = self._obs_hist()
            mac = greedy_mac(env) if self.mac_scheme == "greedy" \
                else random_access(env)
            blocks_before = env.blocks_done.copy()
            startable = env.chain_state != IDLE
            poa_before = env.poa.copy()
            actions = agent.act(obs_hist, greedy=not train,
                                mask=self.action_mask())
            placement = actions.astype(int) - 1          # 0 -> null (-1)
            res = env.step(mac, placement)
            done = res["done"]
            self.history.append(env.observation(res["bs_load"]))
            if train:
                agent.remember(obs_hist, actions, res["reward"],
                               self._obs_hist(), done)
                loss = agent.train_step()
                if loss is not None:
                    losses.append(loss)
                agent.decay_epsilon()
            if trace is not None:
                executed = env.blocks_done > blocks_before
                trace.add(frame=env.frame - 1, poa=poa_before, mac=mac,
                          uploaded=res["uploaded"], placement=placement,
                          executed=executed,
                          exec_node=np.where(executed, env.cur_node, -1),
                          blocks_done=env.blocks_done.copy(),
                          bs_load=res["bs_load"],
                          chain_startable=startable)
            for k in total:
                total[k] += res[k] if k != "reward" else res["reward"]
        return EpisodeStats(
            reward=total["reward"], quality_gain=total["quality_gain"],
            exec_cost=total["exec_cost"], trans_cost=total["trans_cost"],
            delivered_quality=env.total_delivered,
            num_delivered=env.num_delivered,
            collisions=env.num_collisions, losses=losses)

    def train(self, episodes: int, *, log_every: int = 0) -> Dict[str, list]:
        hist = {"reward": [], "loss": [], "delivered": []}
        for ep in range(episodes):
            stats = self.run_episode(train=True, seed=1_000 + ep)
            hist["reward"].append(stats.reward)
            hist["loss"].append(float(np.mean(stats.losses)) if stats.losses else np.nan)
            hist["delivered"].append(stats.delivered_quality)
            if log_every and (ep + 1) % log_every == 0:
                recent = np.mean(hist["reward"][-log_every:])
                print(f"  ep {ep + 1:5d}  reward(avg {log_every})={recent:8.3f}  "
                      f"eps={self.agent.epsilon:.3f}")
        return hist

    # -- vectorized training ---------------------------------------------------

    def train_frames(self, episodes: int, *, num_envs: int = 1) -> int:
        """Frames (= epsilon-decay / train steps) a :meth:`train` (E=1),
        :meth:`train_vectorized` or :meth:`train_fused` run will execute —
        callers calibrating the epsilon schedule should use this instead of
        re-deriving round math."""
        rounds = -(-episodes // max(num_envs, 1)) if num_envs > 1 else episodes
        return rounds * self.env.cfg.horizon

    def calibrate_epsilon(self, episodes: int, *, num_envs: int = 1,
                          final: float = 1e-2) -> float:
        """Set the agent's multiplicative epsilon schedule so exploration
        anneals to ``final`` over exactly the frames a run of ``episodes``
        at ``num_envs`` will execute (:meth:`train_frames`) — the one
        sanctioned way to scale the paper's 0.99995/200k-frame schedule to
        a shorter run (callers must not re-derive the round math)."""
        frames = self.train_frames(episodes, num_envs=num_envs)
        self.agent.cfg.epsilon_decay = float(
            np.exp(np.log(final) / max(frames, 1)))
        return self.agent.cfg.epsilon_decay

    def _obs_hist_vec(self, history: deque, num_envs: int) -> np.ndarray:
        return obs_history_window(                       # (E, H, obs_dim)
            history, self.agent.cfg.history,
            pad=np.zeros((num_envs, self.env.obs_dim), np.float32))

    def train_vectorized(self, episodes: int, *, num_envs: int = 8,
                         log_every: int = 0, seed0: int = 1_000,
                         venv: Optional[VecEdgeSimulator] = None) -> Dict[str, list]:
        """Algorithm 1 over E stacked envs: one batched act, one env step and
        one (amortized) train step per frame collect E transitions.

        Episode seeds tile ``seed0 + round * E + e`` so E=1 matches
        :meth:`train`'s per-episode seeding.  All stacked envs share
        ``self.env``'s static world (same ``cfg.seed`` draw) — like
        :meth:`train`, episodes differ only in mobility/request streams, and
        :meth:`evaluate` measures on the world that was trained on.  Returns
        the same history dict as :meth:`train` with one entry per episode
        (``rounds * num_envs``, trimmed to ``episodes``).
        """
        agent = self.agent
        venv = venv or VecEdgeSimulator(
            self.env.cfg, num_envs,
            seeds=np.full(num_envs, self.env.cfg.seed))
        num_envs = venv.num_envs
        rounds = -(-episodes // num_envs)
        hist = {"reward": [], "loss": [], "delivered": []}
        for rd in range(rounds):
            venv.reset(seeds=seed0 + rd * num_envs + np.arange(num_envs))
            history: deque = deque(maxlen=agent.cfg.history)
            history.append(venv.observation())
            ep_reward = np.zeros(num_envs)
            losses: List[float] = []
            done = False
            while not done:
                obs_hist = self._obs_hist_vec(history, num_envs)
                mac = vec_greedy_mac(venv) if self.mac_scheme == "greedy" \
                    else vec_random_access(venv)
                actions = agent.act_batch(obs_hist, greedy=False,
                                          mask=self.action_mask_vec(venv))
                res = venv.step(mac, actions.astype(int) - 1)
                done = res["done"]
                history.append(venv.observation(res["bs_load"]))
                agent.memory.push_batch(
                    obs_hist, actions, res["rewards"],
                    self._obs_hist_vec(history, num_envs),
                    np.full(num_envs, done))
                loss = agent.train_step()
                if loss is not None:
                    losses.append(loss)
                agent.decay_epsilon()
                ep_reward += res["rewards"]
            mean_loss = float(np.mean(losses)) if losses else np.nan
            hist["reward"].extend(ep_reward.tolist())
            hist["loss"].extend([mean_loss] * num_envs)
            hist["delivered"].extend(venv.total_delivered.tolist())
            if log_every and (rd + 1) % log_every == 0:
                recent = np.mean(hist["reward"][-num_envs * log_every:])
                print(f"  round {rd + 1:5d} ({len(hist['reward'])} eps)  "
                      f"reward(avg)={recent:8.3f}  eps={agent.epsilon:.3f}")
        return {k: v[:episodes] for k, v in hist.items()}

    # -- fused (device-resident) training --------------------------------------

    def _build_fused_round(self, world: jax_env.JaxWorld, num_envs: int,
                           replay: DeviceReplay, mesh=None,
                           axis: str = "env"):
        """Compile one training *round* — jax reset + a ``lax.scan`` over the
        whole episode (act → env step → device replay push → D3QL update per
        frame) — as a single jitted function.  The agent/replay carry crosses
        rounds on device; the only host sync per round is the tiny stats
        pull in :meth:`train_fused`.

        With ``mesh`` (1-D, axis ``axis``), the whole round body runs under
        ``shard_map`` with the env dim sharded.  The design keeps sharded ==
        unsharded EXACT (not just statistical):

        * all round randomness — including the reset draws — is hoisted into
          global (T, E, ...) / (E, ...) stacks outside the shard body, so
          each shard consumes slices of the one stream;
        * env math is strictly per-env (no cross-env arithmetic), so shards
          evolve their env slices independently;
        * each frame's transitions are ``all_gather``-ed back to the global
          env order before ``replay.push``, and the D3QL update runs
          REPLICATED on every shard from that identical replay — the same
          full-batch gradient everywhere, no psum reduction-order drift.
        """
        agent, cfg = self.agent, self.env.cfg
        acfg = agent.cfg
        variant, mac_scheme = self.variant, self.mac_scheme
        h, horizon = acfg.history, cfg.horizon
        update_fn = agent.update_fn
        num_shards = 1 if mesh is None else mesh.shape[axis]
        assert num_envs % num_shards == 0, (num_envs, num_shards)
        if num_shards > 1:
            def to_global(x):
                return jax.lax.all_gather(x, axis, axis=0, tiled=True)
        else:
            def to_global(x):
                return x

        # ``world`` is a parameter (not the closure) so the shard_map body
        # sees the per-shard (E/shards, ...) slice, not the global stack
        def frame_fn(world, carry, draws):
            (params, target, opt_state, rstate, state, obs_hist,
             epsilon, steps) = carry

            if mac_scheme == "greedy":
                mac = jax_env.greedy_mac(cfg, world, state)
            else:
                mac = jax_env.random_access(
                    cfg, state, attempt_draws=draws["mac_attempt"],
                    channel_draws=draws["mac_channel"])
            mask = jax_env.action_mask(cfg, state, variant)
            actions = fused_act(params, obs_hist, epsilon=epsilon,
                                mask=mask, num_ues=acfg.num_ues,
                                num_actions=acfg.num_actions,
                                explore_draw=draws["explore"],
                                q_rand=draws["q_rand"])
            state, info = jax_env.env_step(
                cfg, world, state, mac, actions - 1,
                arrival_draws=draws["arrival"],
                waypoint_draws=draws["waypoint"])
            next_obs = jax_env.observe(cfg, world, state, info["bs_load"])
            next_hist = jnp.concatenate(
                [obs_hist[:, 1:], next_obs[:, None]], axis=1)
            done = (state.frame >= horizon).astype(jnp.float32)
            rstate = replay.push(rstate, to_global(obs_hist),
                                 to_global(actions),
                                 to_global(info["rewards"]),
                                 to_global(next_hist),
                                 jnp.full((num_envs,), done))

            can_train = rstate.size >= acfg.batch_size

            def do_train(args):
                p, t, o = args
                batch = replay.sample_from_uniforms(rstate, draws["sample"])
                p, o, loss, _ = update_fn(p, t, o, batch)
                return p, o, loss

            def skip_train(args):
                p, _, o = args
                return p, o, jnp.asarray(jnp.nan, jnp.float32)

            params, opt_state, loss = jax.lax.cond(
                can_train, do_train, skip_train, (params, target, opt_state))
            steps = steps + can_train.astype(jnp.int32)
            sync = can_train & (steps % acfg.target_sync == 0)
            target = jax.tree_util.tree_map(
                lambda p, t: jnp.where(sync, p, t), params, target)
            epsilon = jnp.maximum(acfg.epsilon_floor,
                                  epsilon * acfg.epsilon_decay)
            return ((params, target, opt_state, rstate, state, next_hist,
                     epsilon, steps), (info["rewards"], loss))

        def scan_round(world, params, target, opt_state, rstate, epsilon,
                       steps, state_key, reset_draws, draws):
            """Reset + the full-episode scan — the (shardable) round body."""
            state = jax_env.reset_env(cfg, world, state_key,
                                      pos_draws=reset_draws["pos"],
                                      dest_draws=reset_draws["dest"],
                                      req_draws=reset_draws["req"])
            obs0 = jax_env.observe(cfg, world, state)
            obs_hist = jnp.repeat(obs0[:, None], h, axis=1)   # (E, H, obs)
            (params, target, opt_state, rstate, state, _, epsilon, steps), \
                (rewards, losses) = jax.lax.scan(
                    functools.partial(frame_fn, world),
                    (params, target, opt_state, rstate, state, obs_hist,
                     epsilon, steps),
                    draws)
            return ((params, target, opt_state, rstate, epsilon, steps),
                    (rewards.sum(axis=0), losses, state.total_delivered))

        if mesh is not None:
            # carry/replay/update replicated; world, reset draws (E, ...)
            # and frame draws (T, E, ...) sharded on the env dim, except the
            # replay-sample uniforms every shard must consume identically.
            # check_vma=False: the specs themselves guarantee that the
            # agent/replay carry stays replicated through lax.scan+cond.
            from jax import P, shard_map
            from repro.distributed.sharding import draw_specs
            frame_draw_keys = ("explore", "q_rand", "arrival", "waypoint",
                               "sample", "mac_attempt", "mac_channel")
            scan_sharded = shard_map(
                scan_round, mesh=mesh,
                in_specs=(jax_env.world_specs(axis), P(), P(), P(), P(),
                          P(), P(), P(),
                          draw_specs(dict.fromkeys(("pos", "dest", "req")),
                                     axis, env_dim=0),
                          draw_specs(dict.fromkeys(frame_draw_keys), axis,
                                     replicated=("sample",))),
                out_specs=((P(), P(), P(), P(), P(), P()),
                           (P(axis), P(), P(axis))),
                check_vma=False)
        else:
            scan_sharded = scan_round

        def round_fn(carry, round_key):
            params, target, opt_state, rstate, epsilon, steps = carry
            keys = jax.random.split(round_key, 11)
            # whole-round randomness in a few batched draws (per-frame
            # threefry inside the scan is an XLA:CPU hot spot).  Reset draws
            # are hoisted too (keys 8-10) so the sharded and unsharded
            # rounds consume ONE identical stream — exact equivalence.
            t, e, u = horizon, num_envs, acfg.num_ues
            fdtype = world.qbar.dtype
            reset_draws = {
                "pos": jax.random.uniform(keys[8], (e, u, 2), fdtype,
                                          0.0, cfg.side),
                "dest": jax.random.uniform(keys[9], (e, u, 2), fdtype,
                                           0.0, cfg.side),
                "req": jax.random.uniform(keys[10], (e, u), fdtype),
            }
            draws = {
                "explore": jax.random.uniform(keys[1], (t, e)),
                "q_rand": jax.random.uniform(
                    keys[2], (t, e, u, acfg.num_actions)),
                "arrival": jax.random.uniform(keys[3], (t, e, u)),
                "waypoint": jax.random.uniform(keys[4], (t, e, u, 2),
                                               jnp.float32, 0.0, cfg.side),
                "sample": jax.random.uniform(keys[5],
                                             (t, acfg.batch_size)),
                "mac_attempt": jax.random.uniform(keys[6], (t, e, u)),
                "mac_channel": jax.random.uniform(keys[7], (t, e, u)),
            }
            return scan_sharded(world, params, target, opt_state, rstate,
                                epsilon, steps, keys[0], reset_draws, draws)

        if jax.default_backend() in ("gpu", "tpu"):
            return jax.jit(round_fn, donate_argnums=(0,))
        return jax.jit(round_fn)

    def train_fused(self, episodes: int, *, num_envs: int = 8,
                    log_every: int = 0, seed: int = 0,
                    mesh=None, mesh_axis: str = "env") -> Dict[str, list]:
        """Algorithm 1 as ONE device program per round: jax reset + a
        jit-compiled ``lax.scan`` chunk running act (epsilon-greedy in-scan)
        → ``jax_env.env_step`` → device-resident replay push → D3QL update
        every frame, with the agent/replay carry donated across rounds.

        Zero host↔device round-trips inside an episode; the host loop only
        pulls per-round stats (E floats).  Like :meth:`train_vectorized`,
        all stacked envs share ``self.env``'s static world; episode
        randomness is jax-native (``jax.random`` streams), so trajectories
        are not numpy-matched — cross-engine logic equivalence is pinned
        separately by ``tests/test_jax_env.py``.  The device replay is
        internal to this method (``agent.memory`` is not populated); agent
        params / target / optimizer state / epsilon / steps are written back
        so :meth:`evaluate` and further training see the fused progress.
        Returns the same history dict as :meth:`train` (one entry per
        episode, trimmed to ``episodes``).

        ``mesh`` (e.g. ``repro.launch.mesh.make_env_mesh``) shards the round
        over the env dim — EXACTLY equivalent to the single-device path
        under the same seed (see :meth:`_build_fused_round`); ``num_envs``
        must be divisible by the mesh size.
        """
        agent, cfg = self.agent, self.env.cfg
        acfg = agent.cfg
        # one compiled round per (num_envs, traced-in agent config), reused
        # across train_fused calls (rebuilding the closure would recompile
        # the whole scan every call).  The config fields are part of the key
        # because they are baked into the trace — mutating e.g.
        # agent.cfg.epsilon_decay between calls must not hit a stale round.
        mesh_key = None if mesh is None else \
            (mesh_axis, tuple(mesh.devices.shape))
        cache_key = (num_envs, acfg.epsilon_decay, acfg.epsilon_floor,
                     acfg.target_sync, acfg.batch_size, acfg.memory_capacity,
                     acfg.history, acfg.num_ues, acfg.num_actions, mesh_key)
        cache = getattr(self, "_fused_cache", None)
        if cache is None:
            cache = self._fused_cache = {}
        if cache_key not in cache:
            world = jax_env.world_from_sim(self.env, num_envs)
            replay = DeviceReplay(acfg.memory_capacity,
                                  obs_shape=(acfg.history, self.env.obs_dim),
                                  action_shape=(acfg.num_ues,))
            cache[cache_key] = (
                replay, self._build_fused_round(world, num_envs, replay,
                                                mesh, mesh_axis))
        replay, round_fn = cache[cache_key]

        carry = (agent.params, agent.target_params, agent.opt_state,
                 replay.init(), jnp.asarray(agent.epsilon, jnp.float32),
                 jnp.asarray(agent.steps, jnp.int32))
        base_key = jax.random.PRNGKey(seed)
        rounds = -(-episodes // num_envs)
        hist = {"reward": [], "loss": [], "delivered": []}
        for rd in range(rounds):
            carry, (ep_reward, losses, delivered) = round_fn(
                carry, jax.random.fold_in(base_key, rd))
            losses = np.asarray(losses)
            valid = losses[~np.isnan(losses)]
            mean_loss = float(valid.mean()) if len(valid) else np.nan
            hist["reward"].extend(np.asarray(ep_reward).tolist())
            hist["loss"].extend([mean_loss] * num_envs)
            hist["delivered"].extend(np.asarray(delivered).tolist())
            if log_every and (rd + 1) % log_every == 0:
                recent = np.mean(hist["reward"][-num_envs * log_every:])
                print(f"  round {rd + 1:5d} ({len(hist['reward'])} eps)  "
                      f"reward(avg)={recent:8.3f}  "
                      f"eps={float(carry[4]):.3f}")
        (agent.params, agent.target_params, agent.opt_state, _,
         epsilon, steps) = carry
        agent.epsilon = float(epsilon)
        agent.steps = int(steps)
        return {k: v[:episodes] for k, v in hist.items()}

    def evaluate(self, episodes: int, *, seed0: int = 9_000,
                 engine: str = "vectorized",
                 num_envs: Optional[int] = None,
                 seed: int = 0, mesh=None) -> Dict[str, float]:
        """Greedy-policy evaluation through the unified policy/engine seam.

        engine: "vectorized" (default — batched numpy rollout; per-episode
        results are numerically identical to the legacy scalar loop for any
        ``num_envs``, since each stacked env replays the scalar stream),
        "fused" (jitted eval scan on the jax engine — jax-native episode
        randomness, seeded by ``seed``) or "scalar" (the original
        ``run_episode`` loop, kept as the reference implementation).
        """
        # policy imports learn_gdm for EpisodeStats — import at call time
        from repro.core.policy import LearnedPolicy, evaluate_policy
        return evaluate_policy(
            LearnedPolicy(self.agent, self.variant), self.env, episodes,
            engine=engine, num_envs=num_envs, seed0=seed0, seed=seed,
            mac_scheme=self.mac_scheme, mesh=mesh,
            scalar_episode=lambda s: self.run_episode(train=False, seed=s))


def summarize(stats: List[EpisodeStats]) -> Dict[str, float]:
    return {
        "reward": float(np.mean([s.reward for s in stats])),
        "quality_gain": float(np.mean([s.quality_gain for s in stats])),
        "delivered_quality": float(np.mean([s.delivered_quality for s in stats])),
        "num_delivered": float(np.mean([s.num_delivered for s in stats])),
        "exec_cost": float(np.mean([s.exec_cost for s in stats])),
        "trans_cost": float(np.mean([s.trans_cost for s in stats])),
        "collisions": float(np.mean([s.collisions for s in stats])),
    }
