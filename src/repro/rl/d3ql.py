"""D3QL: Double + Dueling Deep Q-Learning (paper §III, eqs. 3–5, Table II).

Double-Q target (eq. 3): a' from the *online* net, evaluated by the *target*
net.  Dueling heads live in :mod:`repro.rl.networks` (eq. 4).  Updates follow
(5) with Adam at lr 8e-4, batch 32, gamma 0.9, target sync every 150 steps,
epsilon-greedy with multiplicative decay 0.99995 to floor 1e-5.  The update
step is jitted; action masks restrict per-UE argmax (used by the MP/FP
baselines and capacity masking).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.rl.networks import qnet_apply, qnet_init
from repro.rl.replay import ReplayMemory


def masked_argmax(q: np.ndarray, mask: Optional[np.ndarray]) -> np.ndarray:
    """Mask-then-argmax — the ONLY action-selection path out of the agent,
    applied to exploration draws and Q-values alike, so disallowed actions
    can never be emitted regardless of how ``q`` was produced (including the
    ``explore.all()`` short-circuit that skips the forward pass)."""
    if mask is not None:
        q = np.where(mask, q, -np.inf)
    return q.argmax(axis=-1).astype(np.int32)


def fused_act(params, obs_hist, *, epsilon, mask,
              num_ues: int, num_actions: int, key=None,
              explore_draw=None, q_rand=None) -> jnp.ndarray:
    """In-scan epsilon-greedy acting (pure jax; used by ``train_fused``).

    obs_hist: (E, H, obs_dim); mask: (E, U, A) bool or None; epsilon may be
    a traced scalar.  Per-env exploration (each env independently explores
    with prob epsilon) mirrors ``D3QLAgent.act_batch``, and the mask is
    applied after the explore/greedy merge — same invariant as
    :func:`masked_argmax` on the numpy path.

    Randomness comes either from ``key`` or from pre-drawn ``explore_draw``
    ((E,) uniforms) + ``q_rand`` ((E, U, A) uniforms) — the fused loop
    batch-draws whole scan chunks up front (per-frame threefry inside a
    scan is an XLA:CPU hot spot).
    """
    e = obs_hist.shape[0]
    q = qnet_apply(params, obs_hist, num_ues=num_ues, num_actions=num_actions)
    if explore_draw is None:
        k_explore, k_rand = jax.random.split(key)
        explore_draw = jax.random.uniform(k_explore, (e,))
        q_rand = jax.random.uniform(k_rand, q.shape, q.dtype)
    explore = explore_draw < epsilon
    q = jnp.where(explore[:, None, None], q_rand.astype(q.dtype), q)
    if mask is not None:
        q = jnp.where(mask, q, -jnp.inf)
    return jnp.argmax(q, axis=-1).astype(jnp.int32)


def greedy_act(params, obs_hist, *, mask, num_ues: int,
               num_actions: int) -> jnp.ndarray:
    """Eval-mode acting (pure jax; used inside batched/fused evaluation).

    obs_hist: (E, H, obs_dim); mask: (E, U, A) bool or None.  The greedy
    twin of :func:`fused_act` — no exploration branch, same mask-after-Q
    invariant as :func:`masked_argmax` on the numpy path.
    """
    q = qnet_apply(params, obs_hist, num_ues=num_ues, num_actions=num_actions)
    if mask is not None:
        q = jnp.where(mask, q, -jnp.inf)
    return jnp.argmax(q, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class D3QLConfig:
    obs_dim: int = 64
    num_ues: int = 15
    num_actions: int = 17            # {null} ∪ N
    history: int = 3                 # H (Table II)
    lstm_units: int = 128
    fc: tuple = (128, 64, 32)
    memory_capacity: int = 5_000
    batch_size: int = 32
    gamma: float = 0.9
    learning_rate: float = 8e-4
    epsilon_floor: float = 1e-5      # eps_tilde
    epsilon_decay: float = 0.99995   # eps'
    target_sync: int = 150
    grad_clip: float = 10.0
    seed: int = 0


class D3QLAgent:
    def __init__(self, cfg: D3QLConfig):
        self.cfg = cfg
        key = jax.random.PRNGKey(cfg.seed)
        self.params = qnet_init(key, cfg.obs_dim, cfg.num_ues, cfg.num_actions,
                                lstm_units=cfg.lstm_units, fc=cfg.fc)
        self.target_params = jax.tree_util.tree_map(jnp.copy, self.params)
        self._opt_init, self._opt_update = adamw(cfg.learning_rate, b1=0.9,
                                                 b2=0.999, weight_decay=0.0)
        self.opt_state = self._opt_init(self.params)
        self.memory = ReplayMemory(
            cfg.memory_capacity,
            obs_shape=(cfg.history, cfg.obs_dim),
            action_shape=(cfg.num_ues,),
            seed=cfg.seed)
        self.epsilon = 1.0
        self.steps = 0
        self.rng = np.random.default_rng(cfg.seed)
        self._update = self._build_update()
        qnet = functools.partial(qnet_apply, num_ues=cfg.num_ues,
                                 num_actions=cfg.num_actions)
        self._qvals = jax.jit(qnet)
        # E one-env forwards in one program (see act_rows)
        self._qvals_rows = jax.jit(lambda params, obs_hist: jax.lax.map(
            lambda h: qnet(params, h[None])[0], obs_hist))

    # -- acting --------------------------------------------------------------

    def act(self, obs_hist: np.ndarray, *, greedy: bool = False,
            mask: Optional[np.ndarray] = None) -> np.ndarray:
        """obs_hist: (H, obs_dim) -> per-UE actions (U,) int in [0, A).

        Action 0 is the null action; action n+1 places on BS n.
        ``mask``: (U, A) bool — False entries are disallowed.
        """
        mask_b = None if mask is None else mask[None]
        return self.act_batch(obs_hist[None], greedy=greedy, mask=mask_b)[0]

    def act_batch(self, obs_hist: np.ndarray, *, greedy: bool = False,
                  mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Batched acting: obs_hist (E, H, obs_dim) -> actions (E, U).

        One jitted forward serves all E envs; epsilon-greedy exploration is
        decided per env (each env independently explores with prob epsilon,
        mirroring the scalar per-call draw), and ``mask`` is (E, U, A).
        """
        cfg = self.cfg
        e = obs_hist.shape[0]
        explore = np.zeros(e, dtype=bool) if greedy \
            else self.rng.random(e) < self.epsilon
        q_rand = None
        if explore.any():
            q_rand = self.rng.random(
                (e, cfg.num_ues, cfg.num_actions)).astype(np.float32)
        if explore.all():
            q = q_rand                     # skip the forward entirely
        else:
            q = np.asarray(self._qvals(self.params, obs_hist))    # (E, U, A)
            if q_rand is not None:
                q = np.where(explore[:, None, None], q_rand, q)
        return masked_argmax(q, mask)

    def act_rows(self, obs_hist: np.ndarray,
                 mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Greedy acting for E envs in one call, each env's Q-values from
        a one-env forward: obs_hist (E, H, obs_dim) -> actions (E, U), row
        ``i`` equal to ``act(obs_hist[i], greedy=True, mask=mask[i])``.

        :meth:`act_batch` runs one E-row forward instead, which the
        compiler may lower differently from E one-row forwards (on a TPU a
        one-row matmul becomes an f32 multiply-reduce, a many-row one an
        MXU pass at default precision), so near-tied actions could flip.
        """
        q = np.asarray(self._qvals_rows(self.params, obs_hist))
        return masked_argmax(q, mask)

    def decay_epsilon(self) -> None:
        self.epsilon = max(self.cfg.epsilon_floor,
                           self.epsilon * self.cfg.epsilon_decay)

    # -- learning ------------------------------------------------------------

    def _build_update(self):
        cfg = self.cfg

        def loss_fn(params, target_params, batch):
            q = qnet_apply(params, batch["obs"], num_ues=cfg.num_ues,
                           num_actions=cfg.num_actions)          # (B, U, A)
            q_sel = jnp.take_along_axis(
                q, batch["actions"][..., None], axis=-1)[..., 0]  # (B, U)
            q_tot = q_sel.sum(axis=-1)                            # VDN sum

            # double-Q: argmax online, evaluate target (eq. 3)
            q_next_online = qnet_apply(params, batch["next_obs"],
                                       num_ues=cfg.num_ues,
                                       num_actions=cfg.num_actions)
            a_star = jnp.argmax(q_next_online, axis=-1)           # (B, U)
            q_next_target = qnet_apply(target_params, batch["next_obs"],
                                       num_ues=cfg.num_ues,
                                       num_actions=cfg.num_actions)
            q_next = jnp.take_along_axis(
                q_next_target, a_star[..., None], axis=-1)[..., 0].sum(axis=-1)
            y = batch["rewards"] + cfg.gamma * (1.0 - batch["dones"]) * \
                jax.lax.stop_gradient(q_next)
            td = y - q_tot
            return jnp.mean(td ** 2)

        def update(params, target_params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, target_params, batch)
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            updates, opt_state = self._opt_update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, loss, gnorm

        # the un-jitted pure update is reused inside train_fused's scan body
        # (jitting there would nest jits; the scan is compiled as a whole)
        self.update_fn = update

        # buffer donation: params/opt_state update in place on device (no
        # fresh allocation per train step).  Backends without donation
        # support (CPU) would warn every call, so gate on the backend.
        if jax.default_backend() in ("gpu", "tpu"):
            return jax.jit(update, donate_argnums=(0, 2))
        return jax.jit(update)

    def train_step(self) -> Optional[float]:
        cfg = self.cfg
        if len(self.memory) < cfg.batch_size:
            return None
        # numpy arrays transfer once inside the jitted call — no extra
        # host-side jnp.asarray staging pass
        batch = self.memory.sample(cfg.batch_size)
        self.params, self.opt_state, loss, _ = self._update(
            self.params, self.target_params, self.opt_state, batch)
        self.steps += 1
        if self.steps % cfg.target_sync == 0:
            self.target_params = jax.tree_util.tree_map(jnp.copy, self.params)
        return float(loss)

    def remember(self, obs, action, reward, next_obs, done) -> None:
        self.memory.push(obs, action, reward, next_obs, done)
