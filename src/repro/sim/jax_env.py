"""Device-resident functional port of the vectorized edge simulator.

This is the jax-native twin of :class:`repro.sim.vec_env.VecEdgeSimulator`:
the whole frame — MAC collision resolution (C4/C5), priority-ordered
placement under per-BS capacity (C1–C3), delivery (C9) and the eq. (8)
reward — is pure ``jax.numpy`` over an :class:`EnvState` pytree of
``(E, U)`` / ``(E, N)`` arrays, so a ``lax.scan`` over :func:`env_step`
compiles to one XLA program with zero host round-trips per frame
(see ``LearnGDMController.train_fused``).

Randomness is threaded ``jax.random`` keys (``EnvState.key``) instead of
per-env numpy generators; for the logic-equivalence harness both
:func:`env_step` and the numpy engine accept *injected* per-UE draws
(``arrival_draws``, ``waypoint_draws``) so the two engines can be driven
with identical randomness and compared frame by frame
(``tests/test_jax_env.py``).  The numpy ``VecEdgeSimulator`` remains the
reference implementation; tie-breaking in the priority order is stable
(by UE index) in both engines so ranks — and therefore capacity grants —
agree exactly.

All functions take ``cfg`` (a hashable frozen :class:`SimConfig`) first so
callers jit with ``functools.partial(fn, cfg)``; ``world`` is a pytree
argument and array shapes carry E/U/N statically.

Performance note (XLA:CPU): the numpy engine's lexsort/segment formulation
maps to flat sorts and scatters, which XLA lowers to serial loops — inside a
``lax.scan`` they dominated the frame.  Because U is small (Table II: 15),
every segment quantity here is instead computed as dense O(E·U²) pairwise
comparisons (``rank_i = #{j: pr_j > pr_i} + #{j < i: pr_j = pr_i}``,
``pos_in_group_i = #{j in group: rank_j < rank_i}``), which are
*mathematically identical* to the stable-sort formulation and vectorize
cleanly.  Table lookups use one-hot sums (exact: one value plus IEEE zeros)
instead of gathers where XLA:CPU gathers were hot.  :func:`segment_positions`
is kept as the reference sort-based primitive and pinned against the numpy
one in tests.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import P, shard_map
from repro.sim.env import IDLE, PENDING, SimConfig


class JaxWorld(NamedTuple):
    """Static world (Table II draws), stacked over E envs."""
    w_hat: jax.Array        # (E, N) int32 — per-BS capacity
    eps: jax.Array          # (E, N) — per-BS inference cost
    qbar: jax.Array         # (E, U) — per-UE quality threshold
    service_of: jax.Array   # (E, U) int32
    omega: jax.Array        # (E, S, B+1) — quality curves
    omega_ue: jax.Array     # (E, U, B+1) — omega rows pre-gathered per UE
    y_hat: jax.Array        # (N, N) — inter-node transmission cost


class EnvState(NamedTuple):
    """Per-frame dynamic state; a pytree carried through ``lax.scan``."""
    pos: jax.Array          # (E, U, 2) mobility position (m)
    dest: jax.Array         # (E, U, 2) mobility waypoint
    pause_left: jax.Array   # (E, U) RWP pause countdown
    poa: jax.Array          # (E, U) int32 — current service area / BS
    prev_poa: jax.Array     # (E, U) int32
    blocks_done: jax.Array  # (E, U) int32 — k_i
    chain_state: jax.Array  # (E, U) int32 — IDLE / PENDING / 1 running
    cur_node: jax.Array     # (E, U) int32 — last execution BS or -1
    has_request: jax.Array  # (E, U) bool
    uploaded: jax.Array     # (E, U) bool — m_i^{t-1}
    delivered_quality: jax.Array  # (E, U)
    quality_now: jax.Array  # (E, U)
    total_delivered: jax.Array    # (E,)
    num_delivered: jax.Array      # (E,) int32
    num_collisions: jax.Array     # (E,) int32
    frame: jax.Array        # () int32 — shared episode clock
    key: jax.Array          # jax.random key, advanced by env_step


# -- world / state construction ----------------------------------------------

def world_from_sim(sim, num_envs: Optional[int] = None) -> JaxWorld:
    """Lift a numpy simulator's static world onto the device.

    ``sim`` is either a scalar ``EdgeSimulator`` (its world is tiled
    ``num_envs`` times — the ``train_vectorized`` shared-world regime) or a
    ``VecEdgeSimulator`` (its per-env stack is taken as-is).
    """
    stacked = sim.w_hat.ndim == 2
    if not stacked:
        assert num_envs is not None, "num_envs required for a scalar world"

    def lift(x, dtype=None):
        a = np.asarray(x)
        if not stacked:
            a = np.broadcast_to(a, (num_envs, *a.shape))
        # jnp.array (copy) rather than jnp.asarray: the latter may zero-copy
        # alias the live numpy buffers on CPU (alignment-dependent), and the
        # source sim mutates its world arrays in place in some tests
        return jnp.array(a, dtype=dtype)

    omega = np.asarray(sim.omega)
    service_of = np.asarray(sim.service_of)
    if stacked:
        omega_ue = omega[np.arange(omega.shape[0])[:, None], service_of]
    else:
        omega_ue = omega[service_of]
    return JaxWorld(
        w_hat=lift(sim.w_hat, jnp.int32),
        eps=lift(sim.eps),
        qbar=lift(sim.qbar),
        service_of=lift(sim.service_of, jnp.int32),
        omega=lift(sim.omega),
        omega_ue=lift(omega_ue),
        y_hat=jnp.asarray(sim.y_hat),
    )


def state_from_numpy(venv, key: Optional[jax.Array] = None) -> EnvState:
    """Import a ``VecEdgeSimulator``'s live state (equivalence harness).

    Uses ``jnp.array`` (a copy) instead of ``jnp.asarray``: on CPU the
    latter may zero-copy alias the venv's live numpy buffers
    (alignment-dependent, so nondeterministic per process), and the venv
    mutates several of them in place (``num_collisions``, ``has_request``,
    mobility ``pos``/``dest``/``pause_left``, ...) when it keeps stepping —
    the imported state must be an immutable snapshot.
    """
    m = venv.mobility
    return EnvState(
        pos=jnp.array(m.pos), dest=jnp.array(m.dest),
        pause_left=jnp.array(m.pause_left),
        poa=jnp.array(venv.poa, jnp.int32),
        prev_poa=jnp.array(venv.prev_poa, jnp.int32),
        blocks_done=jnp.array(venv.blocks_done, jnp.int32),
        chain_state=jnp.array(venv.chain_state, jnp.int32),
        cur_node=jnp.array(venv.cur_node, jnp.int32),
        has_request=jnp.array(venv.has_request, bool),
        uploaded=jnp.array(venv.uploaded, bool),
        delivered_quality=jnp.array(venv.delivered_quality),
        quality_now=jnp.array(venv.quality_now),
        total_delivered=jnp.array(venv.total_delivered),
        num_delivered=jnp.array(venv.num_delivered, jnp.int32),
        num_collisions=jnp.array(venv.num_collisions, jnp.int32),
        frame=jnp.asarray(venv.frame, jnp.int32),
        key=key if key is not None else jax.random.PRNGKey(0),
    )


def reset_env(cfg: SimConfig, world: JaxWorld, key: jax.Array, *,
              pos_draws: Optional[jax.Array] = None,
              dest_draws: Optional[jax.Array] = None,
              req_draws: Optional[jax.Array] = None) -> EnvState:
    """Fresh episode state from a jax key (fused-training reset).

    Draw *structure* matches the numpy reset (uniform positions/waypoints,
    request probability 0.9) but streams are jax-native, not numpy-matched —
    cross-engine equivalence starts from :func:`state_from_numpy` instead.

    ``pos_draws`` / ``dest_draws`` ((E, U, 2) in [0, side)) and
    ``req_draws`` ((E, U) uniforms in [0, 1)) inject the reset randomness —
    the sharded fused round hoists them so every shard slices one global
    stream; the key is still split (and stored) identically either way.
    """
    e, u = world.qbar.shape
    fdtype = world.qbar.dtype
    k_pos, k_dest, k_req, key = jax.random.split(key, 4)
    pos = pos_draws if pos_draws is not None else \
        jax.random.uniform(k_pos, (e, u, 2), fdtype, 0.0, cfg.side)
    dest = dest_draws if dest_draws is not None else \
        jax.random.uniform(k_dest, (e, u, 2), fdtype, 0.0, cfg.side)
    poa = area_of(cfg, pos)
    zf = jnp.zeros((e, u), fdtype)
    zi = jnp.zeros((e, u), jnp.int32)
    return EnvState(
        pos=pos, dest=dest, pause_left=zf,
        poa=poa, prev_poa=poa,
        blocks_done=zi, chain_state=jnp.full((e, u), IDLE, jnp.int32),
        cur_node=jnp.full((e, u), -1, jnp.int32),
        has_request=(req_draws if req_draws is not None else
                     jax.random.uniform(k_req, (e, u), fdtype)) < 0.9,
        uploaded=jnp.zeros((e, u), bool),
        delivered_quality=zf, quality_now=zf,
        total_delivered=jnp.zeros((e,), fdtype),
        num_delivered=jnp.zeros((e,), jnp.int32),
        num_collisions=jnp.zeros((e,), jnp.int32),
        frame=jnp.asarray(0, jnp.int32), key=key,
    )


# -- mesh partition specs -----------------------------------------------------

def state_specs(axis: str) -> EnvState:
    """:class:`EnvState` pytree of PartitionSpecs: every (E, ...) field is
    sharded on its leading env dim; the shared episode clock and key are
    replicated."""
    sh = P(axis)
    return EnvState(
        pos=sh, dest=sh, pause_left=sh, poa=sh, prev_poa=sh,
        blocks_done=sh, chain_state=sh, cur_node=sh, has_request=sh,
        uploaded=sh, delivered_quality=sh, quality_now=sh,
        total_delivered=sh, num_delivered=sh, num_collisions=sh,
        frame=P(), key=P())


def world_specs(axis: str) -> JaxWorld:
    """:class:`JaxWorld` specs: the (E, ...) Table II stacks shard with the
    envs; ``y_hat`` (N, N) is the one env-independent table — replicated."""
    sh = P(axis)
    return JaxWorld(w_hat=sh, eps=sh, qbar=sh, service_of=sh, omega=sh,
                    omega_ue=sh, y_hat=P())


# -- primitives ---------------------------------------------------------------

def segment_positions(groups: jax.Array, ranks: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """jnp twin of :func:`repro.sim.vec_env.segment_positions`.

    Static-shape variant: callers route excluded entries to a sentinel group
    (one past the last real group) instead of boolean-filtering.  The
    (group, rank) order is realized as two stable argsorts (a lexsort), so
    no combined sort key can overflow.
    """
    m = groups.shape[0]
    sel = jnp.argsort(ranks)
    sel = sel[jnp.argsort(groups[sel])]
    g_sorted = groups[sel]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), g_sorted[1:] != g_sorted[:-1]])
    seg_start = jax.lax.cummax(jnp.where(first, jnp.arange(m), 0))
    return sel, jnp.arange(m) - seg_start


def area_of(cfg: SimConfig, pos: jax.Array) -> jax.Array:
    cell = jnp.clip((pos / (cfg.side / cfg.grid)).astype(jnp.int32),
                    0, cfg.grid - 1)
    return cell[..., 0] * cfg.grid + cell[..., 1]


def ue_quality(world: JaxWorld, blocks_done: jax.Array) -> jax.Array:
    """Omega_s(k) per UE — one-hot contraction over the pre-gathered per-UE
    curve (exact: selects one table value, adds IEEE zeros)."""
    b1 = world.omega_ue.shape[-1]
    onehot = blocks_done[..., None] == jnp.arange(b1)
    return jnp.where(onehot, world.omega_ue, 0).sum(axis=-1)


def needs_uplink(state: EnvState) -> jax.Array:
    return state.has_request & (state.chain_state == IDLE)


def _priorities(world: JaxWorld, state: EnvState) -> jax.Array:
    diff = world.qbar - ue_quality(world, state.blocks_done)
    pr = jnp.where(diff > 0, 1.0 / jnp.maximum(diff, 1e-12), 1e-8)
    return jnp.maximum(pr, 1e-8)


def _rank(world: JaxWorld, state: EnvState) -> jax.Array:
    """rank[e, i] = processing position of UE i (priority-descending, ties
    stable by UE index — the stable argsort inverse, computed as pairwise
    counts: #{j: pr_j > pr_i} + #{j < i: pr_j = pr_i}."""
    pr = _priorities(world, state)
    u = pr.shape[1]
    pr_i, pr_j = pr[:, :, None], pr[:, None, :]
    earlier = jnp.arange(u)[None, None, :] < jnp.arange(u)[None, :, None]
    return ((pr_j > pr_i) | ((pr_j == pr_i) & earlier)).sum(axis=-1)


def _pairwise_pos(member: jax.Array, same_group: jax.Array,
                  rank: jax.Array) -> jax.Array:
    """pos_i = #{j: member_j, same_group[i, j], rank_j < rank_i} — the
    0-based position of entry i inside its group when group members are
    ordered by rank (identical to :func:`segment_positions` restricted to
    members).  same_group: (E, U, U) with [e, i, j] = groups match."""
    lower = rank[:, None, :] < rank[:, :, None]
    return (same_group & member[:, None, :] & lower).sum(axis=-1)


# -- multiple access ----------------------------------------------------------

def greedy_mac(cfg: SimConfig, world: JaxWorld, state: EnvState) -> jax.Array:
    """Priority-greedy channel assignment, (E, U) in [0, C) or -1 (silent).

    Same semantics as ``vec_greedy_mac`` on the numpy engine: within each
    (env, BS) group, needy UEs in priority-rank order take channels 0..C-1.
    """
    need = needs_uplink(state)
    rank = _rank(world, state)
    same_bs = state.poa[:, :, None] == state.poa[:, None, :]
    channel = _pairwise_pos(need, same_bs, rank)
    return jnp.where(need & (channel < cfg.num_channels),
                     channel, -1).astype(jnp.int32)


def random_access(cfg: SimConfig, state: EnvState,
                  key: Optional[jax.Array] = None, *,
                  attempt_prob: float = 0.8,
                  attempt_draws: Optional[jax.Array] = None,
                  channel_draws: Optional[jax.Array] = None) -> jax.Array:
    """ALOHA-style uncoordinated access (collision ablation), jax-native.

    Randomness from ``key`` or pre-drawn uniforms in [0, 1) (``attempt_draws``
    (E, U) and ``channel_draws`` (E, U)) for chunk-hoisted draws.
    """
    e, u = state.poa.shape
    if attempt_draws is None:
        k1, k2 = jax.random.split(key)
        attempt_draws = jax.random.uniform(k1, (e, u))
        channel_draws = jax.random.uniform(k2, (e, u))
    attempt = needs_uplink(state) & (attempt_draws < attempt_prob)
    chans = jnp.floor(channel_draws * cfg.num_channels).astype(jnp.int32)
    return jnp.where(attempt, chans, -1).astype(jnp.int32)


# -- one frame ----------------------------------------------------------------

def env_step(cfg: SimConfig, world: JaxWorld, state: EnvState,
             mac: jax.Array, placement: jax.Array, *,
             arrival_draws: Optional[jax.Array] = None,
             waypoint_draws: Optional[jax.Array] = None,
             ) -> Tuple[EnvState, Dict[str, jax.Array]]:
    """Advance one frame for all E envs — pure, jit/scan-safe.

    mac: (E, U) int — channel in [0, C) or -1 (silent).
    placement: (E, U) int — BS in [0, N) or -1 (null action).
    arrival_draws: optional (E, U) uniforms in [0, 1) — new-request draws.
    waypoint_draws: optional (E, U, 2) uniforms in [0, side) — RWP redraws.
    When omitted, both are drawn from ``state.key`` (which advances).

    Returns ``(new_state, info)`` with the same reward components as the
    numpy engine's ``step`` (``rewards`` etc. have shape (E,)).
    """
    e, u = world.qbar.shape
    n, c, b = cfg.num_bs, cfg.num_channels, cfg.max_blocks
    fdtype = world.qbar.dtype

    key = state.key
    if arrival_draws is None:
        key, ka = jax.random.split(key)
        arrival_draws = jax.random.uniform(ka, (e, u), fdtype)
    if waypoint_draws is None:
        key, kw = jax.random.split(key)
        waypoint_draws = jax.random.uniform(kw, (e, u, 2), fdtype,
                                            0.0, cfg.side)

    q_prev = ue_quality(world, state.blocks_done)
    pre_mac_state = state.chain_state                         # C6 snapshot
    earlier = jnp.arange(u)[None, None, :] < jnp.arange(u)[None, :, None]

    # ---- multiple access (C4/C5 collision semantics) ----
    want = needs_uplink(state) & (mac >= 0)
    same_slot = (state.poa[:, :, None] == state.poa[:, None, :]) \
        & (mac[:, :, None] == mac[:, None, :]) & want[:, None, :]
    n_senders = same_slot.sum(axis=-1)        # want-senders in my (BS, ch)
    uploaded_now = want & (n_senders == 1)
    # one collision event per (env, BS, channel) group with >1 senders:
    # count each such group once, at its lowest-index member
    group_rep = want & ~(same_slot & earlier).any(axis=-1)
    # .astype: bool sums promote to int64 under x64, which would break the
    # int32 counter carry inside lax.scan
    num_collisions = state.num_collisions + (group_rep & (n_senders > 1)) \
        .sum(axis=1).astype(state.num_collisions.dtype)
    chain_state = jnp.where(uploaded_now, PENDING, state.chain_state)

    # ---- placement execution (C1-C3): capacity masking by rank ----
    k = state.blocks_done                                     # pre-frame
    active = pre_mac_state != IDLE
    eligible = active & (k < b) & (placement >= 0)
    rank = _rank(world, state)
    a_safe = jnp.where(placement >= 0, placement, 0)

    same_bs = a_safe[:, :, None] == a_safe[:, None, :]
    pos_in_bs = _pairwise_pos(eligible, same_bs, rank)
    onehot_a = a_safe[..., None] == jnp.arange(n)             # (E, U, N)
    cap = jnp.where(onehot_a, world.w_hat[:, None, :], 0).sum(axis=-1)
    granted = eligible & (pos_in_bs < cap)

    bs_load = (onehot_a & granted[..., None]).sum(axis=1) \
        .astype(jnp.int32)                                    # (E, N)

    eps_at = jnp.where(onehot_a, world.eps[:, None, :],
                       jnp.zeros((), fdtype)).sum(axis=-1)
    exec_cost = jnp.where(granted, eps_at, 0.0).sum(axis=1)

    src = jnp.where(k == 0, state.prev_poa, state.cur_node)
    src_safe = jnp.where(src >= 0, src, 0)
    hop = world.y_hat[src_safe, a_safe]
    trans_cost = jnp.where(granted, hop, 0.0)

    new_blocks = jnp.where(granted, k + 1, k)
    new_cur = jnp.where(granted, placement.astype(jnp.int32), state.cur_node)
    chain_state = jnp.where(granted, 1, chain_state)

    # ---- delivery decision (mirrors the scalar branch ladder) ----
    delivered = active & (
        (k >= b)
        | ((placement < 0) & (k > 0))
        | (eligible & ~granted & (k > 0))                     # C3 blocked
        | (granted & (new_blocks == b)))

    # ---- delivery (downlink leg of C9) ----
    deliver_q = delivered & (new_blocks > 0)
    new_cur_safe = jnp.where(new_cur >= 0, new_cur, 0)
    trans_cost = trans_cost + jnp.where(
        deliver_q, world.y_hat[new_cur_safe, state.poa], 0.0)
    dq = ue_quality(world, new_blocks)
    delivered_quality = jnp.where(deliver_q, dq, state.delivered_quality)
    total_delivered = state.total_delivered + \
        jnp.where(deliver_q, dq, 0.0).sum(axis=1)
    num_delivered = state.num_delivered + \
        deliver_q.sum(axis=1).astype(state.num_delivered.dtype)
    blocks_done = jnp.where(delivered, 0, new_blocks)
    chain_state = jnp.where(delivered, IDLE, chain_state)
    cur_node = jnp.where(delivered, -1, new_cur)
    has_request = state.has_request & ~delivered

    # ---- reward, eq. (8) ----
    q_now = ue_quality(world, blocks_done)
    gain = (q_now - q_prev) * (q_now >= world.qbar)
    trans_sum = trans_cost.sum(axis=1)
    rewards = gain.sum(axis=1) - cfg.alpha * exec_cost - cfg.beta * trans_sum

    # ---- world evolution ----
    pos, dest, pause_left, poa = _mobility_step(
        cfg, state.pos, state.dest, state.pause_left, waypoint_draws)
    new_req = (~has_request) & (arrival_draws < cfg.arrival_prob)

    new_state = EnvState(
        pos=pos, dest=dest, pause_left=pause_left,
        poa=poa, prev_poa=state.poa,
        blocks_done=blocks_done, chain_state=chain_state, cur_node=cur_node,
        has_request=has_request | new_req, uploaded=uploaded_now,
        delivered_quality=delivered_quality, quality_now=q_now,
        total_delivered=total_delivered, num_delivered=num_delivered,
        num_collisions=num_collisions,
        frame=state.frame + 1, key=key,
    )
    info = {
        "rewards": rewards,                                   # (E,)
        "quality_gain": gain.sum(axis=1),
        "exec_cost": exec_cost,
        "trans_cost": trans_sum,
        "delivered": delivered,                               # (E, U)
        "executed": granted,                                  # (E, U)
        "bs_load": bs_load,                                   # (E, N)
        "uploaded": uploaded_now,                             # (E, U)
        "done": new_state.frame >= cfg.horizon,
    }
    return new_state, info


def _mobility_step(cfg: SimConfig, pos, dest, pause_left, redraw,
                   dt: float = 1.0):
    """RWP kinematics, formula-for-formula the numpy ``VecRandomWaypoint``
    (so f64 trajectories are bit-identical under identical redraws)."""
    delta = dest - pos
    dist = jnp.linalg.norm(delta, axis=-1)
    moving = pause_left <= 0
    step_len = jnp.minimum(cfg.speed * dt, dist)
    direction = jnp.where(dist[..., None] > 1e-9,
                          delta / jnp.maximum(dist[..., None], 1e-9), 0.0)
    pos = jnp.where(moving[..., None],
                    pos + direction * step_len[..., None], pos)
    arrived = moving & (dist <= cfg.speed * dt + 1e-9)
    pause_left = jnp.where(arrived, cfg.pause, pause_left - dt)
    need_new = (pause_left <= 0) & arrived
    expired = (~moving) & (pause_left <= 0)
    pick = need_new | expired
    dest = jnp.where(pick[..., None], redraw, dest)
    return pos, dest, pause_left, area_of(cfg, pos)


# -- observation (eq. 7) ------------------------------------------------------

def observe(cfg: SimConfig, world: JaxWorld, state: EnvState,
            bs_load: Optional[jax.Array] = None) -> jax.Array:
    e, u = world.qbar.shape
    n = cfg.num_bs
    load = (bs_load if bs_load is not None
            else jnp.zeros((e, n), world.qbar.dtype)) \
        / jnp.maximum(world.w_hat, 1)
    psi = jax.nn.one_hot(state.poa, n, dtype=world.qbar.dtype)  # (E, U, N)
    parts = [
        load,
        world.eps / cfg.eps_high,
        ue_quality(world, state.blocks_done) - world.qbar,
        state.uploaded.astype(world.qbar.dtype),
        psi.reshape(e, u * n),
    ]
    return jnp.concatenate(parts, axis=1).astype(jnp.float32)


# -- variant action masks -----------------------------------------------------

def action_mask(cfg: SimConfig, state: EnvState, variant: str) -> jax.Array:
    """(E, U, A) bool — jax twin of ``LearnGDMController.action_mask_vec``.
    ``variant`` is static (python string) at trace time."""
    e, u = state.poa.shape
    a = cfg.num_bs + 1
    if variant == "learn-gdm":
        return jnp.ones((e, u, a), bool)
    if variant == "mp":
        started = state.blocks_done > 0
        aid = jnp.arange(a)
        allowed = (aid == 0) | (aid == (state.cur_node + 1)[..., None])
        return jnp.where(started[..., None], allowed, True)
    if variant == "fp":
        mid = (state.blocks_done > 0) & (state.blocks_done < cfg.max_blocks)
        null_ok = ~mid                                       # no early exit
        return jnp.concatenate(
            [null_ok[..., None], jnp.ones((e, u, a - 1), bool)], axis=-1)
    raise ValueError(f"unknown variant {variant!r}")


def make_step(cfg: SimConfig, world: JaxWorld):
    """Convenience: jitted ``(state, mac, placement) -> (state, info)``."""
    return jax.jit(functools.partial(env_step, cfg, world))


# -- batched policy evaluation -------------------------------------------------

def build_eval_round(cfg: SimConfig, act_fn, *,
                     mac_scheme: str = "greedy", history: int = 1,
                     needs_obs: bool = True, mesh=None, axis: str = "env"):
    """Compile one evaluation round — a ``lax.scan`` over the episode running
    MAC → policy act → :func:`env_step` — as a single jitted function.

    ``act_fn(params, state, obs_hist, draw)`` is the pure policy: given its
    (pytree) params, the :class:`EnvState`, the (E, H, obs_dim) observation
    history and an optional per-frame uniform block ``draw`` (``None`` when
    the draws dict has no ``"policy"`` entry), it returns (E, U) int32
    actions (0 = null, n+1 = BS n).  This is the seam every controller
    evaluates through on the fused engine (``repro.core.policy``).
    ``act_fn`` must not capture device arrays (route world-derived data
    through ``params``): the world is a traced argument so one compiled
    round serves every same-shape world.

    Returns jitted ``round_fn(params, world, state0, draws) ->
    (final_state, stats)`` with ``draws`` a dict of (T, ...) leading-time
    arrays: ``"arrival"`` (T, E, U), ``"waypoint"`` (T, E, U, 2) and
    optionally ``"policy"`` plus, for ``mac_scheme="random"``,
    ``"mac_attempt"`` / ``"mac_channel"`` (T, E, U).  ``state0`` must carry
    zeroed episode counters (a fresh :func:`reset_env` / post-reset
    :func:`state_from_numpy` state): per-round stats are read off the final
    state's counters.  ``needs_obs=False`` (policies whose ``act_fn``
    ignores observations, e.g. GR) drops the per-frame :func:`observe` and
    the history carry from the scan.

    ``mesh`` (a 1-D device mesh with axis ``axis``, e.g.
    ``repro.launch.mesh.make_env_mesh``) shards the whole round over the env
    dim via ``shard_map``: every frame quantity is per-env (no cross-env
    arithmetic anywhere in :func:`env_step`), so each shard scans its env
    slice independently and the result is EXACTLY the single-device round —
    the caller supplies the same host-side ``state0``/``draws`` either way.
    E must be divisible by the mesh size.
    """
    assert mac_scheme in ("greedy", "random")

    def round_fn(params, world: JaxWorld, state0: EnvState, draws):
        if needs_obs:
            obs0 = observe(cfg, world, state0)
            hist0 = jnp.repeat(obs0[:, None], history, axis=1)  # (E, H, obs)
        else:
            hist0 = jnp.zeros((), jnp.float32)                  # inert carry

        def frame_fn(carry, d):
            state, obs_hist = carry
            if mac_scheme == "greedy":
                mac = greedy_mac(cfg, world, state)
            else:
                mac = random_access(cfg, state,
                                    attempt_draws=d["mac_attempt"],
                                    channel_draws=d["mac_channel"])
            actions = act_fn(params, state,
                             obs_hist if needs_obs else None,
                             d.get("policy"))
            state, info = env_step(cfg, world, state, mac, actions - 1,
                                   arrival_draws=d["arrival"],
                                   waypoint_draws=d["waypoint"])
            if needs_obs:
                next_obs = observe(cfg, world, state, info["bs_load"])
                obs_hist = jnp.concatenate(
                    [obs_hist[:, 1:], next_obs[:, None]], axis=1)
            return (state, obs_hist), (info["rewards"], info["quality_gain"],
                                       info["exec_cost"], info["trans_cost"])

        (state, _), (rew, qg, ec, tc) = jax.lax.scan(
            frame_fn, (state0, hist0), draws)
        stats = {
            "reward": rew.sum(axis=0),
            "quality_gain": qg.sum(axis=0),
            "exec_cost": ec.sum(axis=0),
            "trans_cost": tc.sum(axis=0),
            "delivered_quality": state.total_delivered,
            "num_delivered": state.num_delivered,
            "collisions": state.num_collisions,
        }
        return state, stats

    if mesh is None:
        return jax.jit(round_fn)

    # in_specs pytree prefixes: params replicated (every shard runs the same
    # policy), world/state per-field, the draws dict uniformly (T, E, ...).
    # check_vma=False: the specs above are what guarantee that the frame/key
    # carry stays replicated through lax.scan.
    sharded = shard_map(
        round_fn, mesh=mesh,
        in_specs=(P(), world_specs(axis), state_specs(axis),
                  P(None, axis)),
        out_specs=(state_specs(axis), P(axis)),
        check_vma=False)
    return jax.jit(sharded)
