"""Expert-parallel MoE with explicit all-to-all dispatch under shard_map.

The GSPMD einsum formulation (repro.nn.moe) leaves the expert-combine as a
per-layer all-reduce of the full (T, d) activation in f32 — measured as the
dominant collective for qwen3-moe train_4k (EXPERIMENTS.md §Perf).  The
classical EP schedule moves only *routed tokens*:

  tokens are sequence-split over the model axis (SP layout); each shard
  routes its T/tp tokens, packs per-destination buffers of capacity C_s,
  ships them with ONE all_to_all (bf16), runs its local experts, and ships
  results back with a second all_to_all; the combine is then purely local.

Traffic per layer: 2 * T/tp * k * cap_factor * d * 2B per shard — bf16 and
proportional to k/E utilisation instead of 2 * T * d * 4B ring all-reduce.

Differentiable end-to-end (all_to_all transposes to all_to_all; gathers to
scatters), so the same path serves the backward pass.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def moe_apply_sharded(params, x, *, cfg: ModelConfig, mesh, model_axis="model",
                      batch_axes=(), capacity_factor: float | None = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) batch-sharded over ``batch_axes``; returns (y, aux).

    Requires S % tp == 0 (sequence-split dispatch) and num_experts % tp == 0.
    """
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    tp = mesh.shape[model_axis]
    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = e // tp
    b, s, d = x.shape
    dff = cfg.moe_d_ff or cfg.d_ff
    bspec = tuple(batch_axes) if batch_axes else None

    def local_fn(x_l, router, gate_w, up_w, down_w):
        bl, sl, _ = x_l.shape
        t_l = bl * sl
        xf = x_l.reshape(t_l, d)
        cap_s = max(k, int(capacity_factor * t_l * k / tp))     # per-dest
        cap_e = max(k, int(capacity_factor * t_l * k * tp / e)) # per local expert

        logits = xf.astype(jnp.float32) @ router                # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)                    # (T_l, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (t_l * k)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, model_axis)

        # ---- pack per-destination send buffers ----
        flat_ids = ids.reshape(-1)                              # (T_l*k,)
        flat_gates = gates.reshape(-1)
        dest = flat_ids // e_loc                                # owning shard
        order = jnp.argsort(dest)
        dest_s = dest[order]
        ids_s = flat_ids[order]
        gates_s = flat_gates[order]
        tok_s = order // k
        starts = jnp.searchsorted(dest_s, jnp.arange(tp))
        pos = jnp.arange(t_l * k) - starts[dest_s]
        keep = pos < cap_s
        pos_c = jnp.minimum(pos, cap_s - 1)

        send_x = jnp.zeros((tp, cap_s, d), x_l.dtype)
        send_x = send_x.at[dest_s, pos_c].add(
            xf[tok_s] * keep.astype(xf.dtype)[:, None])
        # metadata rides along as an extra channel block (expert id, gate)
        send_eid = jnp.full((tp, cap_s), -1, jnp.int32)
        send_eid = send_eid.at[dest_s, pos_c].max(
            jnp.where(keep, (ids_s % e_loc).astype(jnp.int32), -1))

        # ---- ship tokens to expert owners ----
        recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, model_axis, 0, 0, tiled=False)
        rx = recv_x.reshape(tp * cap_s, d)
        reid = recv_eid.reshape(tp * cap_s)
        rkeep = reid >= 0

        # ---- local expert compute (capacity-bounded buffer) ----
        sort_key = jnp.where(rkeep, reid, e_loc)      # invalid -> sorts last
        r_order = jnp.argsort(sort_key)
        key_s = sort_key[r_order]                     # ascending, e_loc = pad
        rstarts = jnp.searchsorted(key_s, jnp.arange(e_loc))
        rpos = jnp.arange(tp * cap_s) - rstarts[jnp.clip(key_s, 0, e_loc - 1)]
        rvalid = (key_s < e_loc) & (rpos < cap_e)
        rpos_c = jnp.clip(rpos, 0, cap_e - 1)
        reid_c = jnp.clip(key_s, 0, e_loc - 1)

        buf = jnp.zeros((e_loc, cap_e, d), x_l.dtype)
        buf = buf.at[reid_c, rpos_c].add(
            rx[r_order] * rvalid.astype(rx.dtype)[:, None])
        g = jnp.einsum("ecd,edf->ecf", buf, gate_w.astype(buf.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, up_w.astype(buf.dtype))
        out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                             down_w.astype(buf.dtype))

        # unsort back to (tp, cap_s, d) layout and ship results home
        y_sorted = out_buf[reid_c, rpos_c] * rvalid.astype(out_buf.dtype)[:, None]
        y_recv_layout = jnp.zeros((tp * cap_s, d), x_l.dtype)
        y_recv_layout = y_recv_layout.at[r_order].set(y_sorted)
        y_back = jax.lax.all_to_all(
            y_recv_layout.reshape(tp, cap_s, d), model_axis, 0, 0, tiled=False)

        # ---- local combine ----
        contrib = y_back[dest_s, pos_c] * (keep.astype(xf.dtype)
                                           * gates_s.astype(xf.dtype))[:, None]
        y = jnp.zeros((t_l, d), x_l.dtype).at[tok_s].add(contrib)
        return y.reshape(bl, sl, d), aux

    y, aux = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bspec, model_axis, None),      # x: sequence-split (SP)
                  P(),                             # router (replicated)
                  P(model_axis, None, None),       # gate_w (E over model)
                  P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(bspec, model_axis, None), P()),
        check_vma=False,
    )(x, params["router"], params["gate_w"], params["up_w"], params["down_w"])
    return y, aux
