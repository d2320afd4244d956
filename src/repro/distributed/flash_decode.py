"""Sharded flash-decoding: split-K decode attention over the model axis.

When num_kv_heads < tp the KV cache cannot shard over heads; the baseline
seq-shards the cache and lets GSPMD re-shard at the einsum — which lowers to
an involuntary all-gather of the (repeated-to-H) cache: O(S·H·D) bytes over
ICI per layer per step.

Flash-decoding instead keeps the cache seq-sharded and computes attention as
split-K partial softmaxes under ``shard_map``: each model shard attends over
its local cache block, producing a partial (out, logsumexp) pair; the exact
combine is

    m  = max_i m_i
    l  = sum_i l_i * exp(m_i - m)
    o  = sum_i o_i * l_i * exp(m_i - m) / l

so the only ICI traffic is O(H·D + H) per (batch, layer) — independent of S.
This is the TPU-native analogue of the paper's "latent" economy: ship the
tiny sufficient statistic, not the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _local_partial(q, k, v, lengths, start, scale):
    """Partial attention over a local cache block.

    q: (B, H, D); k, v: (B, S_loc, KH, D); lengths: (B,) GLOBAL valid length;
    start: scalar global offset of this block.  Returns (o, m, l) with
    o (B, H, D) f32 unnormalized-but-rescaled, m/l (B, H) f32.

    Inside shard_map there is no GSPMD propagation to appease, so GQA uses
    the grouped einsum directly — no kv repeat, no (B, S, H, D) score-side
    materialization (8x less local traffic for kv=8, H=64 — §Perf iter 4).
    """
    b, h, d = q.shape
    s_loc, kh = k.shape[1], k.shape[2]
    g = h // kh
    qf = q.astype(jnp.float32).reshape(b, kh, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf) * scale        # (B,KH,G,S)
    pos = start + jnp.arange(s_loc)
    valid = pos[None, :] < lengths[:, None]                       # (B, S_loc)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                                  # (B, KH, G)
    # guard fully-masked blocks
    e = jnp.exp(scores - m[..., None])
    e = jnp.where(valid[:, None, None, :], e, 0.0)
    l = jnp.sum(e, axis=-1)                                       # (B, KH, G)
    o = jnp.einsum("bkgs,bskd->bkgd", e, vf)                      # (B,KH,G,D)
    return o.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def sharded_decode_attention(q, k_cache, v_cache, lengths, *,
                             axis: str = "model", batch_axes=(), mesh=None,
                             scale: float | None = None,
                             k_new=None, v_new=None):
    """Split-K decode attention under shard_map over ``axis``.

    q: (B, H, D) replicated over ``axis`` (batch may shard over
    ``batch_axes``); k_cache/v_cache: (B, S, KH, D) seq-sharded over
    ``axis``; lengths: (B,) global VALID length (the new token's position).
    Returns (B, H, D) — or (out, k_cache, v_cache) when ``k_new``/``v_new``
    (B, KH, D) are given: the insert then happens INSIDE the shard_map as a
    masked local dynamic-update-slice on the owning shard, avoiding the
    full-cache reshard copy that a global insert into a seq-sharded buffer
    otherwise triggers (measured +1.5 s/step on deepseek-67b decode_32k —
    EXPERIMENTS.md §Perf iteration 2).  ``mesh`` must be the mesh the
    enclosing jit was sharded against.
    """
    b, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    bspec = tuple(batch_axes) if batch_axes else None
    with_insert = k_new is not None

    def local_fn(q_l, k_l, v_l, len_l, kn_l, vn_l):
        idx = jax.lax.axis_index(axis)
        s_loc = k_l.shape[1]
        start = idx * s_loc
        if with_insert:
            # all batch rows insert at position len_l[0]-1 (aligned batching)
            pos = len_l[0] - 1
            local_pos = jnp.clip(pos - start, 0, s_loc - 1)
            owns = (pos >= start) & (pos < start + s_loc)
            kn = jnp.where(owns, kn_l.astype(k_l.dtype),
                           jax.lax.dynamic_slice_in_dim(k_l, local_pos, 1, 1)[:, 0])
            vn = jnp.where(owns, vn_l.astype(v_l.dtype),
                           jax.lax.dynamic_slice_in_dim(v_l, local_pos, 1, 1)[:, 0])
            k_l = jax.lax.dynamic_update_slice_in_dim(k_l, kn[:, None], local_pos, 1)
            v_l = jax.lax.dynamic_update_slice_in_dim(v_l, vn[:, None], local_pos, 1)
        o, m, l = _local_partial(q_l, k_l, v_l, len_l, start, scale)
        # combine partials across the axis: ship (o, m, l) — O(H*D) bytes
        m_all = jax.lax.all_gather(m, axis)                       # (G, B, H)
        o_all = jax.lax.all_gather(o, axis)                       # (G, B, H, D)
        l_all = jax.lax.all_gather(l, axis)
        m_star = jnp.max(m_all, axis=0)                           # (B, H)
        w = jnp.exp(m_all - m_star[None])                         # (G, B, H)
        l_star = jnp.sum(l_all * w, axis=0)                       # (B, H)
        num = jnp.sum(o_all * w[..., None], axis=0)               # (B, H, D)
        out = num / jnp.maximum(l_star, 1e-30)[..., None]
        if with_insert:
            return out.astype(q_l.dtype), k_l, v_l
        return out.astype(q_l.dtype)

    kn_arg = k_new if with_insert else jnp.zeros((b, *k_cache.shape[2:]), k_cache.dtype)
    vn_arg = v_new if with_insert else jnp.zeros((b, *v_cache.shape[2:]), v_cache.dtype)
    cache_spec = P(bspec, axis, None, None)
    out_specs = (P(bspec, None, None), cache_spec, cache_spec) if with_insert \
        else P(bspec, None, None)
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bspec, None, None), cache_spec, cache_spec, P(bspec),
                  P(bspec, None, None), P(bspec, None, None)),
        out_specs=out_specs,
        # the combine makes the output replicated over `axis`, but the static
        # VMA analysis cannot see through axis_index -> gather -> reduce
        check_vma=False,
    )(q, k_cache, v_cache, lengths, kn_arg, vn_arg)
