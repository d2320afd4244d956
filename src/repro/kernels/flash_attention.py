"""FlashAttention-2 style Pallas TPU kernel (causal / windowed GQA).

Tiling: grid (batch, q_heads, Sq/bq, Sk/bk); the kv-block dimension is the
innermost (sequential on TPU), carrying the online-softmax state
(m, l, acc) in VMEM scratch.  Block shapes are MXU-aligned (last dim = head
dim, multiples of 128 preferred; q/kv tiles default 128).

GQA is handled in the BlockSpec index maps: query head ``h`` reads kv head
``h // (H // KH)`` — no materialized kv repeat.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  bq: int, bk: int, sk_actual: int):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bq, bk)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk_actual                                # kv padding
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]                                   # (bq,)
    l_prev = l_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                        # (bq, bk)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           q_offset: int = 0, scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KH, D).  Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    assert h % kh == 0, (h, kh)
    group = h // kh
    scale = scale if scale is not None else d ** -0.5

    bq = min(block_q, _ceil_to(sq, 8))
    bk = min(block_k, _ceil_to(sk, 8))
    sq_p, sk_p = _ceil_to(sq, bq), _ceil_to(sk, bk)

    # (B, H, S, D) kernel layout
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sq_p != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b, h, sq_p // bq, sk_p // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, sk_actual=sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, qq, kk, g=group: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, qq, kk, g=group: (bb, hh // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    out = out[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
