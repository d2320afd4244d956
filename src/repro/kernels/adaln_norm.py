"""Fused adaLN Pallas TPU kernel (row tiles x full feature dim in VMEM).

The DiT denoise block applies LayerNorm followed by adaLN-zero modulation
twice per layer:

    y = LN(x) * (1 + scale) + shift                      (pre-sublayer)
    r = residual + gate * h;  y = LN(r) * (1 + sc) + sh  (gated epilogue)

Unfused, that is 4+ HBM round trips over the (B, S, d) activation per
sublayer; this kernel does one read + one write per tile.  The epilogue
variant additionally folds the previous sublayer's gated residual add into
the same tile pass and emits BOTH the modulated output and the new residual
stream (two outputs), so the residual never makes a separate trip.

Shapes: x/residual (B, S, d); shift/scale/gate (B, 1, d) — one modulation
vector per batch row (the DiT conditions on timestep + prompt, not on
position); weight/bias (d,) — the LayerNorm affine params.  The modulation
block is (1, 1, d): on the TPU the last two block dims must be (8, 128)
multiples or equal to the array's, and a (1, d) block over a (B, d) array
fails to lower for every B > 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adaln_kernel(x_ref, w_ref, b_ref, sc_ref, sh_ref, o_ref, *, eps: float):
    x = x_ref[0].astype(jnp.float32)                       # (br, d)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * (var + eps) ** -0.5
    y = y * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y = y * (1.0 + sc_ref[0].astype(jnp.float32)) + sh_ref[0].astype(jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def _adaln_epilogue_kernel(h_ref, g_ref, r_ref, w_ref, b_ref, sc_ref, sh_ref,
                           y_ref, res_ref, *, eps: float):
    h = h_ref[0].astype(jnp.float32)                       # (br, d)
    r = r_ref[0].astype(jnp.float32) + g_ref[0].astype(jnp.float32) * h
    res_ref[0] = r.astype(res_ref.dtype)
    mean = jnp.mean(r, axis=-1, keepdims=True)
    var = jnp.var(r, axis=-1, keepdims=True)
    y = (r - mean) * (var + eps) ** -0.5
    y = y * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y = y * (1.0 + sc_ref[0].astype(jnp.float32)) + sh_ref[0].astype(jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


def adaln_norm_pallas(x, shift, scale, weight, bias, gate=None, residual=None,
                      *, eps: float = 1e-5, block_rows: int = 128,
                      interpret: bool = False):
    """x: (B, S, d); shift/scale/gate: (B, 1, d); weight/bias: (d,).

    Without ``gate``/``residual``: returns LN(x) * (1 + scale) + shift.
    With both: computes r = residual + gate * x first and returns
    ``(LN(r) * (1 + scale) + shift, r)``.
    """
    b, s, d = x.shape
    br = min(block_rows, _ceil_to(s, 8))
    s_p = _ceil_to(s, br)
    if s_p != s:
        pad = ((0, 0), (0, s_p - s), (0, 0))
        x = jnp.pad(x, pad)
        if residual is not None:
            residual = jnp.pad(residual, pad)

    grid = (b, s_p // br)
    row_spec = pl.BlockSpec((1, br, d), lambda bb, rr: (bb, rr, 0))
    vec_spec = pl.BlockSpec((1, 1, d), lambda bb, rr: (bb, 0, 0))
    prm_spec = pl.BlockSpec((1, d), lambda bb, rr: (0, 0))
    w2, b2 = weight.reshape(1, d), bias.reshape(1, d)

    if gate is None:
        out = pl.pallas_call(
            functools.partial(_adaln_kernel, eps=eps),
            grid=grid,
            in_specs=[row_spec, prm_spec, prm_spec, vec_spec, vec_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((b, s_p, d), x.dtype),
            interpret=interpret,
            name="adaln_norm",
        )(x, w2, b2, scale, shift)
        return out[:, :s]

    y, res = pl.pallas_call(
        functools.partial(_adaln_epilogue_kernel, eps=eps),
        grid=grid,
        in_specs=[row_spec, vec_spec, row_spec, prm_spec, prm_spec,
                  vec_spec, vec_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s_p, d), x.dtype),
                   jax.ShapeDtypeStruct((b, s_p, d), x.dtype)],
        interpret=interpret,
        name="adaln_norm_epilogue",
    )(x, gate, residual, w2, b2, scale, shift)
    return y[:, :s], res[:, :s]


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
