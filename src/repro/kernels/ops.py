"""Jit'd public wrappers for the Pallas kernels with backend dispatch.

``impl`` semantics (every op):
  * ``"auto"``      — Pallas on TPU backends, XLA reference elsewhere.  The
                      multi-pod dry-run compiles for the CPU target where TPU
                      Pallas cannot lower, so ``auto`` keeps dry-run/prod
                      behaviour identical in math while selecting the fast
                      path on real hardware.
  * ``"pallas"``    — Pallas, compiled (TPU only).
  * ``"interpret"`` — Pallas, interpret mode (CPU correctness validation).
  * ``"xla"``       — pure-jnp oracle from :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.adaln_norm import adaln_norm_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


def resolve_impl(impl: str) -> str:
    """Resolve ``"auto"`` for this host: Pallas on TPU, XLA oracle elsewhere."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


_resolve = resolve_impl          # internal alias (pre-PR-8 name)


# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "scale", "impl", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: float | None = None,
                    impl: str = "auto", block_q: int = 128, block_k: int = 128):
    """Causal/windowed GQA attention.  q: (B,Sq,H,D); k,v: (B,Sk,KH,D)."""
    mode = _resolve(impl)
    if mode == "xla":
        return ref.attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale,
        block_q=block_q, block_k=block_k, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("scale", "impl", "block_k"))
def decode_attention(q, k_cache, v_cache, lengths, *, scale: float | None = None,
                     impl: str = "auto", block_k: int = 256):
    """Single-token GQA cache attention.  q: (B,H,D); caches: (B,S,KH,D)."""
    mode = _resolve(impl)
    if mode == "xla":
        return ref.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, scale=scale, block_k=block_k,
        interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "block_d"))
def ssm_scan(u, delta, a, bmat, cmat, d, *, impl: str = "auto",
             chunk: int = 64, block_d: int = 256):
    """Selective scan.  Returns y only (state threading uses ref.ssm_scan)."""
    mode = _resolve(impl)
    if mode == "xla":
        y, _ = ref.ssm_scan(u, delta, a, bmat, cmat, d)
        return y
    length = u.shape[1]
    chunk = _largest_divisor_leq(length, chunk)
    din = u.shape[2]
    block_d = _largest_divisor_leq(din, block_d)
    return ssm_scan_pallas(u, delta, a, bmat, cmat, d, chunk=chunk,
                           block_d=block_d, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("eps", "impl", "block_rows"))
def adaln_norm(x, shift, scale, weight, bias, gate=None, residual=None, *,
               eps: float = 1e-5, impl: str = "auto", block_rows: int = 128):
    """Fused DiT adaLN: LayerNorm + shift/scale modulation.

    x: (B, S, d); shift/scale/gate: (B, d) or (B, 1, d); weight/bias: (d,).
    With ``gate``+``residual`` the previous sublayer's gated residual add is
    fused in first and ``(y, new_residual)`` is returned.
    """
    b, _, d = x.shape
    mode = _resolve(impl)
    # the oracle takes (B, d); the kernel reads (1, 1, d) blocks of (B, 1, d)
    vec = (b, d) if mode == "xla" else (b, 1, d)
    shift, scale = shift.reshape(vec), scale.reshape(vec)
    if gate is not None:
        gate = gate.reshape(vec)
    if mode == "xla":
        return ref.adaln_norm(x, shift, scale, weight, bias, gate=gate,
                              residual=residual, eps=eps)
    return adaln_norm_pallas(x, shift, scale, weight, bias, gate=gate,
                             residual=residual, eps=eps,
                             block_rows=block_rows,
                             interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("eps", "impl", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-6, impl: str = "auto",
            block_rows: int = 256):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.rmsnorm(x, scale, eps=eps)
    return rmsnorm_pallas(x, scale, eps=eps, block_rows=block_rows,
                          interpret=(mode == "interpret"))


def _largest_divisor_leq(n: int, cap: int) -> int:
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1
