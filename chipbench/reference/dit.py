"""A plain DiT block call: the yardstick the served block call is held to.

Straight ``jax.numpy``, no kernels, no batching tricks, and nothing
imported from the program.  It follows the DiT of arXiv:2212.09748 with
the departures the configuration file lists (4-channel tokens, prompt
conditioning, learned positions, eps output), and one deterministic DDIM
step per block.

The weights are drawn again from the configuration's ``weights_seed``,
with the same key schedule the service uses to draw its own: the
reference takes no array the program made.  ``dtype`` is the type the whole forward pass computes
in: float32 under ``jax.default_matmul_precision("highest")`` is the
reference; bfloat16 is the control (weights, activations and the residual
stream in bfloat16; the statistics of LayerNorm and softmax in float32).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LATENT_CHANNELS = 4
TIMESTEP_FEATURES = 256
NORM_EPS = 1e-5


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def init_params(key, m: dict) -> dict:
    """The service's weights for its key ``key`` (float32)."""
    d, f, L = m["d_model"], m["d_ff"], m["num_layers"]
    q = m["num_heads"] * m["head_dim"]
    s = m["latent_hw"] ** 2
    ks = jax.random.split(key, 8)
    layers = []
    for lk in jax.random.split(ks[0], L):
        k_attn, k_mlp, k_ada = jax.random.split(lk, 3)
        ka = jax.random.split(k_attn, 4)
        k_up, k_down = jax.random.split(k_mlp)
        layers.append({
            "wq": _normal(ka[0], (d, q), d ** -0.5),
            "wk": _normal(ka[1], (d, q), d ** -0.5),
            "wv": _normal(ka[2], (d, q), d ** -0.5),
            "wo": _normal(ka[3], (q, d), q ** -0.5 / math.sqrt(max(1, 2 * L))),
            "w_up": _normal(k_up, (d, f), d ** -0.5),
            "b_up": jnp.zeros((f,), jnp.float32),
            "w_down": _normal(k_down, (f, d), f ** -0.5 / math.sqrt(max(1, 2 * L))),
            "b_down": jnp.zeros((d,), jnp.float32),
            "w_ada": _normal(k_ada, (d, 6 * d), d ** -0.5),
            "ln1_w": jnp.ones((d,), jnp.float32),
            "ln1_b": jnp.zeros((d,), jnp.float32),
            "ln2_w": jnp.ones((d,), jnp.float32),
            "ln2_b": jnp.zeros((d,), jnp.float32),
        })
    return {
        "patch_in": _normal(ks[1], (LATENT_CHANNELS, d), LATENT_CHANNELS ** -0.5),
        "pos": jax.random.normal(ks[2], (1, s, d), jnp.float32)[0] * 0.02,
        "t1": _normal(ks[3], (TIMESTEP_FEATURES, d), TIMESTEP_FEATURES ** -0.5),
        "t2": _normal(ks[4], (d, d), d ** -0.5),
        "table": _normal(ks[5], (m["vocab_size"], d), 0.02),
        "lnf_w": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
        "patch_out": _normal(ks[6], (d, LATENT_CHANNELS), d ** -0.5),
        "layers": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
    }


def service_keys(key, services: int):
    """The keys the services draw their weights from: ``key`` split once
    per service, then the first half of each split."""
    return [jax.random.split(k)[0] for k in jax.random.split(key, services)]


def alpha_bar(total_steps: int):
    """The DDIM schedule: betas linear from 1e-4 to 0.02, float32."""
    betas = jnp.linspace(1e-4, 0.02, total_steps, dtype=jnp.float32)
    return jnp.cumprod(1.0 - betas)


def _layernorm(x, w, b, dt):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + NORM_EPS) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)
    return y.astype(dt)


def _timestep_features(t):
    half = TIMESTEP_FEATURES // 2
    freqs = np.exp(-math.log(10_000.0) * np.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _layer(x, cond, p, m, dt):
    b, s, d = x.shape
    h, hd = m["num_heads"], m["head_dim"]
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
        (jax.nn.silu(cond) @ p["w_ada"]).astype(dt), 6, axis=-1)
    y = _layernorm(x, p["ln1_w"], p["ln1_b"], dt) * (1 + sc1) + sh1
    q = (y @ p["wq"]).reshape(b, s, h, hd)
    k = (y @ p["wk"]).reshape(b, s, h, hd)
    v = (y @ p["wv"]).reshape(b, s, h, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * hd ** -0.5
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    x = x + g1 * (a @ p["wo"])
    y = _layernorm(x, p["ln2_w"], p["ln2_b"], dt) * (1 + sc2) + sh2
    u = jax.nn.gelu(y @ p["w_up"] + p["b_up"], approximate=True)
    return x + g2 * (u @ p["w_down"] + p["b_down"])


def denoise(params, latent, t, prompt, m: dict, dt=jnp.float32):
    """eps for latents (B, S, C) at integer timesteps t (B,)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dt), params)
    x = latent.astype(dt) @ p["patch_in"] + p["pos"][None]
    temb = _timestep_features(t).astype(dt) @ p["t1"]
    temb = jax.nn.silu(temb) @ p["t2"]
    pemb = jnp.take(p["table"], prompt, axis=0).astype(jnp.float32) \
        .mean(axis=1).astype(dt)
    cond = (temb + pemb)[:, None]
    x, _ = jax.lax.scan(lambda c, lp: (_layer(c, cond, lp, m, dt), None), x,
                        p["layers"])
    x = _layernorm(x, p["lnf_w"], p["lnf_b"], dt)
    return (x @ p["patch_out"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mj", "total_steps", "dtype"))
def _block(params, latent, prompt, block_idx, *, mj, total_steps, dtype):
    m = dict(mj)
    ab = alpha_bar(total_steps)
    t = total_steps - 1 - block_idx
    eps = denoise(params, latent, t, prompt, m, jnp.dtype(dtype))
    ab_t = ab[t][:, None, None]
    ab_prev = jnp.where(t > 0, ab[jnp.maximum(t - 1, 0)], 1.0)[:, None, None]
    x0 = (latent - jnp.sqrt(1 - ab_t) * eps) / jnp.sqrt(ab_t)
    return jnp.sqrt(ab_prev) * x0 + jnp.sqrt(1 - ab_prev) * eps, x0


def block(params, inputs: dict, block_idx, m: dict, *, blocks: int,
          dtype: str = "float32", precision: str = "highest"):
    """One block (one DDIM step) for each row at its own block index:
    returns (latent after the step, x0 estimate), float32 numpy.
    ``inputs`` holds the rows' stacked ``latent`` and ``prompt``;
    ``precision`` is JAX's default matmul precision for the call."""
    mj = tuple(sorted((k, v) for k, v in m.items()
                      if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision(precision):
        lat, x0 = _block(params, jnp.asarray(inputs["latent"], jnp.float32),
                         jnp.asarray(inputs["prompt"], jnp.int32),
                         jnp.asarray(block_idx, jnp.int32), mj=mj,
                         total_steps=blocks, dtype=dtype)
    return np.asarray(lat), np.asarray(x0)


def make_params(key, m: dict):
    """``init_params`` as one jitted call on the device."""
    mj = tuple(sorted((k, v) for k, v in m.items()
                      if isinstance(v, (int, float, str))))
    return _init_jit(key, mj=mj)


@functools.partial(jax.jit, static_argnames=("mj",))
def _init_jit(key, *, mj):
    return init_params(key, dict(mj))
