"""Work of the DiT denoiser per sample and denoise step, from its shapes.

This is the algorithm's work, whatever implements it: the matmul FLOPs of
one forward pass of the DiT (arXiv:2212.09748) as the repository's
``gdm-dit`` family runs it, counted 2 per multiply-add.

Per layer and sample, with S tokens of width d and MLP width f:

* q, k, v and output projections: 4 * 2*S*d^2 = 8*S*d^2
* MLP up and down: 2 * 2*S*d*f (= 16*S*d^2 at f = 4d)
* attention scores and the weighted sum: 2 * 2*S^2*d = 4*S^2*d
* adaLN modulation, once per sample: 2*d*6d = 12*d^2

plus the patch embedding in and out (2*S*C*d each), and the timestep MLP
(2*256*d + 2*d^2).  Elementwise work (norms, GELU, softmax) is left out,
as model FLOP counts do.
"""
from __future__ import annotations

LATENT_CHANNELS = 4
TIMESTEP_FEATURES = 256

# the served block call's program, as the device trace names its runs
MODULES = ("jit_run",)


def attention(m: dict) -> dict:
    """The shape of the block call's attention: self-attention over the
    S latent tokens, one kernel row per request row, one call per
    layer."""
    s = m["latent_hw"] ** 2
    return {"q_len": s, "kv_len": s, "rows_per_request": 1,
            "calls_per_step": m["num_layers"]}


def layer_flops(m: dict) -> int:
    s = m["latent_hw"] ** 2
    d = m["d_model"]
    f = m["d_ff"]
    q = m["num_heads"] * m["head_dim"]
    a = attention(m)
    proj = 2 * s * d * q * 3 + 2 * s * q * d          # q, k, v, out
    mlp = 2 * 2 * s * d * f
    attn = 2 * 2 * a["q_len"] * a["kv_len"] * q * a["rows_per_request"]
    ada = 2 * d * 6 * d
    return proj + mlp + attn + ada


def flops_per_sample_step(m: dict) -> int:
    """FLOPs of one denoise step of one sample."""
    s = m["latent_hw"] ** 2
    d = m["d_model"]
    io = 2 * 2 * s * LATENT_CHANNELS * d
    temb = 2 * TIMESTEP_FEATURES * d + 2 * d * d
    return m["num_layers"] * layer_flops(m) + io + temb


def main_term_flops(m: dict) -> int:
    """The leading term L * (24*S*d^2 + 4*S^2*d) alone (MLP ratio 4)."""
    s = m["latent_hw"] ** 2
    d = m["d_model"]
    return m["num_layers"] * (24 * s * d * d + 4 * s * s * d)
