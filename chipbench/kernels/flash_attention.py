"""Work of one call of the non-causal ``flash_attention`` kernel.

One call covers a batch of ``rows`` samples, all heads, S queries against
S keys (the DiT's self-attention: no mask, no rope).  FLOPs: the scores
Q K^T and the weighted sum P V, 2*S^2*hd each per head.  Bytes: Q, K and V
read once and the output written once, in the activations' type; the
least traffic any implementation needs.
"""
from __future__ import annotations

NAMES = ("flash_attention",)
CALLS_PER_LAYER = 1


def cost(m: dict, rows: int, itemsize: int = 4) -> tuple[float, float]:
    s = m["latent_hw"] ** 2
    h, hd = m["num_heads"], m["head_dim"]
    flops = 2 * 2 * rows * h * s * s * hd
    nbytes = 4 * rows * s * h * hd * itemsize
    return float(flops), float(nbytes)
