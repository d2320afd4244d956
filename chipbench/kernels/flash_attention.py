"""Work of one call of the non-causal ``flash_attention`` kernel.

One call covers a batch of kernel rows, all heads, q_len queries against
kv_len keys, with no mask and no rope.  The shape comes from the
configuration's work module (``kernels/<work>.py:attention(m)``): the
query and key lengths (a DiT's self-attention has both at its token
count; a joint text-and-image attention has both at the sum), the kernel
rows each request row stacks (2 under classifier-free guidance), and the
kernel's calls per denoise step.  FLOPs: the scores Q K^T and the
weighted sum P V, 2*q_len*kv_len*hd each per head and kernel row.  Bytes:
Q and the output over q_len, K and V over kv_len, read or written once,
in the activations' type; the least traffic any implementation needs.
"""
from __future__ import annotations

NAMES = ("flash_attention",)


def cost(m: dict, shape: dict, rows: int,
         itemsize: int = 4) -> tuple[float, float]:
    """FLOPs and bytes of one call on ``rows`` request rows."""
    r = rows * shape["rows_per_request"]
    q, kv = shape["q_len"], shape["kv_len"]
    h, hd = m["num_heads"], m["head_dim"]
    flops = 2 * 2 * r * h * q * kv * hd
    nbytes = 2 * r * h * hd * (q + kv) * itemsize
    return float(flops), float(nbytes)
