"""Kernels: the ``flash_attention`` kernel's share of its roofline, at the
attention shape of the configuration's work module."""

from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "flash_attention",
                          ctx.work.attention(ctx.model))
