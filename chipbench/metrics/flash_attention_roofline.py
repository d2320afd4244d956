"""Kernels: the ``flash_attention`` kernel's share of its roofline."""

from chipbench import roofline


def read(ctx):
    return roofline.share(ctx, "flash_attention")
