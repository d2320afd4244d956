"""Named seed streams: every random input of a run comes from ``--seed``.

``stream(seed, tag)`` maps any whole number (negative, or wider than 32
bits) and a tag to a 31-bit seed, so the weights, the latents, the policy
and the order of the cells each draw from a stream of their own.
"""
from __future__ import annotations

import zlib

import numpy as np


def stream(seed: int, tag: str) -> int:
    entropy = [abs(int(seed)), int(seed < 0), zlib.crc32(tag.encode())]
    return int(np.random.SeedSequence(entropy).generate_state(1)[0] >> 1)
