"""A cell of the benchmark at a size a CPU test can hold: the real
traffic files and harness, a DiT with the configuration's structure at
small widths, and fewer cells and frames."""
from __future__ import annotations

import copy
import time

from chipbench import harness

SMALL_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 4, "head_dim": 16, "d_ff": 256,
               "vocab_size": 128, "latent_hw": 8}


def cell(name: str = "dit-xl2-512.fleet8-deep", cells: int = 2,
         frames: int = 3000) -> harness.Cell:
    c = harness.load_cell(name)
    c.config = copy.deepcopy(c.config)
    c.config["model"].update(SMALL_MODEL)
    c.config["check"].update(rows=24, block_rows=8)
    c.traffic = dict(c.traffic, cells=cells, frames=frames,
                     train_episodes=8, train_envs=8)
    return c


def run(c: harness.Cell, seed: int, seconds: float = 2.0,
        traced: bool = False, **kw) -> dict:
    return harness.run(c, seed, seconds, traced,
                       started=time.perf_counter(), require_tpu=False, **kw)


def cpu_peaks(monkeypatch=None):
    """Give the CPU a made-up entry of peaks, so that a traced rehearsal's
    readers run through.  Its numbers are never a device metric."""
    table = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

    def peak(self, key):
        return table[key]

    if monkeypatch is not None:
        monkeypatch.setattr(harness.Context, "peak", peak)
    else:
        harness.Context.peak = peak
