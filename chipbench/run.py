"""The chip benchmark's entry: one run of one cell, one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (Ω, the policy, every bucket's compile) comes first and is
``setup_s``; then the window serves the cell's traffic for ``--seconds``;
then the served block calls are checked against the plain reference.  The
last line on standard output is the result; the numbers the check compared
are the last lines on standard error.  Off a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def configure() -> None:
    """Import paths and the persistent compile cache.  The cache lives at
    one fixed path in the checkout, so that every run after a cell's first
    finds its programs; the program's entry points take it from
    ``JAX_COMPILATION_CACHE_DIR``.  Every program is cached, however short
    its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure()
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       started=STARTED)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
