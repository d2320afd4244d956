"""The benchmark's parts, found by name: ``<kind>/<name>.py`` under
``chipbench/``.

* ``metrics/<metric>.py``: a per-layer reader, named in ``BENCHMARK.json``;
* ``kernels/<name>.py``: a count of work from shapes, named by a reader or
  by a configuration's ``work`` key;
* ``reference/<name>.py``: a plain reference, named by a configuration's
  ``reference`` key.

Each file is loaded once per process, so a reference's jitted functions
trace once however often the check runs.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.cache
def load(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"chipbench: no {kind} part {name!r} "
                                f"({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
