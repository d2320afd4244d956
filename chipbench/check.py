"""Whether the window's block calls are right: the served outputs against
the configuration's plain reference, ``reference/<reference>.py``.

After the window, rows are drawn from the calls the window made: the call
with the most rows first, then calls in an order drawn from the seed,
every live row of each, until the configuration's ``check.rows``.  Each
row is the reference's input as the program had it (every array of the
request's state but the output ``x0``, and the block index) and the
program's output as ``run_batch`` wrote it back to the request (new
latent and x0 estimate).

A reference module gives three functions:

* ``service_keys(key, services)``: each service's key for its weights;
* ``make_params(key, m)``: a service's weights, drawn again from its key;
* ``block(params, inputs, block_idx, m, *, blocks, dtype, precision)``:
  one block for each row, ``inputs`` a dict of the rows' stacked arrays;
  returns (latent after the block, x0 estimate) as numpy.

For each row the error of an output is the distance from the reference's
output over the distance the reference moved it from the input latent:

    err(row) = |out - ref|_2 / |ref - latent_in|_2

and the numbers compared are the largest over the rows, ``latent_err``
and ``x0_err``.  A row that is left as it came in reads 1.  ``control``
puts the reference itself, computed in bfloat16, in the program's place.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import parts, seeds


def reference(conf: dict):
    """The configuration's plain reference module."""
    return parts.load("reference", conf["reference"])


def inputs_of(state: dict) -> List[str]:
    """The keys of a request's state that the reference takes: every
    array but the output ``x0``."""
    return [k for k, v in state.items()
            if k != "x0" and isinstance(v, np.ndarray)]


def sample(calls: List[dict], rows: int, seed: int) -> List[tuple]:
    """(call, row) pairs: the largest call's rows first, then calls in a
    seeded order."""
    if not calls:
        return []
    first = max(range(len(calls)), key=lambda i: calls[i]["rows"])
    rest = np.random.default_rng(seeds.stream(seed, "check")).permutation(
        [i for i in range(len(calls)) if i != first])
    out = []
    for i in [first, *rest.tolist()]:
        for r in range(calls[i]["rows"]):
            if len(out) == rows:
                return out
            out.append((calls[i], r))
    return out


def row_errors(out: np.ndarray, ref: np.ndarray, base: np.ndarray
               ) -> np.ndarray:
    n = len(out)
    num = np.linalg.norm((out.astype(np.float64) - ref).reshape(n, -1), axis=1)
    den = np.linalg.norm((ref.astype(np.float64) - base).reshape(n, -1),
                         axis=1)
    err = num / np.maximum(den, 1e-30)
    return np.where(np.isfinite(err), err, np.inf)


def compare(calls: List[dict], conf: dict, seed: int, weights_key, *,
            control: bool = False,
            precision: str = "") -> Dict[str, object]:
    m = conf["model"]
    chk = conf["check"]
    picked = sample(calls, chk["rows"], seed)
    by_service: Dict[int, List[tuple]] = {}
    for call, r in picked:
        by_service.setdefault(call["service"], []).append((call, r))
    ref = reference(conf)
    keys = ref.service_keys(weights_key, conf["services"])
    errs = {"latent_err": [], "x0_err": []}
    block = chk["block_rows"]
    for sid in sorted(by_service):
        rows = by_service[sid]
        params = ref.make_params(keys[sid], m)
        first_call, first_row = rows[0]
        inputs = {k: np.stack([c["states"][r][k] for c, r in rows])
                  for k in inputs_of(first_call["states"][first_row])}
        lat = inputs["latent"]
        idx = np.asarray([c["idx"][r] for c, r in rows], np.int32)
        out_lat = np.stack([c["out"][r]["latent"] for c, r in rows])
        out_x0 = np.stack([c["out"][r]["x0"] for c, r in rows])
        ref_lat, ref_x0 = _blocks(ref, params, inputs, idx, m, block,
                                  m["gdm_blocks"], "float32",
                                  precision or chk["reference_precision"])
        if control:
            out_lat, out_x0 = _blocks(ref, params, inputs, idx, m, block,
                                      m["gdm_blocks"], "bfloat16", "default")
        del params
        errs["latent_err"].append(row_errors(out_lat, ref_lat, lat))
        errs["x0_err"].append(row_errors(out_x0, ref_x0, lat))
    result: Dict[str, object] = {
        k: float(np.max(np.concatenate(v))) if v else float("inf")
        for k, v in errs.items()}
    result["rows"] = len(picked)
    result["buckets"] = sorted({c["bucket"] for c, _ in picked})
    return result


def _blocks(ref, params, inputs, idx, m, block, blocks, dtype, precision):
    """The reference over the rows in fixed blocks of ``block`` rows (the
    last padded with copies of its first row, in every input), so that it
    compiles once and fits."""
    n = len(idx)
    outs_l, outs_x = [], []
    for a in range(0, n, block):
        sl = slice(a, min(a + block, n))
        k = sl.stop - sl.start
        pad = block - k

        def fill(x):
            return np.concatenate([x[sl], np.repeat(x[sl][:1], pad, axis=0)])

        rl, rx = ref.block(params, {k: fill(v) for k, v in inputs.items()},
                           fill(idx), m, blocks=blocks, dtype=dtype,
                           precision=precision)
        outs_l.append(rl[:k])
        outs_x.append(rx[:k])
    return np.concatenate(outs_l), np.concatenate(outs_x)
