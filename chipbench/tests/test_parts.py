"""A configuration names its architecture's parts, and the harness reaches
them only through it: the check's reference (``reference``), the readers'
count of work (``work``), and the warm-up's requests (the service's own
``init_state``).

* the pin: on the calls of a small window, the check through the loader
  reads exactly what the DiT reference called directly on the stacked
  latent and prompt reads, in the fixed blocks the check has always used;
* a stub architecture whose requests carry a caption and its mask: every
  array of a request but ``x0`` reaches the reference, pad rows filled;
* the warm-up compiles every bucket from the service's own requests, and
  no warm call is a window call;
* ``dit_mfu`` and the device clock's pairing read the configuration's
  work module.
"""
import json
import time
import types

import numpy as np
import pytest

from chipbench import check, harness, parts, trace
from chipbench.reference import dit
from chipbench.tests import small
from chipbench.tests.test_trace import DATA

SEED = 2**31 + 41


# -- the pin ------------------------------------------------------------------

def direct_readings(calls, conf, seed, weights_key, control=False):
    """The check's numbers as the parent computed them: ``dit.block`` on the
    stacked latent and prompt of the sampled rows, per service, in blocks
    of ``block_rows`` padded with copies of the block's first row."""
    m, chk = conf["model"], conf["check"]
    picked = check.sample(calls, chk["rows"], seed)
    by_service = {}
    for call, r in picked:
        by_service.setdefault(call["service"], []).append((call, r))
    keys = dit.service_keys(weights_key, conf["services"])

    def blocks(params, lat, prompt, idx, dtype, precision):
        n, size = len(lat), chk["block_rows"]
        outs_l, outs_x = [], []
        for a in range(0, n, size):
            sl = slice(a, min(a + size, n))
            k = sl.stop - sl.start

            def fill(x):
                return np.concatenate([x[sl],
                                       np.repeat(x[sl][:1], size - k, 0)])

            rl, rx = dit.block(params, {"latent": fill(lat),
                                        "prompt": fill(prompt)}, fill(idx),
                               m, blocks=m["gdm_blocks"], dtype=dtype,
                               precision=precision)
            outs_l.append(rl[:k])
            outs_x.append(rx[:k])
        return np.concatenate(outs_l), np.concatenate(outs_x)

    errs = {"latent_err": [], "x0_err": []}
    for sid in sorted(by_service):
        rows = by_service[sid]
        params = dit.make_params(keys[sid], m)
        lat = np.stack([c["states"][r]["latent"] for c, r in rows])
        prompt = np.stack([c["states"][r]["prompt"] for c, r in rows])
        idx = np.asarray([c["idx"][r] for c, r in rows], np.int32)
        out_lat = np.stack([c["out"][r]["latent"] for c, r in rows])
        out_x0 = np.stack([c["out"][r]["x0"] for c, r in rows])
        ref_lat, ref_x0 = blocks(params, lat, prompt, idx, "float32",
                                 chk["reference_precision"])
        if control:
            out_lat, out_x0 = blocks(params, lat, prompt, idx, "bfloat16",
                                     "default")
        errs["latent_err"].append(check.row_errors(out_lat, ref_lat, lat))
        errs["x0_err"].append(check.row_errors(out_x0, ref_x0, lat))
    return {k: float(np.max(np.concatenate(v))) for k, v in errs.items()}


@pytest.fixture(scope="module")
def window():
    """The calls of a small window of the XL cell, and what it ran on."""
    cell = small.cell()
    _, calls, weights_key = harness.serve(
        cell, SEED, 2.0, False, started=time.perf_counter(),
        require_tpu=False, fault=None)
    return cell.config, calls, weights_key


def test_the_dit_reference_takes_the_latent_and_the_prompt(window):
    conf, calls, _ = window
    assert check.reference(conf) is parts.load("reference", "dit")
    assert {tuple(check.inputs_of(s)) for c in calls for s in c["states"]} \
        == {("latent", "prompt")}


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_check_through_the_loader_reads_the_direct_reference(window,
                                                              control):
    conf, calls, weights_key = window
    got = check.compare(calls, conf, SEED, weights_key, control=control)
    want = direct_readings(calls, conf, SEED, weights_key, control=control)
    assert got["rows"] == conf["check"]["rows"]
    for k, v in want.items():
        assert got[k] == v, (k, got[k], v)


# -- a stub architecture ------------------------------------------------------

CAPTION = 6


class CaptionReference:
    """A test-only reference for requests that carry a caption and its mask
    beside the latent.  It records every block it is given."""

    def __init__(self):
        self.seen = []

    def service_keys(self, key, services):
        return [f"svc{s}" for s in range(services)]

    def make_params(self, key, m):
        return {"service": key}

    def block(self, params, inputs, block_idx, m, *, blocks, dtype,
              precision):
        self.seen.append((params["service"], inputs, np.asarray(block_idx)))
        return step(inputs, block_idx)


def step(inputs, block_idx):
    lat = inputs["latent"]
    shift = (inputs["caption"] * inputs["caption_mask"]).sum(-1) + block_idx
    return lat + shift[:, None, None], lat - shift[:, None, None]


def caption_calls(rng):
    """Block calls whose request states carry a caption, its mask, an x0
    from an earlier block on some rows, and a value that is no array."""
    calls = []
    for service, n in ((0, 5), (1, 3), (1, 4)):
        states = [{"latent": rng.standard_normal((4, 4)).astype(np.float32),
                   "caption": rng.integers(1, 9, CAPTION).astype(np.int32),
                   "caption_mask": (np.arange(CAPTION) < rng.integers(
                       1, CAPTION + 1)).astype(np.int32),
                   "x0": None if i % 2 else np.ones((4, 4), np.float32),
                   "label": "cat"} for i in range(n)]
        idx = rng.integers(0, 4, n).astype(np.int32)
        lat, x0 = step({k: np.stack([s[k] for s in states])
                        for k in ("latent", "caption", "caption_mask")}, idx)
        calls.append({"service": service, "states": states, "idx": idx,
                      "out": [{"latent": lat[i], "x0": x0[i]}
                              for i in range(n)],
                      "rows": n, "bucket": 1 << (n - 1).bit_length()})
    return calls


def test_every_request_array_reaches_the_reference(monkeypatch):
    ref = CaptionReference()
    monkeypatch.setattr(check, "reference", lambda conf: ref)
    calls = caption_calls(np.random.default_rng(3))
    conf = {"reference": "caption", "services": 2,
            "model": {"gdm_blocks": 4},
            "check": {"rows": 11, "block_rows": 4,
                      "reference_precision": "default",
                      "limits": {"latent_err": 1e-6, "x0_err": 1e-6}}}
    got = check.compare(calls, conf, 9, None)
    assert got["rows"] == 11
    assert got["latent_err"] == 0.0 and got["x0_err"] == 0.0

    picked = check.sample(calls, 11, 9)
    want = {sid: [(c, r) for c, r in picked if c["service"] == sid]
            for sid in (0, 1)}
    for sid, rows in want.items():
        blocks = [(i, idx) for s, i, idx in ref.seen if s == f"svc{sid}"]
        live = 0
        for inputs, idx in blocks:
            assert set(inputs) == {"latent", "caption", "caption_mask"}
            assert all(len(v) == 4 for v in inputs.values()) and len(idx) == 4
            k = min(4, len(rows) - live)
            for j in range(4):
                c, r = rows[live + (j if j < k else 0)]
                for key, v in inputs.items():
                    np.testing.assert_array_equal(v[j], c["states"][r][key])
                assert idx[j] == c["idx"][r]
            live += k
        assert live == len(rows)
    # 5 rows of service 0 and 6 of service 1 in blocks of 4: 2 blocks each
    assert len(ref.seen) == 4


# -- the warm-up --------------------------------------------------------------

class CaptionService:
    """A service whose requests carry a caption and its mask beside the
    latent; it logs every batch it runs."""

    def __init__(self):
        self.batches = []

    def _bucket(self, b):
        return 1 << (b - 1).bit_length()

    def init_state(self, rng):
        return {"latent": rng.standard_normal((4, 4)).astype(np.float32),
                "caption": rng.integers(1, 9, CAPTION).astype(np.int32),
                "caption_mask": np.ones(CAPTION, np.int32), "x0": None}

    def run_batch(self, states, block_idxs):
        self.batches.append((states, np.asarray(block_idxs)))
        return [dict(s, x0=s["latent"]) for s in states], \
            np.ones(len(states))


def test_warm_up_runs_every_bucket_on_the_services_own_requests():
    services = {0: CaptionService(), 1: CaptionService()}
    fleet = types.SimpleNamespace(
        cells=[types.SimpleNamespace(service_of=np.array([0, 1, 0, 0, 1]))],
        num_cells=3)
    warmed = harness.warm_buckets(services, fleet, 16, SEED)
    # 3 and 2 UEs a cell over 3 cells: up to 9 and 6 rows a call
    assert warmed == {0: [1, 2, 4, 8, 16], 1: [1, 2, 4, 8]}
    for sid, svc in services.items():
        assert [len(s) for s, _ in svc.batches] == warmed[sid]
        for states, idx in svc.batches:
            assert all(set(s) == {"latent", "caption", "caption_mask", "x0"}
                       for s in states)
            assert idx.dtype == np.int32 and not idx.any()

    # as in the run: the Recorder wraps the warmed services, and only a
    # call made while the window captures reaches its calls
    rec = harness.Recorder(1.0, traced=False)
    for sid, svc in services.items():
        rec.wrap_service(sid, svc)
    assert rec.calls == []
    rec.capture = True
    rng = np.random.default_rng(0)
    services[1].run_batch([services[1].init_state(rng) for _ in range(3)],
                          np.array([0, 1, 2]))
    assert [(c["service"], c["rows"], c["bucket"]) for c in rec.calls] \
        == [(1, 3, 4)]


def test_warm_up_refuses_more_rows_than_the_traffic_allows():
    fleet = types.SimpleNamespace(
        cells=[types.SimpleNamespace(service_of=np.zeros(5, int))],
        num_cells=4)
    with pytest.raises(ValueError, match="max_bucket 16"):
        harness.warm_buckets({0: CaptionService()}, fleet, 16, SEED)


# -- the work count -----------------------------------------------------------

def test_dit_mfu_reads_the_configurations_work():
    class Ctx:
        model = {"scale": 3}
        work = types.SimpleNamespace(
            flops_per_sample_step=lambda m: 2e9 * m["scale"])
        steps_per_block = 2
        window_s = 4.0
        calls = [{"rows": 5, "bucket": 8}, {"rows": 2, "bucket": 2}]

        def peak(self, key):
            return 1e12

    assert harness.read_metric("dit_mfu", Ctx()) == pytest.approx(
        100.0 * 7 * 2 * 6e9 / (4.0 * 1e12))


@pytest.mark.parametrize("programs,paired", [(("jit_run",), True),
                                             (("jit_other",), False)],
                         ids=["pairs", "no-program"])
def test_clock_pairing_reads_the_works_programs(monkeypatch, programs,
                                                paired):
    if not DATA.exists():
        pytest.skip("no recorded profile")
    work = types.SimpleNamespace(MODULES=programs)
    load = parts.load
    monkeypatch.setattr(harness.parts, "load",
                        lambda kind, name: work if kind == "kernels"
                        and name == "stub" else load(kind, name))
    conf = {"model": small.SMALL_MODEL, "work": "stub"}
    ctx = harness.Context(
        profile=trace.load(str(DATA)), rec=types.SimpleNamespace(calls=[]),
        cell=types.SimpleNamespace(config=conf), device={"kind": "x"},
        before={}, after={}, compiles=0, spb=1)
    assert ctx.work is work
    (lag,) = ctx.lag.values()
    assert (lag is not None) == paired


# -- a configuration without its parts ----------------------------------------

@pytest.mark.parametrize("key", harness.PART_KEYS)
def test_a_configuration_must_name_its_parts(tmp_path, key):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = bench["configs"][0]
    conf = harness.load_json(harness.ROOT / entry["file"])
    del conf[key]
    (tmp_path / "conf.json").write_text(json.dumps(conf))
    entry["file"] = str(tmp_path / "conf.json")
    (tmp_path / "bench.json").write_text(json.dumps(bench))
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == entry["name"])
    with pytest.raises(ValueError, match=f"names no {key!r}"):
        harness.load_cell(cell, tmp_path / "bench.json")


def test_every_configuration_names_parts_that_exist():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for entry in bench["configs"]:
        conf = harness.load_json(harness.ROOT / entry["file"])
        ref = parts.load("reference", conf["reference"])
        work = parts.load("kernels", conf["work"])
        assert all(callable(getattr(ref, f)) for f in
                   ("service_keys", "make_params", "block"))
        assert callable(work.flops_per_sample_step) and work.MODULES
