"""Record the small TPU profile that ``test_trace.py`` reads, and print
how the trace names what it holds.  Run on a chip:

    python3 chipbench/tests/record_profile.py [out.xplane.pb]

It serves two block calls of a small DiT (Pallas kernels) inside host
spans, with a host span around a 20 ms sleep between them, and copies the
profile to ``chipbench/tests/data/tpu_small.xplane.pb`` (or the path
given).
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

OUT = ROOT / "chipbench" / "tests" / "data" / "tpu_small.xplane.pb"


def main() -> int:
    import jax
    import numpy as np
    from chipbench import harness, trace
    from chipbench.tests import small
    from repro.serving.gdm_service import GDMService

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_profile: needs a TPU")
    conf = harness.load_json(ROOT / "chipbench" / "configs"
                             / "gdm-dit.json")
    conf["model"].update(small.SMALL_MODEL)
    svc = GDMService(jax.random.PRNGKey(0), model_cfg=harness.model_config(
        conf), impl="pallas")
    rng = np.random.default_rng(0)
    states = [svc.init_state(rng) for _ in range(4)]
    idx = np.arange(4) % 4
    svc.run_batch(states, idx)                        # compile
    log_dir = tempfile.mkdtemp(prefix="chipbench-probe-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.SPAN + "ClusterEngine.step"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace.SPAN + "run_batch"):
                states, _ = svc.run_batch(states, idx)
            with jax.profiler.TraceAnnotation(trace.SPAN + "sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace.find(log_dir)
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    print(f"profile: {out} ({out.stat().st_size} bytes)")

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(out))
    for plane in data.planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print("plane", plane.name, lines)
        for ln in plane.lines:
            seen = set()
            for e in ln.events:
                if e.name in seen or len(seen) >= 25:
                    continue
                seen.add(e.name)
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                print("  ", ln.name, "|", e.name, e.start_ns, e.duration_ns,
                      json.dumps(stats, default=str)[:600])
    prof = trace.load(str(out))
    for name, ops in prof.ops.items():
        print("ops", name, len(ops))
    for s in prof.spans:
        inside = [e.name for ops in prof.ops.values() for e in ops
                  if s.start <= e.start < s.end]
        print("span", s.name, s.start, s.end, len(inside), inside[:8])
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
