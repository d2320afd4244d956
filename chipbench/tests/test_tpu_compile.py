"""Each configuration's served block call compiles for a described v5e at
the largest bucket its cells warm, and fits the chip with three services'
weights beside it.  No chip is needed; the test skips where the topology
cannot be described.  The configurations and their buckets come from
``BENCHMARK.json``; the program is the one the service builds, with its
own schedule, and its arguments come from the service's own request
state, so a new configuration is covered by its entries alone.

    python -m pytest -q chipbench/tests/test_tpu_compile.py -s
"""
import json
import os

import numpy as np
import pytest

from chipbench import check, harness

CHIP_BYTES = 16e9


def configs_and_buckets():
    """Each configuration of the benchmark with the largest bucket any of
    its cells' traffic allows."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    bucket = {}
    for w in bench["workloads"]:
        traffic = harness.load_json(harness.HERE / "traffic"
                                    / f"{w['traffic']}.json")
        bucket[w["config"]] = max(bucket.get(w["config"], 0),
                                  traffic["max_bucket"])
    return [(c, bucket[c["name"]]) for c in bench["configs"]]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def shape_only_service(monkeypatch):
    """A service as ``make_gdm_services`` builds it on Pallas kernels,
    with its weights as shapes and Ω left unmeasured: nothing is computed
    at full width."""
    import jax
    from repro.serving import gdm_service
    init = gdm_service.init_gdm
    monkeypatch.setattr(gdm_service, "init_gdm",
                        lambda key, cfg: jax.eval_shape(
                            lambda k: init(k, cfg), key))
    monkeypatch.setattr(gdm_service, "quality_per_block",
                        lambda *a, num_blocks, **k: np.zeros(num_blocks))

    def build(mcfg, steps_per_block):
        services, _ = gdm_service.make_gdm_services(
            1, jax.random.PRNGKey(0), num_blocks=mcfg.gdm_blocks,
            steps_per_block=steps_per_block, model_cfg=mcfg, impl="pallas")
        return services[0]

    return build


@pytest.mark.parametrize(
    "entry,bucket", configs_and_buckets(),
    ids=lambda v: v["name"] if isinstance(v, dict) else str(v))
def test_block_call_compiles_and_fits(one_chip, shape_only_service, entry,
                                      bucket):
    import jax

    conf = harness.load_json(harness.ROOT / entry["file"])
    svc = shape_only_service(harness.model_config(conf),
                             conf["steps_per_block"])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    # the service's own block program takes the weights, the request's
    # arrays stacked to the bucket in the order its state holds them, and
    # the block indices
    state = svc.init_state(np.random.default_rng(0))
    rows = [shaped(jax.ShapeDtypeStruct((bucket, *state[k].shape),
                                        state[k].dtype))
            for k in check.inputs_of(state)]
    args = (jax.tree_util.tree_map(shaped, svc.params), *rows,
            shaped(jax.ShapeDtypeStruct((bucket,), np.int32)))
    compiled = svc._runner.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    weights = mem.argument_size_in_bytes
    total = conf["services"] * weights + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    print(json.dumps({"config": entry["name"], "bucket": bucket,
                      "argument_bytes": weights,
                      "temp_bytes": mem.temp_size_in_bytes,
                      "output_bytes": mem.output_size_in_bytes,
                      "services_plus_bucket_bytes": total,
                      "tpu_custom_calls": text.count(
                          'custom_call_target="tpu_custom_call"')}))
    assert 'custom_call_target="tpu_custom_call"' in text
    assert total < CHIP_BYTES
