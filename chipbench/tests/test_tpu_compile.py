"""Each configuration's served block call compiles for a described v5e at
the largest bucket its cells warm, and fits the chip with three services'
weights beside it.  No chip is needed; the test skips where the topology
cannot be described.

    python -m pytest -q chipbench/tests/test_tpu_compile.py -s
"""
import json
import os

import pytest

from chipbench import harness

CHIP_BYTES = 16e9


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


@pytest.mark.parametrize("config,bucket", [("gdm-dit", 56),
                                           ("dit-xl2-512", 56)])
def test_block_call_compiles_and_fits(one_chip, config, bucket):
    import jax
    import jax.numpy as jnp
    from repro.models.gdm import init_gdm, make_schedule
    from repro.serving.gdm_service import block_runner

    conf = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    mcfg = harness.model_config(conf)
    blocks = mcfg.gdm_blocks
    runner = block_runner(mcfg, make_schedule(blocks), steps_per_block=1,
                          total_steps=blocks, impl="pallas")
    params = jax.eval_shape(lambda: init_gdm(jax.random.PRNGKey(0), mcfg))

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    hw2 = mcfg.latent_hw ** 2
    args = (jax.tree_util.tree_map(shaped, params),
            shaped(jax.ShapeDtypeStruct((bucket, hw2, 4), jnp.float32)),
            shaped(jax.ShapeDtypeStruct((bucket, 8), jnp.int32)),
            shaped(jax.ShapeDtypeStruct((bucket,), jnp.int32)))
    compiled = runner.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    weights = mem.argument_size_in_bytes
    total = conf["services"] * weights + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    print(json.dumps({"config": config, "bucket": bucket,
                      "argument_bytes": weights,
                      "temp_bytes": mem.temp_size_in_bytes,
                      "output_bytes": mem.output_size_in_bytes,
                      "services_plus_bucket_bytes": total,
                      "tpu_custom_calls": text.count(
                          'custom_call_target="tpu_custom_call"')}))
    assert 'custom_call_target="tpu_custom_call"' in text
    assert total < CHIP_BYTES
