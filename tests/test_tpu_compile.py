"""Ahead-of-time compiles of the DiT hot path for a described TPU v5e.

The TPU compiler is installed on CPU-only hosts too: it compiles for a chip
that is described, not attached, and refuses what the chip would refuse — a
block shape the tiling cannot take, too much fast memory — which the
interpret-mode tests in ``test_kernels.py`` cannot see.  Nothing runs, so
these pin compilability only; results are pinned by the interpret tests and
on the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.gdm import LATENT_CHANNELS, init_gdm, make_schedule
from repro.rl.d3ql import D3QLAgent, D3QLConfig
from repro.serving.gdm_service import block_runner

CFG = get_config("gdm-dit")                 # full width: 12 x d768, 16x16
TOKENS = CFG.latent_hw ** 2
KERNEL_NAMES = ("adaln_norm", "adaln_norm_epilogue", "flash_attention",
                "gdm_block")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b", [1, 8, 64])
@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "epilogue"])
def test_adaln_norm_compiles_for_v5e(one_chip, b, epilogue):
    d = CFG.d_model
    row, vec = _spec(one_chip, b, TOKENS, d), _spec(one_chip, b, 1, d)
    prm = _spec(one_chip, d)
    if epilogue:
        fn = jax.jit(lambda x, sh, sc, w, bias, g, r: ops.adaln_norm(
            x, sh, sc, w, bias, g, r, impl="pallas"))
        args = (row, vec, vec, prm, prm, vec, row)
    else:
        fn = jax.jit(lambda x, sh, sc, w, bias: ops.adaln_norm(
            x, sh, sc, w, bias, impl="pallas"))
        args = (row, vec, vec, prm, prm)
    text = fn.lower(*args).compile().as_text()
    name = "adaln_norm_epilogue" if epilogue else "adaln_norm"
    assert "tpu_custom_call" in text and name in text


def test_flash_attention_noncausal_compiles_for_v5e(one_chip):
    qkv = _spec(one_chip, 8, TOKENS, CFG.num_heads, CFG.head_dim)
    fn = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=False,
                                                     impl="pallas"))
    text = fn.lower(qkv, qkv, qkv).compile().as_text()
    assert "tpu_custom_call" in text and "flash_attention" in text


def _compile_block_call(bucket, params_sharding, row_sharding, mesh=None):
    """The served block call at full width, as ``GDMService`` builds it."""
    params = jax.tree_util.tree_map(
        lambda s: _spec(params_sharding, *s.shape, dtype=s.dtype),
        jax.eval_shape(lambda k: init_gdm(k, CFG), jax.random.PRNGKey(0)))
    runner = block_runner(CFG, make_schedule(4), steps_per_block=1,
                          total_steps=4, impl="pallas", mesh=mesh)
    return runner.lower(
        params, _spec(row_sharding, bucket, TOKENS, LATENT_CHANNELS),
        _spec(row_sharding, bucket, 8, dtype=jnp.int32),
        _spec(row_sharding, bucket, dtype=jnp.int32)).compile()


def test_run_block_batched_pallas_compiles_for_v5e(one_chip):
    compiled = _compile_block_call(8, one_chip, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in KERNEL_NAMES:
        assert name in text, name
    # the weights are arguments, not constants baked into the program
    assert compiled.memory_analysis().argument_size_in_bytes > 600e6


def test_sharded_block_call_compiles_for_v5e_2x2(topo):
    """The fleet's batch over four chips: Pallas kernels only partition
    under shard_map, so this is the mesh path of ``GDMService``."""
    mesh = Mesh(np.array(topo.devices), ("batch",))
    compiled = _compile_block_call(32, NamedSharding(mesh, jax.P()),
                                   NamedSharding(mesh, jax.P("batch")),
                                   mesh=mesh)
    assert "tpu_custom_call" in compiled.as_text()


def _multiply_reduces(text):
    """The program's fused multiply-reduces (its matmuls on the vector
    unit), names and metadata dropped: what decides their arithmetic."""
    out = set()
    for body in re.findall(r"^%\S+ \(.*?\{\n(.*?)\n\}", text, re.M | re.S):
        if " reduce(" in body and "multiply(" in body:
            out.add(re.sub(r"%[\w.\-]+", "%",
                           re.sub(r", metadata=\{.*", "", body)))
    return out


def test_pooled_policy_act_lowers_like_one_cell_acts(one_chip):
    """A fleet's pooled act (``D3QLAgent.act_rows``) runs the forward one
    cell at a time inside one program: the compiler lowers a one-row
    matmul to an f32 multiply-reduce but an 8-row one to the MXU at
    default precision, so only the one-row program gives every cell the
    Q-values, hence the actions, of its own act.  Shapes of the
    paper-fig3 fleet (15 UEs, 16 nodes)."""
    agent = D3QLAgent(D3QLConfig(obs_dim=302, num_ues=15, num_actions=17))
    params = jax.tree_util.tree_map(lambda a: _spec(one_chip, *a.shape),
                                    agent.params)

    def text(fn, envs):
        obs = _spec(one_chip, envs, agent.cfg.history, agent.cfg.obs_dim)
        return fn.lower(params, obs).compile().as_text()

    alone, rows = text(agent._qvals, 1), text(agent._qvals_rows, 8)
    assert "convolution(" not in rows
    assert _multiply_reduces(rows) == _multiply_reduces(alone) != set()
    assert "convolution(" in text(agent._qvals, 8)
