"""Compiles for a described TPU v5e 2x2 host — no chip is needed.

Interpret mode cannot check what only the TPU compiler does: Mosaic's
tiling rules for the kernels' blocks, the device's memory, and the rule
that a Pallas call must sit in a ``shard_map`` manual over every mesh axis.
These tests compile the wire kernels at smollm-360m's wire width on one
described chip, and the spmd step over the four described chips.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            try:
                desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model_d():
    """smollm-360m's spmd wire width (lane-padded flat parameter count)."""
    from repro.configs import get_config
    from repro.core.aggregator import wire_unraveler
    from repro.models.lm import build_model

    shapes = jax.eval_shape(build_model(get_config("smollm-360m")).init, jax.random.PRNGKey(0))
    return wire_unraveler(shapes)[1]


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_coded_reduce_compiles_at_model_width(one_chip, model_d):
    from repro.kernels.coded_reduce import coded_reduce_pallas

    g = jax.ShapeDtypeStruct((5, model_d), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip)
    ma = _compile(coded_reduce_pallas, g, w).memory_analysis()
    assert ma.output_size_in_bytes >= 4 * model_d  # (D,) f32, tile-padded


def test_fused_int8_encode_compiles_at_model_width(one_chip, model_d):
    from repro.kernels.wire import coded_encode_int8_pallas

    g = jax.ShapeDtypeStruct((5, model_d), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip)
    err = jax.ShapeDtypeStruct((model_d,), jnp.float32, sharding=one_chip)
    _compile(coded_encode_int8_pallas, g, w, err)


def test_int8_decode_compiles_at_model_width(one_chip, model_d):
    from repro.kernels.wire import coded_decode_int8_pallas

    q = jax.ShapeDtypeStruct((4, model_d), jnp.int8, sharding=one_chip)
    ws = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip)
    ma = _compile(coded_decode_int8_pallas, q, ws).memory_analysis()
    assert ma.output_size_in_bytes >= 4 * model_d  # (D,) f32, tile-padded


@pytest.mark.parametrize("compress", [False, True], ids=["f32_psum", "int8_wire_kernels"])
def test_spmd_step_compiles_on_four_chips(topo, compress):
    """faithful_spmd_step over a (4, 1) mesh of described chips: both wire
    paths' Pallas kernels compile inside the shard_map."""
    from repro.core.aggregator import faithful_spmd_step, wire_unraveler

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rep, dp = NamedSharding(mesh, PartitionSpec()), NamedSharding(mesh, PartitionSpec("data"))

    def loss_fn(params, slot):
        h = jnp.tanh(slot["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"])[:, 0] - slot["y"]) ** 2

    shapes = {"w1": jax.ShapeDtypeStruct((64, 96), jnp.float32),
              "w2": jax.ShapeDtypeStruct((96, 1), jnp.float32)}
    D = wire_unraveler(shapes)[1]
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep) for k, v in shapes.items()}
    m, n_slots, mb = 4, 3, 2
    slots = {"x": jax.ShapeDtypeStruct((m, n_slots, mb, 64), jnp.float32, sharding=dp),
             "y": jax.ShapeDtypeStruct((m, n_slots, mb), jnp.float32, sharding=dp)}
    coeff = jax.ShapeDtypeStruct((m, n_slots), jnp.float32, sharding=dp)
    a = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=dp)
    err = jax.ShapeDtypeStruct((m, D if compress else 1), jnp.float32, sharding=dp)
    step = faithful_spmd_step(loss_fn, mesh, ("data",), compress=compress,
                              wire_kernel=compress)
    compiled = _compile(step, params, slots, coeff, a, err)
    if compress:
        assert "all-gather" in compiled.as_text()
    else:
        assert "all-reduce" in compiled.as_text()
