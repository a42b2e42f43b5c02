"""The main path's Pallas kernels at full width, compiled for a described
TPU v5e chip (nothing runs; the TPU compiler refuses what the chip would).

Covers Algorithm 3's ``fill_aggregate`` at m = 16 uploads of the paper's
full 12-block CIFAR master and the ``int8:pallas`` codec's
quantize/dequantize at the same length, and asserts each compiles to a
Mosaic ``tpu_custom_call`` — i.e. the kernel is compiled, not
interpreted.  The topology is described inside a module fixture, never at
import: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import fill_aggregate as _fa
from repro.kernels import quantize as _q
from repro.models import cnn

M_UPLOADS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def full_p():
    """Parameter count of the full-width master (shapes only)."""
    cfg = get_config("cifar-supernet")
    shapes = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_full_width_master_size(full_p):
    assert full_p == 26_119_059


def test_fill_aggregate_compiles_for_v5e(one_chip, full_p, no_compile_cache):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = compiled_text(
        lambda c, m, w, p: _fa.fill_aggregate(c, m, w, p, interpret=False),
        spec((M_UPLOADS, full_p)), spec((M_UPLOADS, full_p)),
        spec((M_UPLOADS,)), spec((full_p,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["quantize", "dequantize"])
def test_int8_codec_kernels_compile_for_v5e(one_chip, full_p,
                                            no_compile_cache, direction):
    if direction == "quantize":
        fn = lambda x, s: _q.quantize_int8(x, s, interpret=False)
        dtype = jnp.float32
    else:
        fn = lambda q, s: _q.dequantize_int8(q, s, interpret=False)
        dtype = jnp.int8
    text = compiled_text(
        fn, jax.ShapeDtypeStruct((full_p,), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text
