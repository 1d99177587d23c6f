"""Compile the device kernel for a TPU v5e chip that is described, not
attached.

The TPU compiler ships with jax, so it can refuse here what the chip's
compiler would refuse there (unsupported types, layouts, memory) without a
chip.  The topology is described inside a module-scoped fixture: only one
process at a time may load the TPU library, so nothing here touches it while
the module is imported, and the tests skip where no topology can be
described.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels.finish_batch import _finish_jnp  # noqa: E402


@pytest.fixture(scope="module")
def topo():
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
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one, so keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("batch", [8, 1024, 65536])
def test_finish_kernel_compiles_for_v5e(batch, one_chip, no_persistent_cache):
    """The jitted ``finish_cost`` kernel, at the pow2 batch sizes the
    executor pads to, compiles for one v5e chip under x64."""
    with jax.enable_x64(True):
        i64 = jax.ShapeDtypeStruct((batch,), jnp.int64, sharding=one_chip)
        b = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=one_chip)
        compiled = _finish_jnp.lower(i64, i64, b, i64, i64, b, i64).compile()
    outs = compiled.out_info
    assert len(outs) == 9
    assert all(o.shape == (batch,) for o in outs)
    assert [o.dtype for o in outs] == [jnp.int64] * 5 + [jnp.bool_] * 4
