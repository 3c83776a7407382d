"""The grid kernels compile with Mosaic for a TPU v5e at real size.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached. These tests lower the three Pallas kernels of
``kernels/policy_scan.py`` with ``interpret=False`` at 4,096 scenarios x
8,736 hourly bins for one chip of a described ``v5e:2x2`` slice, and
check that the compiled program holds a Mosaic kernel
(``tpu_custom_call``) and fits the chip's 16 GB. What the interpreter
accepts and Mosaic refuses (a dynamic slice of a loaded value, an
unaligned slice, too much VMEM) fails here, with no chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.twin import (PARAM_DIM, num_policies,  # noqa: E402
                             registry_version)
from repro.kernels import policy_scan as ps  # noqa: E402

N, T = 4096, 8736
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def f32(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                               sharding=one_chip)


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _statics(**kw):
    lanes, chunk = ps.tile_plan(T, PARAM_DIM)
    return dict(dt_hours=1.0, version=registry_version(), lanes=lanes,
                chunk=chunk, interpret=False, **kw)


SLO = dict(slo_limit=4 * 3600.0, slo_mode=0)


@pytest.mark.parametrize("kernel", ["agg", "agg_fault", "scan"])
def test_grid_kernel_compiles_with_mosaic(kernel, f32, no_persistent_cache):
    stream, lanes_in = f32(T, N), (f32(N, PARAM_DIM),
                                   f32(N, num_policies()))
    if kernel == "agg":
        low = ps._policy_agg.lower(stream, *lanes_in, **_statics(**SLO))
    elif kernel == "agg_fault":
        low = ps._policy_agg_fault.lower(stream, stream, stream, *lanes_in,
                                         **_statics(**SLO))
    else:
        low = ps._policy_scan.lower(stream, *lanes_in, **_statics())
    compiled = low.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < HBM_BYTES, mem
