"""Compile the five Pallas kernels for a described TPU v5e, at the
widths of the models that use them (``repro.kernels.cases``).

Nothing runs: the TPU compiler, which is installed with jaxlib, compiles
each kernel with ``interpret=False`` for a chip that is described, not
attached.  This catches what interpret mode hides — block shapes that
break the tiling rules, primitives Mosaic cannot lower, unaligned
loads.  The topology is described inside a fixture (never at import),
so only the worker that runs these tests loads the TPU library.
"""
import functools
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import CASE_NAMES, kernel_case


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_v5e(one_chip, name):
    case = kernel_case(name)
    fn = jax.jit(functools.partial(case.run, interpret=False))
    compiled = fn.lower(*case.specs(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
