"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the Mosaic compiler refuses misaligned blocks, too much VMEM and
unsupported ops here, at no chip time. Widths: ``repro.kernels.chip_cases``
(the same cases ``chip_smoke.py`` runs on the chip).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chip_cases import chip_cases

CASE_NAMES = ["flash_attention[smollm-360m]", "flash_attention[internlm-7b]",
              "ssd[mamba2-1.3b]"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def test_case_names_cover_the_chip_cases():
    assert [c.name for c in chip_cases()] == CASE_NAMES


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    case = next(c for c in chip_cases() if c.name == name)
    shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
              for s in case.shapes]
    fn = jax.jit(lambda *a: case.kernel(*a, interpret=False))
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
