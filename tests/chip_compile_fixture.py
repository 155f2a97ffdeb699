"""The described v5e of the ``test_chip_compile_*`` files (on-chip-measurement
guide, section 2): imported, not collected. Nothing runs: the tests say that
the chip's compiler accepts the kernels at the cells' published widths, with
the scoped-VMEM flag ``import areal_tpu`` puts into ``LIBTPU_INIT_ARGS``;
results and times come only from the chip. One file would be one worker's
(``--dist loadfile``), so what is compiled is split over files of even cost.

The topology is described inside a module-scoped fixture, never while a
module is imported, and every compile happens in the test's own process,
with the persistent compile cache off (a described-chip entry can be written
but not read back without the chip).
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    # the flag must be in the environment before the TPU library loads
    import areal_tpu  # noqa: F401

    assert "xla_tpu_scoped_vmem_limit_kib" in os.environ["LIBTPU_INIT_ARGS"]
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
