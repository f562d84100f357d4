"""Programs of the device path compiled for a described TPU v5e, at the sizes
the benchmark runs: what the chip's compiler makes of them, without a chip.

Nothing runs here, so nothing is timed. The topology is described inside a
fixture, never while a module is imported: only one process at a time may
load the TPU's library, and under several test workers every worker imports
every test file. Keep compiles for a described chip in this one file."""

from __future__ import annotations

import numpy as np
import pytest

import cubed_tpu.runtime.executors.jax as jx


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a chip that is not attached cannot be read
    # back from the persistent cache; keep these out of it
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


def _plane_layouts(compiled):
    first, second, _ = compiled.output_formats
    assert first.layout == second.layout
    return first.layout.major_to_minor


#: a 200 MB chunk of ``zarr-add.rechunk``'s target, one of ``zarr-add.store``'s,
#: and one whose rows are too short to be laid out row-major on the chip
SLAB, BLOCK, NARROW = (10000, 2500), (5000, 5000), (3125000, 8)


@pytest.mark.parametrize("dtype", [np.uint64, np.float64])
@pytest.mark.parametrize("shape", [SLAB, BLOCK], ids=["slab", "block"])
def test_the_planes_of_a_chunk_leave_the_chip_row_major(shape, dtype, one_chip):
    nbytes = int(np.prod(shape)) * 8
    compiled, held = jx._plane_program(shape, np.dtype(dtype), one_chip)
    assert _plane_layouts(compiled) == (0, 1)
    # the value and its planes; for the slab a relayout's temporary too, which
    # the device's own column-major choice for that shape would have saved
    least = 3 if shape == SLAB else 2
    assert least * nbytes <= held <= (least + 0.1) * nbytes
    assert held == jx._hbm_footprint(compiled)


def test_planes_with_short_rows_keep_the_layout_the_chip_chose(one_chip):
    nbytes = int(np.prod(NARROW)) * 8
    compiled, held = jx._plane_program(NARROW, np.dtype(np.uint64), one_chip)
    # row-major, each row of 8 would pad to 128: 6.6 GB on the chip for 200 MB
    assert _plane_layouts(compiled) == (1, 0)
    assert 2 * nbytes <= held <= 2.1 * nbytes
