"""What only the chip's compiler can say, asked of it without a chip: the
TPU compiler is installed here and compiles for a described v5e. These are
compiles, not runs: no time, no result. One file, the topology described in
a fixture, so that only the worker that runs this file loads libtpu."""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("side_in,temporaries", [(224, False), (256, True)],
                         ids=["same_size", "resized"])
def test_resize_program_crosses_the_host_boundary_in_row_order(one_chip, side_in, temporaries):
    """``device_get`` returns a device layout as strides, and the TPU keeps
    a 4-D image batch with its batch dimension minor-most, so a 4-D result
    reaches the host with each image scattered across the buffer (PERF.md,
    PR 26). The stage program therefore takes and returns (rows, H*W*C):
    both sides row-major, and no temporary where no shape changes."""
    import jax

    from mmlspark_tpu.image import ImageTransformer

    shape = (512, side_in, side_in, 3)
    _, run = ImageTransformer(toFloat=True).resize(224, 224)._pipeline()
    flat = jax.ShapeDtypeStruct((shape[0], int(np.prod(shape[1:]))), np.uint8, sharding=one_chip)
    compiled = run.lower(flat, shape).compile()
    (taken,), _ = compiled.input_formats
    assert taken.layout.major_to_minor == (0, 1)
    assert compiled.output_formats.layout.major_to_minor == (0, 1)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == shape[0] * 224 * 224 * 3 * 4
    assert (memory.temp_size_in_bytes > 0) == temporaries
