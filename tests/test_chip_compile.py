"""The chip partition's kernel compiled for a described TPU v5e at the
paper's input i1 (m = n = k = 30000): bf16 in, f32 out, Mosaic custom call
present, program within one chip's HBM.  Nothing runs; the TPU compiler
refuses here what the chip would refuse."""
import os
import re

import jax
import ml_dtypes
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hgemms import mxu_matmul, row_major_matmul

N = K = 30_000
V5E_HBM_BYTES = 16 * 2**30
# Chip rows of i1 once the host CPU has taken its share: the whole chip
# share of a one-chip plan, and one chip's quarter of a four-chip plan.
ONE_CHIP_ROWS = 29_856
FOUR_CHIP_ROWS = 7_464
# The chip share of a one-chip-plus-CPU plan whose rows pad less than its
# columns, so the compiler on its own lays C out column-major.
COLUMN_MAJOR_ROWS = 29_552


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("rows", [ONE_CHIP_ROWS, FOUR_CHIP_ROWS],
                         ids=["one-chip-share", "four-chip-share"])
def test_mxu_partition_compiles_for_v5e(rows, one_chip, no_persistent_cache):
    bf16 = ml_dtypes.bfloat16
    compiled = jax.jit(mxu_matmul).lower(
        jax.ShapeDtypeStruct((rows, K), bf16, sharding=one_chip),
        jax.ShapeDtypeStruct((K, N), bf16, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (rows, N) and out.dtype == jax.numpy.float32
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= V5E_HBM_BYTES, total


def test_pinned_partition_returns_row_major_c_for_v5e(topo, one_chip,
                                                      no_persistent_cache):
    """The chip partition's program as ``HGemms`` runs it: C rows come out
    row-major with no relayout copy of C, in the module the benchmark's
    roofline reads (``jit_matmul``)."""
    bf16 = ml_dtypes.bfloat16
    rows = COLUMN_MAJOR_ROWS
    compiled = row_major_matmul(topo.devices[0]).lower(
        jax.ShapeDtypeStruct((rows, K), bf16, sharding=one_chip),
        jax.ShapeDtypeStruct((K, N), bf16, sharding=one_chip)).compile()
    assert compiled.output_formats.layout.major_to_minor == (0, 1)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= f32\[[^\]]*\]\S* copy\(", text)
    assert re.match(r"HloModule jit_matmul\b", text)
