"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (tiling, VMEM
budget, Mosaic lowering) — which interpret-mode tests cannot see.  Nothing
runs, so these say nothing about results or times.  The topology is
described inside a fixture (never at import), so every xdist worker
collects the same tests and only the worker given this file loads libtpu.
"""
import os

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

#: rwkv6-3b's fused-ring chunk matmul: w_ck [2560, 8960] over a 2-way data
#: (K) and 2-way model (N) axis, at 2048 tokens (chip_smoke.CHUNK_MKN)
CHUNK_MKN = (2048, 1280, 4480)
#: one rwkv6-3b activation block on the wire: 2048 tokens x d_model
WIRE_ND = (2048, 2560)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_pallas_matmul_compiles_at_rwkv6_chunk(one_chip):
    from repro.kernels.collective_matmul import pallas_matmul
    m, k, n = CHUNK_MKN
    compiled = pallas_matmul.lower(
        _sds((m, k), "bfloat16", one_chip), _sds((k, n), "bfloat16", one_chip),
        interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("wire_dtype", ["int8", "float8_e4m3fn"])
def test_quant_pack_compiles(one_chip, wire_dtype):
    from repro.kernels.quant import quant_pack
    compiled = quant_pack.lower(_sds(WIRE_ND, "bfloat16", one_chip),
                                wire_dtype=wire_dtype).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("wire_dtype", ["int8", "float8_e4m3fn"])
def test_dequant_unpack_compiles(one_chip, wire_dtype):
    from repro.kernels.quant import BLOCK_ROWS, dequant_unpack
    n, d = WIRE_ND
    compiled = dequant_unpack.lower(
        _sds((n, d), wire_dtype, one_chip),
        _sds((n // BLOCK_ROWS, 1), "float32", one_chip),
        out_dtype=jnp.bfloat16).compile()
    assert _has_kernel(compiled)


def test_quant_pack_pads_ragged_rows(one_chip):
    """A row count off the scale-block grid is padded before the kernel."""
    from repro.kernels.quant import quant_pack
    compiled = quant_pack.lower(_sds((2045, 2560), "float32", one_chip),
                                wire_dtype="int8").compile()
    assert _has_kernel(compiled)


def test_rdma_ring_compiles_on_four_chips(topo):
    """The in-kernel RDMA ring lowers for a 4-chip axis at a size whose
    operands, output and gathered copy all fit VMEM (the kernel keeps them
    whole there, so model widths do not)."""
    from jax import shard_map
    from repro.kernels.collective_matmul_rdma import \
        ring_allgather_matmul_rdma
    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    n, k, m = 128, 256, 512

    def body(x, w):
        return ring_allgather_matmul_rdma(x, w, "x")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                           out_specs=P("x"), check_vma=False))
    compiled = fn.lower(
        _sds((4 * n, k), "bfloat16", NamedSharding(mesh, P("x"))),
        _sds((k, m), "bfloat16", NamedSharding(mesh, P()))).compile()
    assert _has_kernel(compiled)
