"""The simulator's device programs compile for a TPU v5e.

Nothing runs: each test lowers a jitted program with shapes placed on a
*described* v5e:2x2 topology and compiles it with the TPU compiler, which
refuses what the chip would refuse (misaligned kernel tiles, programs that
do not fit its memory, shardings it cannot partition).  The topology is
described inside a module-scoped fixture, never at import: only one
process at a time may load the TPU library, so a module that did it while
being collected would break every other test worker.  Where no topology
can be described, the fixture skips this file's tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.dcn import jax_backend as dcn_jax
from repro.runtime import make_mesh
from repro.sim import MODEL_REGISTRY, DcnSpec, make_model
from repro.sim import jax_backend as sim_jax
from repro.slo import jax_backend as slo_jax

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30
ROWS = 1024                      # the engines' default snapshot block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sharding(topo, chips: int):
    if chips == 1:
        return SingleDeviceSharding(topo.devices[0])
    mesh = make_mesh((chips,), ("snap",), devices=topo.devices[:chips])
    return NamedSharding(mesh, P("snap"))


def _sweep_program(topo, arches, nodes: int, chips: int):
    models = [make_model(a, nodes, 4) for a in arches]
    sharding = _sharding(topo, chips)
    mesh = None if chips == 1 else sharding.mesh
    fn = sim_jax._grid_fn(models, [16, 32, 64], mesh, nodes)
    arg = jax.ShapeDtypeStruct((ROWS, nodes), bool, sharding=sharding)
    return fn.lower(arg).compile()


# infinitehbd-k3 is narrowed to 4,096 nodes: its scans compile in ~11 s
# at the full 32,768
@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("arch,nodes", [("tpuv4", 32_768),
                                        ("infinitehbd-k3", 4096)])
def test_sweep_program_compiles(topo, arch, nodes, chips):
    mem = _sweep_program(topo, [arch], nodes, chips).memory_analysis()
    # the snapshot block is split over the chips, one bool byte per node
    assert mem.argument_size_in_bytes == ROWS * nodes // chips


def test_smoke_sweep_block_fits_one_chip(topo):
    """chip_smoke.py's sweep block: every architecture at 131,072 GPUs,
    1,024 snapshots (~1 GB of temporaries when first compiled)."""
    compiled = _sweep_program(topo, list(MODEL_REGISTRY), 32_768, 1)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes == ROWS * 32_768
    assert used < V5E_HBM_BYTES // 4, used


@pytest.mark.parametrize("chips", [1, 4])
def test_mask_draw_compiles(topo, chips):
    """The counter-mask draw of one sweep block at 131,072 GPUs: the
    block's keys in, its bool masks out, split over the chips."""
    sharding = _sharding(topo, chips)
    mesh = None if chips == 1 else sharding.mesh
    fn = sim_jax._draw_fn(32_768, mesh)
    keys = jax.ShapeDtypeStruct((ROWS, 2), jnp.uint32, sharding=sharding)
    one = _sharding(topo, 1) if chips == 1 else NamedSharding(mesh, P())
    thresh = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one)
    full = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one)
    mem = fn.lower(keys, thresh, full).compile().memory_analysis()
    assert mem.output_size_in_bytes == ROWS * 32_768 // chips


@pytest.mark.parametrize("chips", [1, 4])
def test_interval_build_compiles(topo, chips):
    """The churn stream's interval-mask build of one block at 131,072
    GPUs: a chunk of deltas and the carried counts in, the bool block out,
    its rows split over the chips."""
    from repro.core.trace import DELTA_CAPACITY
    sharding = _sharding(topo, chips)
    mesh = None if chips == 1 else sharding.mesh
    one = _sharding(topo, 1) if chips == 1 else NamedSharding(mesh, P())
    fn = sim_jax._interval_fn(ROWS, 32_768, mesh)
    block = jax.ShapeDtypeStruct((ROWS, 32_768), bool, sharding=sharding)
    carry = jax.ShapeDtypeStruct((32_768,), jnp.int32, sharding=one)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    deltas = jax.ShapeDtypeStruct((3, DELTA_CAPACITY), jnp.int32,
                                  sharding=one)
    mem = fn.lower(block, carry, start, deltas).compile().memory_analysis()
    # the block and the counts, plus the output tuple's table
    out = ROWS * 32_768 // chips + 32_768 * 4
    assert out <= mem.output_size_in_bytes <= out + 1024


def test_dcn_program_compiles(topo):
    spec = DcnSpec(num_nodes=256, tp_sizes=(32,), agg_domain=64)
    fn = dcn_jax._grid_fn(spec.config, (32,), (spec.job_gpus(32),), None)
    arg = jax.ShapeDtypeStruct((64, 256), bool, sharding=_sharding(topo, 1))
    assert fn.lower(arg).compile().memory_analysis() is not None


def test_slo_scan_compiles(topo):
    # the README serving example: 1,235 intervals, 2 streams, 8 archs
    one = _sharding(topo, 1)
    shapes = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
              for s in ((1235, 2), (1235, 8), (1235, 2))]
    assert slo_jax._scan_fn().lower(*shapes).compile() is not None


def test_prefix_scan_kernel_compiles_to_a_tpu_custom_call(topo):
    from repro.kernels.prefix_scan.prefix_scan import prefix_scan_pallas
    fn = jax.jit(lambda x: prefix_scan_pallas(x, interpret=False))
    arg = jax.ShapeDtypeStruct((ROWS, 32_768), jnp.int32,
                               sharding=_sharding(topo, 1))
    assert "tpu_custom_call" in fn.lower(arg).compile().as_text()
