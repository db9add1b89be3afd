"""Compile the main path's programs for a described TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so these tests
need no accelerator.  They catch what interpret mode cannot: Mosaic's
refusals (scalar stores to VMEM, unaligned dynamic indices, 1-D operand
tilings) and programs that do not fit one chip's 16 GB of HBM.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_in_hlo(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,mpk", [(16, 16), (256, 1)])
def test_hier_minsearch_compiles(one_chip, k, mpk):
    from repro.kernels.hier_minsearch import assign_tasks
    compiled = assign_tasks.lower(
        _sds((k, mpk), jnp.float32, one_chip),
        _sds((100,), jnp.float32, one_chip)).compile()
    assert _kernel_in_hlo(compiled)


def test_selective_scan_compiles_at_mamba_width(one_chip):
    from repro.kernels.selective_scan import selective_scan
    B, L, D, N = 1, 2048, 1024, 16
    act = _sds((B, L, D), jnp.float32, one_chip)
    bc = _sds((B, L, N), jnp.float32, one_chip)
    compiled = selective_scan.lower(
        act, act, _sds((D, N), jnp.float32, one_chip), bc, bc,
        _sds((D,), jnp.float32, one_chip), chunk=128, block_d=256).compile()
    assert _kernel_in_hlo(compiled)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    x = _sds((1, 2048, 8, 128), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(x, x, x).compile()
    assert _kernel_in_hlo(compiled)


def _paper_point_sweep(one_chip, topology):
    """The batched event loop at the paper's m=256 deployment (k=16, tree
    queue, batch_pop=64) over 4 thresholds x 2 seeds on ``topology``: the
    program ``ExperimentSpec.run`` dispatches on a chip, compiled."""
    from repro.core import sweep as SW
    from repro.core.sim import SimParams
    from repro.core.transport import Topology
    p = SimParams(m=256, k=16, n_childs=100, max_apps=64, queue_cap=8192,
                  queue_impl="tree", batch_pop=64)
    b, s = 4, 2
    knobs = jax.tree.map(lambda x: _sds((b,), x.dtype, one_chip), p.knobs)
    return SW._sweep.lower(
        p.shape, knobs,
        _sds((s, p.max_apps), jnp.float32, one_chip),
        _sds((s, p.max_apps), jnp.int32, one_chip),
        _sds((s, p.max_apps, p.n_childs), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip),
        p.policy, Topology(topology), None, None).compile()


def test_paper_point_sweep_compiles_and_fits(one_chip):
    """The paper's point on the hier_tree fabric."""
    compiled = _paper_point_sweep(one_chip, "hier_tree")
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


def test_torus_point_sweep_compiles_and_fits(one_chip):
    """The same deployment on the MPPA-256's 2D torus (torus2d)."""
    compiled = _paper_point_sweep(one_chip, "torus2d")
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
