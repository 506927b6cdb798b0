"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (tiles that break the
layout rules, more VMEM or SMEM than a kernel may use).  Every test
asserts that the kernel lowered to ``tpu_custom_call``, i.e. that it runs
compiled and not in the interpreter.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so collection must not touch it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_eneac import HotspotConfig, SpmmConfig
from repro.kernels.hotspot.hotspot import hotspot_hp_step_pallas, hotspot_hpc_pallas
from repro.kernels.hotspot.ops import hotspot_hpc_window
from repro.kernels.spmm.ref import COL_BLOCK, ROW_BLOCK
from repro.kernels.spmm.spmm import spmm_block_ell_window

CFG = HotspotConfig()
GRID = (CFG.grid, CFG.grid)
ACC_CHUNK = 128                                  # chip_smoke.py's HotSpot chunk
WINDOW = ACC_CHUNK + 2 * CFG.sim_steps           # rows of one ACC window
SPMM = SpmmConfig()
SPMM_WINDOW = 1024 // ROW_BLOCK + 1              # row blocks of one ACC window


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: entries
    compiled for it could not be read back on this host."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [GRID[0], WINDOW])
def test_hotspot_hp_step_compiles(one_chip, rows):
    x = _sds(one_chip, (rows, GRID[1]))
    step = jax.jit(lambda t, p: hotspot_hp_step_pallas(
        t, p, CFG, grid=GRID, interpret=False))
    _assert_kernel(step.lower(x, x).compile())


def test_hotspot_hpc_compiles_at_full_grid(one_chip):
    # 2048² needs 80 MiB of scoped VMEM: refused without an explicit limit
    x = _sds(one_chip, GRID)
    run = jax.jit(lambda t, p: hotspot_hpc_pallas(
        t, p, CFG, CFG.sim_steps, interpret=False))
    _assert_kernel(run.lower(x, x).compile())


def test_hotspot_hpc_window_compiles(one_chip):
    x = _sds(one_chip, GRID)
    compiled = hotspot_hpc_window.lower(
        x, x, _sds(one_chip, (), jnp.int32), cfg=CFG, window=WINDOW,
        steps=CFG.sim_steps, interpret=False).compile()
    _assert_kernel(compiled)


def test_spmm_window_compiles_at_paper_chunk(one_chip):
    # 29957 rows -> 3745 row blocks; a lognormal row of mean 120 nonzeros
    # occupies nearly every one of the 235 column blocks of its row block
    n_rb = -(-SPMM.rows // ROW_BLOCK)
    n_cb = -(-SPMM.cols // COL_BLOCK)
    compiled = spmm_block_ell_window.lower(
        _sds(one_chip, (n_rb, n_cb, ROW_BLOCK, COL_BLOCK)),
        _sds(one_chip, (n_rb, n_cb), jnp.int32),
        _sds(one_chip, (n_rb,), jnp.int32),
        _sds(one_chip, (n_cb * COL_BLOCK, 128)),
        _sds(one_chip, (), jnp.int32),
        n_row_blocks=SPMM_WINDOW, interpret=False).compile()
    _assert_kernel(compiled)
