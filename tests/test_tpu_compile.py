"""The radar kernels compile for a TPU v5e at deployment shapes.

Interpret mode cannot see Mosaic's block-shape rules or its VMEM limit,
so each kernel of the product path is compiled here for one chip of a
described (not attached) ``v5e:2x2`` topology, at full VCP-212 geometry
(12 scans x 720 azimuths x 1192 gates, 14 cuts) onto a 240 x 240 grid.
A compile is not a run: it proves only that the chip's compiler accepts
each kernel, and that the Pallas kernel — not a fallback — is in the
program.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.grid_map import grid_map_pallas
from repro.kernels.grid_update import grid_update_pallas
from repro.kernels.qvp_reduce import qvp_reduce_pallas
from repro.kernels.zr_accum import zr_accum_pallas

T, A, R, CUTS = 12, 720, 1192, 14       # VCP-212 at full geometry
CELLS = 240 * 240


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off (a described-topology compile is written to
    the cache but cannot be read back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qvp_reduce_compiles_at_full_vcp(one_chip):
    block = _spec(one_chip, (T, A, R))
    _assert_kernel_compiles(lambda f, q: qvp_reduce_pallas(f, q),
                            block, block)


def test_zr_accum_compiles_at_full_vcp(one_chip):
    _assert_kernel_compiles(lambda d, t: zr_accum_pallas(d, t),
                            _spec(one_chip, (T, A, R)),
                            _spec(one_chip, (T,)))


@pytest.mark.parametrize("cuts,k", [(1, 1), (1, 4), (CUTS, 1)],
                         ids=["ppi-nearest", "ppi-idw", "cappi-14-cuts"])
def test_grid_map_compiles_at_full_vcp(one_chip, cuts, k):
    _assert_kernel_compiles(
        lambda f, i, w: grid_map_pallas(f, i, w),
        _spec(one_chip, (T, cuts * A * R)),
        _spec(one_chip, (CELLS, k), jnp.int32),
        _spec(one_chip, (CELLS, k)),
    )


@pytest.mark.parametrize("t,cells,touched,op", [
    (1, CELLS, 45_000, "set"),            # one new scan of a CAPPI state
    (T, CELLS, 45_000, "max"),            # a composite window
    (1, A * R, 300_000, "add"),           # streaming QPE fold, one scan
], ids=["cappi-append", "composite-max", "qpe-fold"])
def test_grid_update_compiles_at_full_vcp(one_chip, t, cells, touched, op):
    _assert_kernel_compiles(
        lambda s, u, p: grid_update_pallas(s, u, p, op=op),
        _spec(one_chip, (t, cells)),
        _spec(one_chip, (t, touched)),
        _spec(one_chip, (cells,), jnp.int32),
    )
