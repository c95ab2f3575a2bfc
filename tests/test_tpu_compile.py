"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel at the widths the main path uses
and hands it to the TPU compiler, which refuses what the chip's Mosaic
lowering cannot take (a dynamic slice of a loaded value, an unaligned
block, more VMEM than a kernel may use).  Every compiled program must hold
a ``tpu_custom_call``: the kernel itself, not its interpreter.

The v5e topology is described inside a module fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under xdist every worker imports this file.  All such compiles stay in
this one file so the worker that loads the library runs all of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.admission import admission_pallas
from repro.kernels.congestion import congestion_pallas
from repro.kernels.minplus import minplus_pallas
from repro.kernels.power import matmul_pallas

# Phase C of chip_smoke.py: 4 seeds of jellyfish(512, 24, 18) under
# permutation traffic, k=8 -> ~24.6k paths over 2E = 9,216 link slots.
P, SLOTS, BATCH = 24576, 9216, 4
# apsp_minplus_blocked streams 512 x 512 tiles through the min-plus kernel.
TILE = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _cases():
    f32, i32 = jnp.float32, jnp.int32
    return {
        "congestion_rank2": (congestion_pallas,
                             [((P, SLOTS), f32), ((P,), f32), ((SLOTS,), f32)]),
        "congestion_rank3": (congestion_pallas,
                             [((BATCH, P, SLOTS), f32), ((BATCH, P), f32),
                              ((BATCH, SLOTS), f32)]),
        "minplus_tile": (minplus_pallas,
                         [((TILE, TILE), f32), ((TILE, TILE), f32)]),
        "matmul": (matmul_pallas, [((2048, 2048), f32), ((2048, 8), f32)]),
        "admission": (admission_pallas,
                      [((4096, 48), f32), ((4096,), f32), ((4096, 48), i32),
                       ((4096, 8), i32)]),
    }


@pytest.mark.parametrize("case", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = _cases()[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
