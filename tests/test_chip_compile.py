"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the main path's Pallas kernels
and one pricing program are compiled here at the widths they run at on
the chip. This catches what interpret mode cannot: block shapes the
(8, 128) tiling rule refuses, kernels that overrun the scoped VMEM, and
dtypes Mosaic lacks. Nothing runs, so nothing here says anything about
results or times.

The topology is described only inside the module-scoped fixture: the TPU
library may be loaded by one process at a time, so describing it while a
module is imported would break runs with several test workers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without the chip; keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _matmul(s):
    from repro.kernels.matmul import matmul_pallas

    x = _spec(s, (4096, 4096), jnp.bfloat16)
    return matmul_pallas, (x, x)


def _flash_attention(s):
    """smollm-135m: 9 query heads of 64, batch 4, 2048 tokens."""
    from repro.kernels.flash_attention import flash_attention_pallas

    x = _spec(s, (4 * 9, 2048, 64), jnp.bfloat16)
    return flash_attention_pallas, (x, x, x)


def _mamba_scan(s):
    """hymba-1.5b: d_inner 3200, state 16."""
    from repro.kernels.mamba_scan import mamba_scan_pallas

    x = _spec(s, (2, 1024, 3200))
    bc = _spec(s, (2, 1024, 16))
    return mamba_scan_pallas, (x, x, bc, bc, _spec(s, (3200, 16)))


def _segment_rowmax(s):
    """A 1024-processor congestion table reduced over 4-wide subtrees."""
    from repro.kernels.segment_reduce import segment_rowmax_pallas

    return (lambda v: segment_rowmax_pallas(v, 4)), (_spec(s, (4096, 1024)),)


def _wkv6(s):
    """rwkv6-3b: 40 heads of 64."""
    from repro.kernels.wkv6 import wkv6_pallas

    x = _spec(s, (40, 1024, 64))
    return wkv6_pallas, (x, x, x, x, _spec(s, (40, 64)))


def _stencil(s):
    """The registry's 1024x8192 stencil field."""
    from repro.kernels.stencil import stencil_pallas

    return stencil_pallas, (_spec(s, (1024, 8192)),)


KERNELS = {
    "matmul": _matmul,
    "flash_attention": _flash_attention,
    "mamba_scan": _mamba_scan,
    "segment_rowmax": _segment_rowmax,
    "wkv6": _wkv6,
    "stencil": _stencil,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("mode", ["dense", "scatter"])
def test_pricer_program_compiles_for_v5e(one_chip, dtype, mode):
    """SUMMA at 64 processors, one chunk of candidate placements, in both
    formulations; float64 is emulated on the v5e."""
    from repro import apps
    from repro.sim import jax_backend as jb
    from repro.sim.cost import time_search_space

    app = apps.get("summa")
    n = 64
    model = time_search_space(app).cost_model(
        n, dict(next(iter(app.search_space.option_combos()))))
    grid = next(iter(app.search_space.grids(n)))
    jeng = jb.to_jax(model.batch(grid), dtype=dtype)
    exp = jb._export_for(jeng.schedule, jeng.topology)
    assert exp.mode == "dense"
    dt = np.dtype(dtype)
    build = exp._build_dense if mode == "dense" else exp._build_scatter
    with jax.enable_x64(dtype == "float64"):
        rows = _spec(one_chip, (exp.chunk(mode), exp.ntiles), jnp.int32)
        compiled = jax.jit(build(dt)).lower(rows).compile()
        out = compiled.out_info
    assert out.shape == (exp.chunk(mode), exp.u)
    assert out.dtype == dt
