"""Compile the hot-path kernels for a described TPU v5e, without the chip.

The TPU compiler ships with the installed ``libtpu``: it compiles for a
v5e topology that is described and not attached, and refuses what the chip
would refuse (unaligned blocks, too much scoped VMEM, a program larger
than HBM).  Interpret-mode tests cannot see any of that.  Each test
compiles one kernel at the widths the main path uses (8^3 sub-grids with
3 ghost layers, 5 fields) and checks that Mosaic produced a kernel and
that the program fits one chip's memory.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.sedov import CONFIG
from repro.core.scenario import UniformSedovScenario
from repro.kernels.gravity import gravity_pallas
from repro.kernels.hydro_rhs import hydro_rhs_pallas

V5E_HBM_BYTES = 16 * 1000 ** 3
BUCKET = 32                  # the default max_aggregated rung
LANE_BUCKET = 128            # one full lane row of tasks in slot_lane
KW = dict(gamma=CONFIG.gamma, ghost=CONFIG.ghost, subgrid=CONFIG.subgrid)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _subgrids(n, sharding):
    p = CONFIG.padded
    return jax.ShapeDtypeStruct((n, CONFIG.n_fields, p, p, p), jnp.float32,
                                sharding=sharding)


def _widths(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    return compiled


@pytest.mark.parametrize("layout,traced_h,n", [
    ("slot_grid", False, BUCKET),
    ("slot_grid", True, BUCKET),
    ("slot_lane", False, LANE_BUCKET),
    ("slot_lane", False, BUCKET),
])
def test_hydro_rhs_kernel_compiles_for_v5e(one_chip, layout, traced_h, n):
    if traced_h:
        fn = lambda u, h: hydro_rhs_pallas(u, h_slots=h, layout=layout, **KW)
        specs = (_subgrids(n, one_chip), _widths(n, one_chip))
    else:
        fn = lambda u: hydro_rhs_pallas(u, h=1.0 / 64, layout=layout, **KW)
        specs = (_subgrids(n, one_chip),)
    assert "tpu_custom_call" in _compile(fn, *specs).as_text()


def test_gravity_kernel_compiles_for_v5e(one_chip):
    fn = lambda u, h: gravity_pallas(u, h, ghost=CONFIG.ghost,
                                     subgrid=CONFIG.subgrid)
    compiled = _compile(fn, _subgrids(BUCKET, one_chip),
                        _widths(BUCKET, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_default_xla_body_compiles_for_v5e(one_chip):
    """The body a uniform scenario runs where ``slot_lane`` is not selected
    (``uniform_batched_body``): vmap of the jnp stencil over one bucket of
    the paper's Table II configuration."""
    body = UniformSedovScenario(CONFIG).batched_body
    _compile(body, _subgrids(BUCKET, one_chip))
