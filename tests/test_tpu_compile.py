"""The main path's programs compile for a TPU v5e chip, at real sizes.

No chip is attached: the installed TPU compiler compiles for a *described*
``v5e:2x2`` topology, so what the chip's compiler refuses (a Mosaic
lowering, a 64-bit index map, a complex128 op the TPU has no expansion
for) fails here at no chip time. Nothing runs, so these tests say nothing
about results or speed.

Every compile happens with x64 on, as in every process that imports
``repro.core``. The kernels are called with ``interpret=False`` directly:
the dispatch in ``repro.kernels.ops`` asks the host backend and would
trace them in interpret mode here. The topology is described inside a
fixture, never at import time, so every xdist worker collects the same
tests and only the one given this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as the campaign runs)
from repro.core.asymmetric_batched import _solve_vmapped
from repro.core.duration import theoretical_duration
from repro.core.poibin import poibin_pmf
from repro.core.utility import UtilityParams, social_cost
from repro.kernels.fedavg_agg import fedavg_agg
from repro.kernels.poibin_dft import poibin_dft
from repro.kernels.ref import poibin_dft_ref

# configs/resnet18_cifar.py at its published widths, CIFAR 3x3 stem (Table I's
# 11,181,642 counts torchvision's 7x7 stem).
RESNET18_PARAMS = 11_173_962
N_PAPER = 50                     # the paper's fleet


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()


@pytest.mark.parametrize("scenarios", [None, 2])
def test_fedavg_agg_compiles_at_resnet18_width(one_chip, scenarios):
    """Alone, and vmapped over a campaign's scenario batch (which adds a
    squeezed leading dim to every block)."""
    merge = functools.partial(fedavg_agg, interpret=False)
    lead = ()
    if scenarios is not None:
        merge, lead = jax.vmap(merge), (scenarios,)
    n, f32 = 8, jnp.float32
    compiled = _compile(merge,
                        _spec(one_chip, lead + (RESNET18_PARAMS,), f32),
                        _spec(one_chip, lead + (n, RESNET18_PARAMS), f32),
                        _spec(one_chip, lead + (n,), f32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("with_loo", [True, False])
def test_poibin_dft_compiles_at_paper_fleet(one_chip, dtype, with_loo):
    compiled = _compile(
        lambda p: poibin_dft(p, with_loo=with_loo, interpret=False),
        _spec(one_chip, (64, N_PAPER), jnp.dtype(dtype)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_poibin_dft_ref_compiles(one_chip, dtype):
    """The ``"ref"`` oracle of the kernel: it held a complex DFT."""
    _compile(poibin_dft_ref, _spec(one_chip, (64, N_PAPER), jnp.dtype(dtype)))


def test_poibin_pmf_compiles_at_paper_fleet(one_chip):
    """Eq. (9) at N=50: a complex128 DFT here aborts the TPU compiler."""
    _compile(poibin_pmf, _spec(one_chip, (N_PAPER,), jnp.float64))


def test_social_cost_compiles_at_paper_fleet(one_chip):
    """The PoA evaluation's social cost reaches eq. (9) through utility."""
    params = UtilityParams(gamma=0.5, cost=0.2, n_nodes=N_PAPER)
    dur = theoretical_duration(N_PAPER)
    _compile(lambda p: social_cost(p, params, dur),
             _spec(one_chip, (), jnp.float64))


def test_heterogeneous_solve_compiles(one_chip):
    b, n, f64 = 512, N_PAPER, jnp.float64
    compiled = _solve_vmapped.lower(
        _spec(one_chip, (b, n), f64), _spec(one_chip, (b, n), f64),
        _spec(one_chip, (b, n + 1), f64), _spec(one_chip, (b, n), f64),
        damping=0.5, max_iters=200, tol=1e-5).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
