"""Compile rehearsals of the GP device programs for a described TPU v5e.

JAX ships the TPU compiler, so these tests compile — never run — the
main path's GP kernels and programs at real bucket sizes for one chip of
a ``v5e:2x2`` topology that is described, not attached.  They catch what
interpret mode cannot: block shapes Mosaic refuses, scalar VMEM
accesses, ops with no Pallas TPU lowering, VMEM over-use.

The topology is described inside a module-scoped fixture and never while
a module is imported: only one process at a time may load the TPU
compiler library, and every pytest-xdist worker imports every test file.
The persistent compile cache is off around these compiles, since an
entry written for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.suggest import gp
from repro.kernels import gp as gpk

DIMS = 8
LANES = 8
POOL = 1024 + 1024 // 4         # BayesOpt's candidate pool (bayesopt.py)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _nll_args(one_chip, k, b):
    s = lambda *shape: _spec(one_chip, shape)               # noqa: E731
    return (s(k, DIMS), s(k), s(k), s(k, b, DIMS), s(k, b), s(k, b))


def _nll_sum(ll, la, ln, x, y, m):
    return jnp.sum(gpk.gp_nll(ll, la, ln, x, y, m, interpret=False))


@pytest.mark.parametrize("bucket", [256, 1024])
def test_gp_nll_forward_compiles_for_v5e(one_chip, bucket):
    compiled = jax.jit(_nll_sum).lower(
        *_nll_args(one_chip, LANES, bucket)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bucket", [256, 1024])
def test_gp_nll_grad_compiles_for_v5e(one_chip, bucket):
    grad = jax.grad(_nll_sum, argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(
        *_nll_args(one_chip, LANES, bucket)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gp_ei_compiles_for_v5e(one_chip):
    k, b = LANES, 256
    s = lambda *shape: _spec(one_chip, shape)               # noqa: E731
    compiled = jax.jit(
        lambda *a: gpk.gp_ei(*a, interpret=False)).lower(
        s(k, DIMS), s(k), s(k, b, DIMS), s(k, b), s(k, b, b), s(k, b),
        s(k), s(k), s(k, POOL, DIMS), s(k)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _posterior_spec(one_chip, b, lanes=()):
    s = lambda *shape: _spec(one_chip, lanes + shape)       # noqa: E731
    return gp.GPPosterior(gp.GPParams(s(DIMS), s(), s()), s(b, DIMS), s(b),
                          s(b), s(b, b), s(b), s(), s())


def test_select_lanes_compiles_for_v5e(one_chip):
    post = _posterior_spec(one_chip, 256, lanes=(LANES,))
    gp._select_lanes.lower(
        post, _spec(one_chip, (LANES, POOL, DIMS)),
        _spec(one_chip, (LANES,)), _spec(one_chip, (LANES,), jnp.int32),
        k_pad=gp.SELECT_PAD).compile()


def test_posterior_compiles_for_v5e(one_chip):
    b = 256
    s = lambda *shape: _spec(one_chip, shape)               # noqa: E731
    gp._posterior.lower(gp.GPParams(s(DIMS), s(), s()), s(b, DIMS), s(b),
                        s(b), s(), s()).compile()
