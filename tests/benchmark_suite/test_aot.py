"""Rehearsal 3 kept as a test: the one-chip train step of the benchmark
compiles for a described v5e at the published widths (depth cut to one
layer: a compile, not a cell), with its Mosaic kernels in it and inside
the chip's memory. Marked slow (about two minutes: the full-gate tier).

The topology is described inside a fixture, never at import: every
xdist worker imports this file, and only one process may load libtpu."""
import pytest

from benchmarks import aot, spec


@pytest.fixture(scope='module')
def topo():
    try:
        return aot.describe_topology()
    except Exception as exc:    # no libtpu, or another process holds it
        pytest.skip(f'no v5e:2x2 topology can be described here: {exc}')


@pytest.fixture(scope='module')
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow
def test_train_step_compiles_for_v5e_at_published_widths(one_chip):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    cell = spec.Spec().cell('train-1chip')
    cell['config']['num_hidden_layers'] = 1
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    from paddle_tpu.ops import pallas
    gate = pallas._pallas_enabled
    try:
        aot.force_kernels_on()
        ma = aot.compile_train(cell, one_chip)
    finally:
        pallas._pallas_enabled = gate
        pallas.pallas_ce_enabled.cache_clear()
        jax.config.update('jax_enable_compilation_cache', True)
        cc.reset_cache()
    assert 0 < ma.peak_memory_in_bytes < 15.75 * 2**30
