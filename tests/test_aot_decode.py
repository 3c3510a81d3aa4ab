"""The decode block as the v5e compiler leaves it, kept as a test: the KV
write is one native scatter a leaf (no `while` over the slots under scope
`kv_write`) and the donated pool is aliased. What PR 27 did NOT reach is
stated too, so that it cannot get worse unseen: at most one leaf a layer
is still staged through the fast memory space `S(1)` and copied back out
(PERF.md section 7 says what turns that off). The half-length program
(PR 29: attention over the first `max_length // 2` rows of a slot)
stages no leaf at all: the slice is fused into both contractions, which
read the leaf where it lies. Compiled for a described chip at the cells'
widths and two layers; costs no chip time, says nothing of speed. Marked
slow (about a minute a case). Since PR 31 also: an expert layer's routed
experts are ONE Mosaic call, and no `while` is left under `moe/experts`
(two expert layers; both expert cells, both programs).

The topology is described inside a fixture, never at import: every xdist
worker imports this file, and only one process may load libtpu — so
beside `tests/benchmark_suite/test_aot.py` on another worker one of the
two skips, unless the run lifts the lock."""
import re

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


def _compile_decode_block(cell, one_chip, program='whole'):
    """-> (optimized HLO text, memory analysis, the pool's leaves) of the
    decode block (`whole`, or the `half`-length one) of the engine the
    benchmark builds for `cell`."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from benchmarks.kinds import _serve
    from paddle_tpu.ops import pallas

    class _Run:
        config, traffic, seed = cell['config'], cell['traffic'], 0
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    gate = pallas._pallas_enabled
    try:
        aot.force_kernels_on()      # the engine reads the gate as built
        eng = _serve.Server(_Run).router.replicas[0].engine
        jit = eng._decode_jit if program == 'whole' else eng._decode_half_jit
        compiled = jit.lower(*aot.abstract(
            (eng._params, eng._frozen, eng._buffers, eng.pool.cache,
             eng._tok, eng._pos, eng._steps, eng._active, eng._temp,
             eng._topk, eng._topp, eng._greedy, eng._keys),
            one_chip)).compile()
    finally:
        pallas._pallas_enabled = gate
        pallas.pallas_ce_enabled.cache_clear()
        jax.config.update('jax_enable_compilation_cache', True)
        cc.reset_cache()
    return (compiled.as_text(), compiled.memory_analysis(),
            jax.tree_util.tree_leaves(eng.pool.cache))


def staged_pool_rows(hlo_text, leaf):
    """The async copies between HBM and `S(1)` whose operand is rows of
    a pool leaf: a whole leaf (`copy-start`) or a slab of its slots
    (`slice-start`), told by dtype and the leaf's [rows, heads, dim].
    -> (copies into `S(1)`, copies out of it), as instruction names."""
    rows = r'f32\[\d+,%d,%d,%d\]\{[^}]*\}' % tuple(leaf.shape[1:])
    into, out = [], []
    for ln in hlo_text.splitlines():
        m = re.search(r'^\s*(%\S+) = \(+(' + rows + r')\)?, (' + rows
                      + r'), .* (copy|slice)-start\(', ln)
        if not m:
            continue
        # copy-start: (destination, source, context); slice-start:
        # ((source), destination, context)
        dst, src = (m.group(2), m.group(3)) if m.group(4) == 'copy' \
            else (m.group(3), m.group(2))
        if 'S(1)' in dst and 'S(1)' not in src:
            into.append(m.group(1))
        elif 'S(1)' in src and 'S(1)' not in dst:
            out.append(m.group(1))
    return into, out


def test_staged_pool_rows_reads_the_compilers_spelling():
    class leaf:
        shape = (12, 1024, 16, 128)
    hlo = '''
  %copy-start.1 = (f32[12,1024,16,128]{3,2,1,0:T(8,128)}, f32[12,1024,16,128]{3,2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%fusion.884)
  %slice-start = ((f32[12,1024,16,128]{3,2,1,0:T(8,128)}), f32[3,1024,16,128]{3,2,1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%get-tuple-element.1282), slice={[0:3], [0:1024], [0:16], [0:128]}
  %slice-start.9 = ((bf16[2048,8192]{1,0:T(8,128)(2,1)}), bf16[512,8192]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%p.3), slice={[0:512], [0:8192]}
  %copy-start.7 = (f32[12,1024,16,128]{3,2,1,0:T(8,128)}, f32[12,1024,16,128]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%x)
'''
    assert staged_pool_rows(hlo, leaf) == (['%slice-start'],
                                           ['%copy-start.1'])


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
@pytest.mark.parametrize('workload', ['serve-chat', 'serve-moe-docs'])
def test_decode_block_writes_its_rows_in_place_on_v5e(workload, program,
                                                      one_chip):
    cell = spec.Spec().cell(workload)
    cell['config']['num_hidden_layers'] = 2
    text, ma, leaves = _compile_decode_block(cell, one_chip, program)
    assert 'decode' in re.search(r'HloModule (\S+)', text).group(1)
    loops = [ln for ln in text.splitlines()
             if re.search(r' while\(', ln) and 'kv_write' in ln]
    assert not loops, f'the KV write is a loop over the slots again: ' \
                      f'{loops[0][:200]}'
    # ... and is there under its scope, as the scatter it was written as
    assert re.search(r' scatter\(.*kv_write', text)
    # reached by PR 27: one leaf a layer at most goes through S(1) and
    # comes back out (the parent: the same, around the loops); zero is
    # the aim, and passes
    layers = cell['config']['num_hidden_layers']
    into, out = staged_pool_rows(text, leaves[0])
    assert len(out) <= layers, f'more than a leaf a layer evicted: {out}'
    if program == 'half':
        # attention reads half of each leaf where it lies: nothing of a
        # leaf's size moves, in or out
        assert not into and not out, (into, out)
        rows = leaves[0].shape[1] // 2
        assert re.search(r'f32\[%d,%d,%d,%d\]\S* slice\(' % (
            leaves[0].shape[0], rows, *leaves[0].shape[2:]), text)
    pool_bytes = sum(v.size * v.dtype.itemsize for v in leaves)
    assert ma.alias_size_in_bytes == pool_bytes


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
@pytest.mark.parametrize('workload', ['serve-moe-docs',
                                      'serve-hybrid-reason'])
def test_an_expert_layer_is_one_kernel_on_v5e(workload, program, one_chip):
    """One dense layer and two expert layers of the cell's configuration:
    the decode block holds one Mosaic custom call a layer under
    `moe/experts` and no loop there; the pool is still aliased and no
    more leaves go through `S(1)` than before the kernel."""
    cell = spec.Spec().cell(workload)
    cfg = cell['config']
    cfg['num_hidden_layers'] = 3
    cfg['layer_types'] = cfg['layer_types'][:3]
    text, ma, leaves = _compile_decode_block(cell, one_chip, program)
    assert 'decode' in re.search(r'HloModule (\S+)', text).group(1)
    experts = [ln for ln in text.splitlines() if 'moe/experts' in ln]
    kernels = [ln for ln in experts if 'tpu_custom_call' in ln]
    assert len(kernels) == 2, [ln[:160] for ln in kernels]
    assert all('moe_decode_experts' in ln for ln in kernels)
    loops = [ln for ln in experts if re.search(r' while\(', ln)]
    assert not loops, f'a loop under moe/experts again: {loops[0][:200]}'
    rows = [v for v in leaves if v.ndim == 4]       # K and V, not state
    into, out = staged_pool_rows(text, rows[0])
    assert len(out) <= len(rows) // 2, f'more than a leaf a layer: {out}'
    if program == 'half':
        assert not into and not out, (into, out)
    pool_bytes = sum(v.size * v.dtype.itemsize for v in leaves)
    assert ma.alias_size_in_bytes == pool_bytes
