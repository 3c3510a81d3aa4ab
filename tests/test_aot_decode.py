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
(two expert layers; both expert cells, both programs). Since PR 33: a
pool leaf whose head size is not whole lanes is held in the layout the
whole-length block's compile chose for it (AUTO), and ENTRY of neither
decode program copies a whole leaf any more (serve-hybrid-reason,
serve-swa-reason: 4 and 6 such copies with the default layouts, at three
layers); seat, copy and slice compile to the pool's formats; the other
cells' leaves keep the default, which is what AUTO would give them. Since
PR 38: serve-mla-long's decode programs hold ONE Mosaic attention call a
latent layer (`mla_decode_attention`, under `attention`), which takes both
leaves of the layer where the row's write left them: nothing of a leaf's
size is copied, transposed or staged. Since PR 44: serve-kda-reason's hold
ONE `kda_decode_step` a KDA layer under `kda/state_write`, and no fusion or
copy of the state's shape beside it. Since PR 46: serve-ssm-reason's make
the state ONCE a Mamba layer, the update and the sum over the states in one
fusion, and hold one `kv_decode_attention` for twenty query heads on one
K,V head. Since PR 47: its largest PREFILL holds one `ssm_prefill_scan` a
Mamba layer under `ssm` and no `while` there. Since PR 49: serve-moe-docs'
largest PREFILL holds one `moe_grouped_experts` an expert layer under
`moe/experts` and no `while` there. Every compile here goes through the
store's compile site with the formats the pool holds, as the engine's do.

The topology is described inside a fixture, never at import: every xdist
worker imports this file, and only one process may load libtpu — so
beside `tests/benchmark_suite/test_aot.py` on another worker one of the
two skips, unless the run lifts the lock."""
import contextlib
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


@contextlib.contextmanager
def _engine_for_the_chip(cell):
    """The engine the benchmark builds for `cell` (on the host CPU), with
    the kernels' gate forced as on a TPU and jax's compile cache off
    while the test compiles for the described chip."""
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
        yield _serve.Server(_Run).router.replicas[0].engine
    finally:
        pallas._pallas_enabled = gate
        pallas.pallas_ce_enabled.cache_clear()
        jax.config.update('jax_enable_compilation_cache', True)
        cc.reset_cache()


def _compile(jit, args, one_chip):
    """`jit` (a `StoredJit`) through the store's compile site at `args`
    on the described chip: its donation, and its pools in the formats
    they hold as this is called."""
    from paddle_tpu.programs import store
    return store._compile_program(
        jit._fn, aot.abstract(args, one_chip), jit._donate,
        store.pool_formats(jit._pool_io))


def _pool_leaves(eng):
    import jax
    return jax.tree_util.tree_leaves(eng.pool.cache)


def _compile_whole(eng, one_chip):
    """The whole-length decode block, which chooses the layout of the
    leaves that ask (`own_layout`, as the rule gives it on a TPU); the
    pool books what it chose, as `adopt_formats` would on a chip."""
    eng.pool.own_layout = eng.pool.asks('tpu', one_chip)
    compiled = _compile(eng._decode_jit, eng._decode_args(), one_chip)
    eng.pool.book_formats(compiled.input_formats[0][3],
                          compiled.output_formats[1])
    return compiled


def _compile_decode_block(cell, one_chip, program='whole'):
    """-> (optimized HLO text, memory analysis, the pool's leaves, its
    bytes on the device) of the decode block (`whole`, or the `half`-
    length one) of the engine the benchmark builds for `cell`."""
    with _engine_for_the_chip(cell) as eng:
        compiled = _compile_whole(eng, one_chip)
        if program == 'half':
            compiled = _compile(eng._decode_half_jit, eng._decode_args(),
                                one_chip)
    return (compiled.as_text(), compiled.memory_analysis(),
            _pool_leaves(eng), sum(eng.pool.entry_bytes().values()))


def whole_leaf_copies(hlo_text, leaves):
    """The `copy` instructions of ENTRY whose result is a whole pool
    leaf, told by dtype and shape: a relayout at the block's edge."""
    entry = hlo_text[hlo_text.index('\nENTRY '):]
    shapes = {','.join(map(str, v.shape)) for v in leaves if v.ndim == 4}
    return [ln.split(' = ')[0].strip() for ln in entry.splitlines()
            if any(re.search(r' = f32\[%s\]\{[^}]*\} copy\(' % sh, ln)
                   for sh in shapes)]


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


def _rows_of(leaves):
    """A regex for an array of a pool leaf's dtype and shape, in any
    layout."""
    shapes = sorted({','.join(map(str, v.shape)) for v in leaves})
    return r'f32\[(?:' + '|'.join(shapes) + r')\]\{[^}]*\}'


def moved_leaves(hlo_text, leaves):
    """The instructions ANYWHERE in the program that copy, transpose or
    stage an array of a pool leaf's size (`copy`, `transpose`,
    `copy-start`, `slice-start` whose first result is one): what a
    kernel that constrains its operands' layouts could bring about."""
    moved = re.compile(r'\s*(%\S+) = \(*' + _rows_of(leaves) + r'[^=]* '
                       r'(?:copy|copy-start|slice-start|transpose)\(')
    return [m.group(1) for m in map(moved.match, hlo_text.splitlines())
            if m]


def test_moved_leaves_reads_the_whole_program():
    class c:
        shape = (16, 16384, 512)

    class r:
        shape = (16, 16384, 64)
    hlo = """
%body (p: f32[16,16384,64]) -> f32[16,16384,64] {
  %copy.3 = f32[16,16384,64]{2,1,0:T(8,128)} copy(%p)
  %transpose.9 = f32[16,16384,512]{1,2,0:T(8,128)} transpose(%q), dimensions={0,2,1}
  %fusion.4 = f32[16,16384,64]{2,1,0:T(8,128)} fusion(%p, %copy.1), kind=kLoop
  %copy-start.2 = (f32[16,16384,512]{2,1,0:T(8,128)S(1)}, f32[16,16384,512]{2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%x)
  %copy.8 = f32[16,32,64]{2,1,0:T(8,128)} copy(%small)
  %k = f32[16,32,512]{2,1,0} custom-call(%a, %fusion.4), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[16,16384,64]{2,1,0}}
}

ENTRY %main (a: f32[16,16384,64]) -> f32[16,16384,64] {
  %copy.135 = f32[16,16384,64]{1,2,0:T(8,128)} copy(%a)
}
"""
    assert moved_leaves(hlo, [c, r]) == ['%copy.3', '%transpose.9',
                                         '%copy-start.2', '%copy.135']


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


def test_whole_leaf_copies_reads_entry_alone():
    class leaf:
        shape, ndim = (32, 4096, 8, 64), 4
    hlo = '''
%body (p: f32[32,4096,8,64]) -> f32[32,4096,8,64] {
  %copy.3 = f32[32,4096,8,64]{3,2,1,0:T(8,128)} copy(%p)
}

ENTRY %main (a: f32[32,4096,8,64], b: f32[32,3,2048]) -> f32[32,4096,8,64] {
  %copy.135 = f32[32,4096,8,64]{3,2,1,0:T(8,128)} copy(%a)
  %copy.9 = f32[32,3,2048]{2,0,1:T(8,128)} copy(%b)
  %copy.140 = f32[32,4096,8,64]{1,3,2,0:T(8,128)} copy(%while.1)
  %copy.7 = f32[32,2048,8,64]{3,2,1,0:T(8,128)} copy(%slice.2)
}
'''
    assert whole_leaf_copies(hlo, [leaf]) == ['%copy.135', '%copy.140']


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
@pytest.mark.parametrize('workload', ['serve-chat', 'serve-moe-docs'])
def test_decode_block_writes_its_rows_in_place_on_v5e(workload, program,
                                                      one_chip):
    cell = spec.Spec().cell(workload)
    cell['config']['num_hidden_layers'] = 2
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
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
        # leaf's size moves, in or out — serve-chat's bf16 queries
        # through a slice fused into both contractions, serve-moe-docs'
        # float32 ones through the kernel, which takes the leaf whole
        # and walks under the mask's 2,048 columns (PR 40)
        assert not into and not out, (into, out)
        rows = leaves[0].shape[1] // 2
        sliced = re.search(r'f32\[%d,%d,%d,%d\]\S* slice\(' % (
            leaves[0].shape[0], rows, *leaves[0].shape[2:]), text)
        assert bool(sliced) == (workload == 'serve-chat')
        assert ('kv_decode_attention' in text) == (workload != 'serve-chat')
    # whole lanes: the default layout pads nothing, so the bytes on the
    # device are the logical ones
    assert pool_bytes == sum(v.size * v.dtype.itemsize for v in leaves)
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
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
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
    # the pool's bytes ON THE DEVICE: lfm2's K and V, 64 wide, are held
    # in tiles of 128 lanes, twice their logical size
    kv, state = (sum(v.size * v.dtype.itemsize for v in leaves
                     if (v.ndim == 4) is is_kv) for is_kv in (True, False))
    assert pool_bytes == state + kv * (
        2 if workload == 'serve-hybrid-reason' else 1)
    assert ma.alias_size_in_bytes == pool_bytes


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
def test_latent_attention_is_one_kernel_on_the_leaves_as_held_on_v5e(
        program, one_chip):
    """serve-mla-long at 16 x 16,384, a dense layer and two expert
    layers: a sub-step holds one `mla_decode_attention` a layer under
    `attention` beside the expert kernels; its operands are the leaves
    the row's scatter has just written (the current token's row is
    among those attended), in the layout the pool holds them; no
    `[16,16384,512]` or `[16,16384,64]` array is copied, transposed or
    staged anywhere in the program; XLA's temporaries stay under the
    0.18 GiB the einsums' program had at five layers (PR 37)."""
    cell = spec.Spec().cell('serve-mla-long')
    cell['config']['num_hidden_layers'] = 3
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
    assert 'decode' in re.search(r'HloModule (\S+)', text).group(1)
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln]
    attention = [ln for ln in calls if 'mla_decode_attention' in ln]
    assert len(attention) == 3, [ln[:160] for ln in calls]
    assert all('/attention/mla_decode_attention' in ln for ln in attention)
    assert sum('moe/experts/moe_decode_experts' in ln for ln in calls) == 2
    assert not moved_leaves(text, leaves)
    # the kernel reads what `kv_write`'s scatters return, leaf for leaf
    written = {m.group(1) for ln in text.splitlines() if 'kv_write' in ln
               for m in [re.match(r'\s*(%\S+) = ' + _rows_of(leaves)
                                  + r' fusion\(', ln)] if m}
    for ln in attention:
        operands = re.search(r'custom-call\(([^)]*)\)', ln).group(1)
        assert len(written & set(operands.split(', '))) == 2, ln[:300]
        assert 'f32[16,16384,512]{2,1,0}, f32[16,16384,64]{2,1,0}' in ln
    # the 64-wide leaf is held 128 lanes wide, as the kernel reads it
    assert pool_bytes == sum(
        v.size // v.shape[-1] * max(v.shape[-1], 128) * 4 for v in leaves)
    assert ma.alias_size_in_bytes == pool_bytes
    assert ma.temp_size_in_bytes <= 0.18 * 2 ** 30


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
def test_a_kda_layers_recurrence_is_one_kernel_in_place_on_v5e(
        program, one_chip):
    """serve-kda-reason at 48 slots, its dense KDA layer, one expert KDA
    layer and the latent layer (PR 44): a sub-step holds ONE
    `kda_decode_step` a KDA layer, under `kda/state_write`, beside the
    expert kernels and the latent layer's; nothing else in the program
    makes, copies or moves an array of the state's `[48,32,128,128]` —
    no fusion reads the state beside the kernel — and the pool, state
    leaves and latent rows, is aliased whole."""
    cell = spec.Spec().cell('serve-kda-reason')
    cell['config'].update(num_hidden_layers=3, kept_layers=[0, 6, 11],
                          layer_types=['kda', 'kda', 'mla'])
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln]
    steps = [ln for ln in calls if 'kda_decode_step' in ln]
    assert len(steps) == 2, [ln[:160] for ln in calls]
    assert all('/kda/state_write/kda_decode_step' in ln for ln in steps)
    assert sum('/attention/mla_decode_attention' in ln for ln in calls) == 1
    assert sum('moe/experts/moe_decode_experts' in ln for ln in calls) == 2
    state = r' = \(*f32\[48,32,128,128\]\{[^}]*\}[^=]* (\S+)\('
    made_by = {m.group(1) for m in (re.search(state, ln) for ln in
                                    text.splitlines()) if m}
    assert made_by <= {'custom-call', 'parameter', 'get-tuple-element',
                       'while', 'tuple'}, made_by
    assert ma.alias_size_in_bytes == pool_bytes


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
def test_a_state_space_layers_update_is_one_pass_in_place_on_v5e(
        program, one_chip):
    """serve-ssm-reason at its slots and widths, a Mamba layer, an
    attention layer and a Mamba layer (PR 46; XLA's step, no kernel): a
    sub-step makes the state's `[slots, 16, 5120]` ONCE a Mamba layer —
    one fusion that updates it and sums over its states in the same
    pass — and nothing copies or moves it; the attention layer's twenty
    query heads on ONE K,V head are one `kv_decode_attention`; the
    pool, both state leaves and the rows, is aliased whole. What a
    kernel for the update (ROADMAP A4k) has to beat is this."""
    cell = spec.Spec().cell('serve-ssm-reason')
    cell['config'].update(num_hidden_layers=3, attn_layer_offset=1,
                          attn_layer_period=3)
    slots = cell['traffic']['slots']
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln]
    assert len(calls) == 1 and '/attention/kv_decode_attention' in calls[0]
    # what MAKES an array of the state's shape, outside the fused
    # computations (inside one nothing is materialised)
    made_by, fused = [], False
    for ln in text.splitlines():
        if ln and not ln[0].isspace():          # a computation opens, or ends
            fused = 'fused_computation' in ln
        m = re.match(r'\s*(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\(', ln)
        if m and not fused and f'f32[{slots},16,5120]' in m.group(1):
            made_by.append(m.group(2))
    assert set(made_by) <= {'fusion', 'parameter', 'get-tuple-element',
                            'while', 'tuple'}, set(made_by)
    assert made_by.count('fusion') == 2
    assert ma.alias_size_in_bytes == pool_bytes


@pytest.mark.slow
def test_a_state_space_layers_prefill_is_one_kernel_a_layer_on_v5e(one_chip):
    """serve-ssm-reason's largest prefill at its widths, a Mamba layer,
    an attention layer and a Mamba layer (PR 47): the recurrence over
    the bucket's tokens is ONE `ssm_prefill_scan` a Mamba layer, under
    `ssm`; no `while` is left under `ssm` (the chunks' `lax.scan` and
    the associative scan inside it are gone) and nothing outside the
    kernel makes the pairs `[1, tokens, 16, 5120]` an associative scan
    composes."""
    import numpy as np
    cell = spec.Spec().cell('serve-ssm-reason')
    cell['config'].update(num_hidden_layers=3, attn_layer_offset=1,
                          attn_layer_period=3)
    cell['traffic']['slots'] = 2        # the prefill never holds the pool
    bucket = max(cell['traffic']['buckets'])
    with _engine_for_the_chip(cell) as eng:
        assert eng._scan_chunks(bucket) == {'ssm_chunks': 0,
                                            'ssm_kernel_layers': 2}
        text = _compile(
            eng._prefill_jit,
            (eng._params, eng._frozen, eng._buffers,
             np.zeros((1, bucket), np.int32), np.int32(bucket - 3)),
            one_chip).as_text()
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln]
    scans = [ln for ln in calls if 'ssm_prefill_scan' in ln]
    assert len(scans) == 2, [ln[:160] for ln in calls]
    assert all(re.search(r'/ssm/(jit\(ssm_prefill_scan\)/)?ssm_prefill_scan', ln)
               for ln in scans)
    loops = [ln for ln in text.splitlines()
             if re.search(r'op_name="[^"]*/ssm/[^"]*while', ln)]
    assert not loops, loops[:3]
    pairs = [ln for ln in text.splitlines()
             if re.match(r'\s*(?:ROOT )?%\S+ = \(*f32\[1,\d+,16,5120\]', ln)]
    assert not pairs, [ln[:160] for ln in pairs[:3]]


@pytest.mark.slow
def test_an_expert_layers_prefill_is_one_grouped_kernel_on_v5e(one_chip):
    """serve-moe-docs' largest prefill at its widths, one dense layer and
    three expert layers (PR 49): the routed experts of the bucket's
    tokens are ONE `moe_grouped_experts` an expert layer, under
    `moe/experts`, and no `while` is left there (the loop over blocks of
    256 sorted rows is gone); the engine says so on `serving.prefill`.
    The LAST layer's experts are in no prefill program, loop or kernel:
    a prefill returns K and V, which nothing behind the last layer's
    attention feeds, and the compiler drops the rest. The decode
    programs keep `moe_decode_experts`
    (`test_an_expert_layer_is_one_kernel_on_v5e`)."""
    import numpy as np
    cell = spec.Spec().cell('serve-moe-docs')
    cfg = cell['config']
    cfg['num_hidden_layers'] = 4
    cfg['layer_types'] = cfg['layer_types'][:4]
    cell['traffic']['slots'] = 2        # the prefill never holds the pool
    bucket = max(cell['traffic']['buckets'])
    with _engine_for_the_chip(cell) as eng:
        assert eng._scan_chunks(bucket) == {'expert_kernel_layers': 3}
        assert eng._scan_chunks(256) == {'expert_kernel_layers': 0}
        text = _compile(
            eng._prefill_jit,
            (eng._params, eng._frozen, eng._buffers,
             np.zeros((1, bucket), np.int32)), one_chip).as_text()
    experts = [ln for ln in text.splitlines() if 'moe/experts' in ln]
    kernels = [ln for ln in experts if 'tpu_custom_call' in ln]
    assert len(kernels) == 2, [ln[:160] for ln in kernels]
    assert all('moe_grouped_experts' in ln for ln in kernels)
    loops = [ln for ln in experts if re.search(r' while\(', ln)]
    assert not loops, f'a loop under moe/experts again: {loops[0][:200]}'


def _cut_to_three_layers(cfg):
    """A dense layer and two expert layers, every kind of cache entry
    the configuration has: lfm2 conv, attention, conv; mimo a full
    layer, a ring, a full layer."""
    cfg['num_hidden_layers'] = 3
    if 'layer_types' in cfg:
        cfg['layer_types'] = cfg['layer_types'][:3]
    if 'hybrid_layer_pattern' in cfg:
        cfg['hybrid_layer_pattern'] = [0, 1, 0]
        cfg['moe_layer_freq'] = [0, 1, 1]


@pytest.mark.slow
@pytest.mark.parametrize('program', ['whole', 'half'])
@pytest.mark.parametrize('workload, attending, row_bytes', [
    ('serve-moe-docs', 3, 2 * 4 * 128 * 4),
    ('serve-hybrid-reason', 1, 2 * 8 * 128 * 4),
    ('serve-swa-reason', 2, 4 * (256 + 128) * 4)])
def test_attention_by_head_is_one_kernel_on_the_leaves_as_held_on_v5e(
        workload, attending, row_bytes, program, one_chip):
    """The three cells whose queries are float32 over K and V by head,
    at their shapes and three layers (trinity-mini: three attending
    layers; lfm2: conv, attention, conv; mimo: full, ring, full — the
    ring has a sink and keeps XLA): a sub-step holds ONE
    `kv_decode_attention` an attending layer under `attention`; its K
    and V operands are the 4-D leaves the row's scatter has just
    written, in the layout the pool holds them — nothing of such a
    leaf's size is copied, transposed or staged anywhere in the
    program — and a row costs `row_bytes` on the device: trinity-mini's
    4 x 128 whole, lfm2's 64 lanes padded to 128 (twice the logical
    row), mimo's K 192 padded to 256 (4/3) beside its whole V."""
    cell = spec.Spec().cell(workload)
    _cut_to_three_layers(cell['config'])
    text, ma, leaves, pool_bytes = _compile_decode_block(cell, one_chip,
                                                         program)
    assert 'decode' in re.search(r'HloModule (\S+)', text).group(1)
    held = [v for v in leaves if v.ndim == 4 and v.shape[1] == 4096]
    assert len(held) == 2 * attending
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln]
    attention = [ln for ln in calls if 'kv_decode_attention' in ln]
    assert len(attention) == attending, [ln[:160] for ln in calls]
    assert all('/attention/kv_decode_attention' in ln for ln in attention)
    assert not moved_leaves(text, held)
    # the kernel reads what the row's scatters return, leaf for leaf:
    # its K and V operands are the fusions that wrote the leaves
    written = {m.group(1) for ln in text.splitlines()
               for m in [re.match(r'\s*(%\S+) = ' + _rows_of(held)
                                  + r' fusion\(', ln)] if m}
    assert any('kv_write' in ln for ln in text.splitlines()
               if re.match(r'\s*%\S+ = ' + _rows_of(held) + r' fusion', ln))
    # (a leaf at most 128 lanes a head goes in as its lines `[slot, row
    # x H_kv, D]`: a bitcast of what was written, not a copy)
    views = {m.group(1): m.group(2) for ln in text.splitlines()
             for m in [re.match(r'\s*(%\S+) = \S+ bitcast\((%[^),]+)\)', ln)]
             if m}
    for ln in attention:
        operands = re.sub(r'/\*[^*]*\*/', '', re.search(
            r'custom-call\(([^)]*)\)', ln).group(1)).split(', ')
        assert len(written & {views.get(o, o) for o in operands}) == 2, \
            ln[:300]
    slots, rows = held[0].shape[:2]
    others = pool_bytes - attending * slots * rows * row_bytes
    assert others == sum(
        v.size // v.shape[-1] * -(-v.shape[-1] // 128) * 128 * 4
        for v in leaves if v.ndim == 4 and v.shape[1] != 4096) + sum(
        v.size * 4 for v in leaves if v.ndim != 4)
    assert ma.alias_size_in_bytes == pool_bytes


@pytest.mark.slow
@pytest.mark.parametrize('workload, relaid, own', [
    ('serve-hybrid-reason', 4, 2), ('serve-swa-reason', 6, 3)])
def test_no_pool_leaf_is_relaid_at_the_blocks_edges_on_v5e(
        workload, relaid, own, one_chip):
    """With the device's default layouts ENTRY of the decode block
    copies every leaf whose head size is not whole lanes on its way in
    and again on its way out (`relaid` copies at three layers: what the
    parent of PR 33 ran). Held in the layout the whole-length block's
    compile chooses, no leaf is copied by either decode program; the
    pool is aliased at its bytes on the device; seat, copy and slice
    take and return it in those formats, seat and copy in place."""
    import jax
    import numpy as np
    cell = spec.Spec().cell(workload)
    _cut_to_three_layers(cell['config'])
    with _engine_for_the_chip(cell) as eng:
        pool, args = eng.pool, eng._decode_args()
        leaves = _pool_leaves(eng)
        # nothing asked, as on the parent: the copies are there to see
        assert pool.own_layout == [None] * len(leaves)
        for jit in (eng._decode_jit, eng._decode_half_jit):
            text = _compile(jit, args, one_chip).as_text()
            assert len(whole_leaf_copies(text, leaves)) == relaid
        whole = _compile_whole(eng, one_chip)
        assert pool.own_layout_leaves == own
        asked = [f is not None for f in pool.own_layout]
        assert asked == [v.ndim == 4 and v.shape[-1] % 128 != 0
                         for v in leaves]
        half = _compile(eng._decode_half_jit, args, one_chip)
        device_bytes = sum(pool.entry_bytes().values())
        assert device_bytes > pool.pool_bytes       # lanes padded
        for compiled in (whole, half):
            assert not whole_leaf_copies(compiled.as_text(), leaves)
            assert compiled.memory_analysis().alias_size_in_bytes \
                == device_bytes
            taken = jax.tree_util.tree_leaves(compiled.input_formats[0][3])
            back = jax.tree_util.tree_leaves(compiled.output_formats[1])
            for fmt, t, b in zip(pool.formats, taken, back):
                assert t == b and (fmt is None or fmt == t)
        # every leaf is held with the head size in the lanes
        for fmt in pool.formats:
            assert fmt is None or fmt.layout.major_to_minor == (0, 1, 2, 3)
        # a leaf the rule leaves out was taken in the default layout
        # before and after
        row = pool.row_spec
        slot = np.int32(1)
        for jit, at, in_place in (
                (pool._seat_jit, (pool.rows, row, slot), True),
                (pool._copy_jit, (pool.rows, slot, slot), True),
                (pool._slice_jit, (pool.rows, slot), False)):
            compiled = _compile(jit, at, one_chip)
            taken = jax.tree_util.tree_leaves(compiled.input_formats[0][0])
            assert [t for t, f in zip(taken, pool.formats)
                    if f is not None] \
                == [f for f in pool.formats if f is not None]
            if in_place:
                assert jax.tree_util.tree_leaves(
                    compiled.output_formats) == taken
                assert compiled.memory_analysis().alias_size_in_bytes \
                    == device_bytes
                assert not whole_leaf_copies(compiled.as_text(), leaves)


@pytest.mark.slow
@pytest.mark.parametrize('workload', ['serve-chat', 'serve-docs',
                                      'serve-moe-docs'])
def test_the_rule_leaves_out_what_the_compiler_would_not_move(workload,
                                                              one_chip):
    """A leaf of whole lanes asks for nothing, and AUTO would give it
    the layout it has: the default, heads x head size in the minor tile
    (a tile of 4 sublanes for trinity-mini's 4 heads). No whole-leaf
    copy in ENTRY either way."""
    import jax
    from jax.experimental.layout import Format, Layout
    cell = spec.Spec().cell(workload)
    cell['config']['num_hidden_layers'] = 2
    with _engine_for_the_chip(cell) as eng:
        pool, args = eng.pool, eng._decode_args()
        leaves = _pool_leaves(eng)
        assert pool.asks('tpu', one_chip) == [None] * len(leaves)
        default = _compile(eng._decode_jit, args, one_chip)
        assert not whole_leaf_copies(default.as_text(), leaves)
        pool.own_layout = [Format(Layout.AUTO, one_chip)] * len(leaves)
        auto = _compile(eng._decode_jit, args, one_chip)
        had, chosen = (jax.tree_util.tree_leaves(c.input_formats[0][3])
                       for c in (default, auto))
        assert had == chosen
        assert all(f.layout.major_to_minor == (0, 1, 2, 3) for f in had)
        assert not whole_leaf_copies(auto.as_text(), leaves)
