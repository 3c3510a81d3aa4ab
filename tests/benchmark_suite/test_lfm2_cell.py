"""The cell `serve-hybrid-reason`: its counts against numbers worked out
by hand, its roofline reader on made-up spans and a made-up trace, a toy
rehearsal of the cell on the CPU, added to a toy root by new files and
entries alone, and the cell's real entries.

Importing this module also extends `test_program_spans.py`'s pin of the
`per_layer` entries (PR 24 pinned them by equality) by the one this
cell brought: that file and `conftest.py`, which names PR 26's three,
are the benchmark's and a PR may not edit them, so the pin is now
extended from two places (PERF.md section 7e asks a `benchmark` issue to
make it a subset test). Every worker collects every module before a test
runs, so the extension is there when `test_program_spans`' fixture reads
the set."""
import json
import os

import pytest

import _toy
import test_program_spans as _pin
from benchmarks import counts_lfm2 as CL
from benchmarks import spec

NEW_PER_LAYER = {'hybrid_decode_roofline'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-hybrid-reason'
CFG = SPEC.cell(CELL)['config']
GIB = 2.0 ** 30
PUBLISHED_LAYERS = ['conv', 'conv'] + [
    'full_attention', 'conv', 'conv', 'conv'] * 9 + ['full_attention', 'conv']


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_of_the_cut_as_the_file_states():
    assert CL.conv_params(CFG) == 12_582_912 + 4_194_304 + 6_144
    assert CL.attention_params(CFG) \
        == 2 * 4_194_304 + 2 * 1_048_576 + 128 == 10_485_888
    assert CL.dense_mlp_params(CFG) == 72_351_744
    assert CL.expert_params(CFG) == 9_437_184
    assert CL.router_params(CFG) == 131_072 + 64
    assert CL.layer_params(CFG, 0) == 89_139_200        # dense, conv
    assert CL.layer_params(CFG, 1) == 614_600_896       # experts, attention
    assert CL.layer_params(CFG, 2) == 620_898_368       # experts, conv
    assert CL.total_params(CFG) == CFG['params'] == 2_700_654_976
    assert round(2 * CFG['params'] / GIB, 2) == 5.03


def test_parameters_uncut():
    uncut = dict(CFG, num_hidden_layers=40, num_dense_layers=2,
                 layer_types=PUBLISHED_LAYERS)
    assert CL.total_params(uncut) == CFG['published']['params'] \
        == 23_843_661_440
    assert PUBLISHED_LAYERS.count('full_attention') == 10


def test_the_file_holds_the_published_widths_and_the_four_cuts():
    bench = {c['name']: c for c in SPEC.bench['configs']}['lfm2-24b-a2b']
    assert CFG['reduced'] == bench['reduced'] == [
        'num_hidden_layers', 'num_dense_layers', 'layer_types',
        'max_position_embeddings']
    assert set(CFG['changed']) == set(CFG['reduced'])
    widths = dict(hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=8, intermediate_size=11776,
                  moe_intermediate_size=1536, num_experts=64,
                  num_experts_per_tok=4, conv_L_cache=3, conv_bias=False,
                  vocab_size=65536, norm_eps=1e-5, norm_topk_prob=True,
                  use_expert_bias=True, routed_scaling_factor=1)
    assert {k: CFG[k] for k in widths} == widths
    assert CFG['rope_parameters']['rope_theta'] == 1_000_000
    assert CFG['layer_types'] == ['conv', 'full_attention'] + ['conv'] * 3
    assert (CFG['num_hidden_layers'], CFG['num_dense_layers'],
            CFG['max_position_embeddings']) == (5, 1, 4096)
    pub = CFG['published']
    assert (pub['num_hidden_layers'], pub['num_dense_layers'],
            pub['max_position_embeddings']) == (40, 2, 128_000)
    for key in ('deployment', 'assumed', 'changed', 'published'):
        assert CFG[key]
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'LFM2-24B-A2B')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert row['config']['layer_types'] == PUBLISHED_LAYERS
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_bytes_of_a_decode_substep_by_hand():
    # always read, in parameters: four conv operators and one attention,
    # two norms a layer, the dense MLP, four routers and biases, the
    # final norm, the embedding once as the head
    always = (4 * 16_783_360 + 10_485_888 + 5 * 4096 + 72_351_744
              + 4 * 131_136 + 2048 + 65_536 * 2048)
    assert CL.always_read_params(CFG) == always == 284_735_872
    # 32 slots at 2180 rows of ONE attention layer, 4 KiB a row; the
    # state of 4 conv layers x 3 x 2048 x 4 B a slot, read and written
    assert CL.kv_row_bytes_per_layer(CFG) == 4096
    assert CL.state_bytes_per_slot(CFG) == 4 * 3 * 2048 * 4 == 98_304
    rows, state = 32 * 2180, 32 * 98_304 * 2
    need = CL.decode_substep_bytes(CFG, 55.7, rows, state)
    assert need == pytest.approx(
        2 * (always + 4 * 55.7 * 9_437_184) + rows * 4096 + state)
    assert round(need / 1e9, 2) == 5.07
    # nothing touched, nothing cached, no state: the other weights alone
    assert CL.decode_substep_bytes(CFG, 0, 0, 0) == 2 * always
    # the pool of the cell: ONE attention layer x 32 slots x 4096 rows
    assert 32 * 4096 * CL.kv_row_bytes_per_layer(CFG) / GIB == 0.5
    assert 32 * CL.state_bytes_per_slot(CFG) == 3 * 2 ** 20


# ---------------------------------------------------------------------------
# the readers, on made-up spans and made-up trace summaries
# ---------------------------------------------------------------------------
def _context(substep_s, rounds, peaks=True, trace=True, events=()):
    from paddle_tpu import observability as obs
    log = obs.get_event_log()
    log.clear()
    ident = iter(range(1, 1000))
    for i, attrs in enumerate(rounds):
        step = next(ident)
        log.append({'name': 'serving.router_step', 'ph': 'X', 'ts': 1.0 * i,
                    'dur': 0.5, 'id': step, 'parent': 0})
        log.append({'name': 'serving.decode_round', 'ph': 'X',
                    'ts': 1.0 * i + 0.1, 'dur': 0.3, 'id': next(ident),
                    'parent': step, 'attrs': attrs})
    raw = {'decode_rounds': len(rounds), 'decode_block': 4}
    summary = {'modules0': {'jit__decode_block_fn(123)': (
        substep_s * 4 * 10, 10), 'jit__prefill_fn(4)': (0.5, 2)},
        'events0': list(events)}
    return spec.ReadContext(
        SPEC.cell(CELL), raw, summary if trace else None,
        SPEC.peaks('TPU v5 lite') if peaks else None, None)


ROWS, STATE = 32 * 2180, 32 * 98_304 * 2 * 4      # a round: four sub-steps


def _round(touched=56 * 16, rows=ROWS, state=STATE):
    return {'active': 32, 'slots': 32, 'real_rows': 32 * 2180,
            'needed_rows': rows, 'read_rows': 32 * 4096, 'rows': 4096,
            'experts_touched': touched, 'expert_layer_substeps': 16,
            'experts': 64, 'attn_layers': 1, 'state_layers': 4,
            'state_bytes': state}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('lfm2_decode_roofline')
    need = CL.decode_substep_bytes(CFG, 56.0, ROWS, STATE / 4)
    least = need / 819e9
    assert read(_context(4 * least, [_round(), _round()]),
                match='decode') == pytest.approx(25.0)
    # a sub-step that takes exactly its bytes' time reads 100, and one
    # that takes longer never more
    assert read(_context(least, [_round()]), match='decode') \
        == pytest.approx(100.0)
    for slower in (1.01, 2.0, 7.0):
        assert read(_context(slower * least, [_round()]),
                    match='decode') < 100.0
    # means over rounds: touched per layer and sub-step, rows and state
    # per round
    mixed = _context(4 * least, [_round(48 * 16, 0, 0),
                                 _round(64 * 16, 2 * ROWS, 2 * STATE)])
    assert read(mixed, match='decode') == pytest.approx(25.0)


def test_roofline_reader_reports_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('lfm2_decode_roofline')
    for missing in ('state_bytes', 'needed_rows', 'experts_touched'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    # the two span metrics the cell shares read the same made-up rounds
    share = SPEC.read_metric('moe_experts_touched_share',
                             _context(0.01, [_round(), _round(48 * 16)]))
    assert share == pytest.approx(100.0 * (56 + 48) / 2 / 64)
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(100.0 * 2180 / 4096)


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_hybrid'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    cfg = dict(CFG, name='toy-lfm2', source='none: toy', vocab_size=512,
               hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
               max_position_embeddings=64, param_dtype='float32',
               params=0, reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-lfm2.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    # prompts shorter than their bucket, answers of several blocks
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30})
    _write(os.path.join(bdir, 'traffic', 'toy-reason.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-hybrid.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-lfm2', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-lfm2.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-hybrid', 'config': 'toy-lfm2', 'traffic': 'toy-reason',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-hybrid')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('moe_experts_touched_share', 'attn_needed_rows_share',
                 *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-hybrid']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    out, lines = _toy.run_toy(toy_root, 'toy-hybrid', seed=5000000041,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    # 2 slots x 2 picks of 8 experts: 2 to 4 distinct a layer
    assert 25.0 <= m['moe_experts_touched_share']['value'] <= 50.0
    # ONE layer of five attends: the rows written of the rows read
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    # these need a device plane: nothing on the CPU, and no error
    assert not NEW_PER_LAYER & set(m)
    assert 'decode_roofline' not in m and 'moe_decode_roofline' not in m


def test_toy_rehearsal_end_to_end_metrics(toy_root):
    out, _ = _toy.run_toy(toy_root, 'toy-hybrid', seed=42, seconds=1.5)
    assert out['correct'] is True
    assert set(out['metrics']) == {'tpot_p50_ms', 'setup_s'}


_ALTERED_TOKEN = '''
import numpy as _np
import paddle_tpu.serving.engine as _e
_fetch = _e._from_device
def _altered(x):
    v = _np.array(_fetch(x))
    if v.dtype.kind == "i" and v.ndim == 2 and v.shape[0] == 2:
        v[:, -1] = (v[:, -1] + 1) % 512     # one token of each block altered
    return v
_e._from_device = _altered
'''


def test_toy_rehearsal_altered_served_token_is_not_correct(toy_root):
    out, lines = _toy.run_toy(toy_root, 'toy-hybrid', seed=43, seconds=2.0,
                              patch=_ALTERED_TOKEN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('lfm2-24b-a2b', 'reason-hybrid', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | {'moe_experts_touched_share',
                            'attn_needed_rows_share',
                            'decode_substep_ms'} <= layer
    # their counts are the dense blocks' and AFMoE's
    assert not {'decode_roofline', 'moe_decode_roofline'} & layer
    for m in SPEC.bench['per_layer']:
        if m['name'] in NEW_PER_LAYER:
            assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['slots'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == (32, 4096, 4, 4)
    assert tr['prompt']['max'] + tr['output']['max'] <= 3584 \
        < tr['max_length']
    assert max(tr['buckets']) >= tr['prompt']['max']
    limits = SPEC.cell(CELL)['limits']
    assert limits['control'] == 'fp8' and 0 < limits['served_gap'] < 2


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER <= _pin.NEW_DEVICE
