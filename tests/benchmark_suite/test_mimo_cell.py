"""The cell `serve-swa-reason`: its counts against numbers worked out by
hand, its roofline and picks-held readers on made-up spans and a made-up
trace, a toy rehearsal of the cell on the CPU, added to a toy root by
new files and entries alone, and the cell's real entries.

Importing this module also extends `test_program_spans.py`'s pin of the
`per_layer` entries (PR 24 pinned them by equality) by the two this
cell brought: that file, `conftest.py` (PR 26's three) and
`test_lfm2_cell.py` (PR 30's one) are the benchmark's and a PR may not
edit them, so the pin is now extended from three places (PERF.md section
7e asks a `benchmark` issue to make it a subset test). Every worker
collects every module before a test runs, so the extension is there when
`test_program_spans`' fixture reads the set."""
import json
import os

import pytest

import _toy
import test_program_spans as _pin
from benchmarks import counts_mimo as CM
from benchmarks import spec

NEW_PER_LAYER = {'swa_decode_roofline', 'moe_picks_held_share'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-swa-reason'
CFG = SPEC.cell(CELL)['config']
GIB = 2.0 ** 30
PUBLISHED_PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
SIX_CUTS = ['num_hidden_layers', 'hybrid_layer_pattern', 'moe_layer_freq',
            'n_routed_experts', 'vocab_size', 'max_position_embeddings']


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_of_the_cut_as_the_file_states():
    # a full layer (layer 0): q, k, v, o; a window layer adds 4 KV heads
    # and 64 of sink
    assert CM.attention_params(CFG, 0) \
        == 50_331_648 + 3_145_728 + 2_097_152 + 33_554_432 == 89_128_960
    assert CM.attention_params(CFG, 1) \
        == 50_331_648 + 6_291_456 + 4_194_304 + 33_554_432 + 64 \
        == 94_371_904
    assert CM.dense_mlp_params(CFG) == 201_326_592
    assert CM.expert_params(CFG) == 25_165_824
    assert CM.router_params(CFG) == 1_048_576 + 256
    assert CM.norm_params(CFG) == 8_192
    assert CM.layer_params(CFG, 0) == 290_463_744       # dense, full
    assert CM.layer_params(CFG, 1) == 498_082_112       # experts, window
    assert CM.layer_params(CFG, 6) == 492_839_168       # experts, full
    assert CM.total_params(CFG) == CFG['params'] == 3_429_955_392 \
        == 290_463_744 + 5 * 498_082_112 + 492_839_168 + 156_237_824 + 4_096
    assert round(2 * CFG['params'] / GIB, 2) == 6.39
    assert round(2 * CFG['params'] / 1e9, 2) == 6.86


def test_parameters_uncut_and_active():
    pub = CFG['published']
    uncut = dict(CFG, num_hidden_layers=48,
                 hybrid_layer_pattern=PUBLISHED_PATTERN,
                 moe_layer_freq=[0] + [1] * 47, n_routed_experts=256,
                 vocab_size=152_576)
    # 1 dense layer (full) + 39 window and 8 full expert layers
    assert PUBLISHED_PATTERN.count(1) == 39
    assert CM.total_params(uncut) == pub['params'] == 308_778_780_864
    # top-8 of the 256: "309B-A15B"
    assert CM.total_params(uncut, 8) == pub['active_params'] \
        == 15_445_936_320
    # one expert layer whole: 6.5 B parameters, 13 GB in bf16
    assert round(CM.layer_params(uncut, 1) / 1e9, 1) == 6.5


def test_the_file_holds_the_published_widths_and_the_six_cuts():
    bench = {c['name']: c for c in SPEC.bench['configs']}['mimo-v2.5']
    assert CFG['reduced'] == bench['reduced'] == SIX_CUTS
    assert set(CFG['changed']) == set(CFG['reduced'])
    widths = dict(hidden_size=4096, num_attention_heads=64,
                  num_key_value_heads=4, swa_num_key_value_heads=8,
                  head_dim=192, v_head_dim=128, swa_head_dim=192,
                  swa_v_head_dim=128, partial_rotary_factor=0.334,
                  rope_theta=10_000_000, swa_rope_theta=10_000,
                  sliding_window=128, attention_value_scale=0.707,
                  intermediate_size=16_384, moe_intermediate_size=2048,
                  num_experts_per_tok=8, layernorm_epsilon=1e-5,
                  add_swa_attention_sink_bias=True,
                  add_full_attention_sink_bias=False, norm_topk_prob=True,
                  scoring_func='sigmoid', n_group=1, topk_group=1,
                  n_shared_experts=None, routed_scaling_factor=None)
    assert {k: CFG[k] for k in widths} == widths
    assert int(CFG['head_dim'] * CFG['partial_rotary_factor']) == 64
    assert CFG['expert_share'] == {'routed': 256, 'first': 0}
    assert (CFG['num_hidden_layers'], CFG['n_routed_experts'],
            CFG['vocab_size'], CFG['max_position_embeddings']) \
        == (7, 16, 19_072, 4096)
    assert CFG['hybrid_layer_pattern'] == [0, 1, 1, 1, 1, 1, 0]
    assert CFG['moe_layer_freq'] == [0, 1, 1, 1, 1, 1, 1]
    assert CFG['vocab_size'] * 8 == 152_576     # an eighth: the floor
    pub = CFG['published']
    assert (pub['num_hidden_layers'], pub['n_routed_experts'],
            pub['vocab_size'], pub['max_position_embeddings']) \
        == (48, 256, 152_576, 1_048_576)
    assert pub['hybrid_layer_pattern'] == PUBLISHED_PATTERN
    for key in ('deployment', 'assumed', 'changed', 'published'):
        assert CFG[key]
    assert (CFG['model_class'], CFG['param_dtype'], CFG['kv_dtype']) \
        == ('MiMoV2ForCausalLM', 'bfloat16', 'float32')
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'MiMo-V2.5')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert row['config']['hybrid_layer_pattern'] == PUBLISHED_PATTERN
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_bytes_of_a_decode_substep_by_hand():
    # always read, in parameters: two full and five window attentions,
    # two norms a layer, the dense MLP, six routers and biases, the final
    # norm, the head's slice
    always = (2 * 89_128_960 + 5 * 94_371_904 + 7 * 8_192 + 201_326_592
              + 6 * 1_048_832 + 4_096 + 19_072 * 4_096)
    assert CM.always_read_params(CFG) == always == 935_917_376
    # K 192 + V 128 wide, float32: 4 heads on a full layer, 8 on a window
    assert CM.kv_row_bytes(CFG, False) == 4 * 320 * 4 == 5_120
    assert CM.kv_row_bytes(CFG, True) == 8 * 320 * 4 == 10_240
    # a made-up round: 32 slots at 1,600 rows on the two full layers,
    # every ring full, 10.2 of the 16 held experts touched a layer
    full, ring = 32 * 2 * 1_600, 32 * 5 * 128
    need = CM.decode_substep_bytes(CFG, 10.2, full, ring)
    assert need == pytest.approx(
        2 * (always + 6 * 10.2 * 25_165_824) + full * 5_120 + ring * 10_240)
    assert round(need / 1e9, 2) == 5.69
    # nothing touched, nothing cached: the other weights alone, 1.87 GB
    assert CM.decode_substep_bytes(CFG, 0, 0, 0) == 2 * always
    assert round(2 * always / 1e9, 2) == 1.87
    # the pool of the cell: 46.25 MiB a slot, 1.45 GiB for 32
    assert CM.slot_bytes(CFG, 4096) == 2 * 4096 * 5_120 + 5 * 128 * 10_240 \
        == 46.25 * 2 ** 20
    assert 32 * CM.slot_bytes(CFG, 4096) == 1_551_892_480
    # with max_length rows on every layer it would be 7.5 GiB
    assert 32 * 4096 * (2 * 5_120 + 5 * 10_240) / GIB == 7.5


# ---------------------------------------------------------------------------
# the readers, on made-up spans and made-up trace summaries
# ---------------------------------------------------------------------------
def _context(substep_s, rounds, peaks=True, trace=True):
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
    summary = {'modules0': {
        'jit__decode_block_fn(123)': (substep_s * 4 * 6, 6),
        'jit__decode_block_half_fn(7)': (substep_s * 4 * 4, 4),
        'jit__state_prefill_fn(4)': (0.5, 2)}, 'events0': []}
    return spec.ReadContext(
        SPEC.cell(CELL), raw, summary if trace else None,
        SPEC.peaks('TPU v5 lite') if peaks else None, None)


FULL, RING = 32 * 2 * 1_600, 32 * 5 * 128


def _round(touched=10 * 24, full=FULL, ring=RING, held=384):
    return {'active': 32, 'slots': 32, 'real_rows': 32 * 1_600,
            'needed_rows': full + ring, 'needed_rows_window': ring,
            'read_rows': 32 * (2 * 4096 + 5 * 128), 'rows': 4096,
            'experts_touched': touched, 'expert_layer_substeps': 24,
            'expert_kernel_substeps': 24, 'experts': 16,
            'picks': 32 * 8 * 24, 'picks_held': held}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('mimo_decode_roofline')
    need = CM.decode_substep_bytes(CFG, 10.0, FULL, RING)
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
    # means over rounds: touched per layer and sub-step; full and ring
    # rows per round, each at its own row bytes
    mixed = _context(4 * least, [_round(8 * 24, 0, 0),
                                 _round(12 * 24, 2 * FULL, 2 * RING)])
    assert read(mixed, match='decode') == pytest.approx(25.0)
    # rows moved from the full layers to the rings cost twice as much
    moved = _context(4 * least, [_round(full=FULL - 1000, ring=RING + 1000)])
    assert read(moved, match='decode') > 25.0


def test_readers_report_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('mimo_decode_roofline')
    for missing in ('needed_rows_window', 'needed_rows', 'experts_touched'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    # a parent's span: no picks, so no share of them
    bare = {k: v for k, v in _round().items()
            if k not in ('picks', 'picks_held')}
    assert SPEC.read_metric('moe_picks_held_share',
                            _context(0.01, [bare])) is None


def test_the_span_metrics_of_the_cell_on_made_up_rounds():
    ctx = _context(0.01, [_round(held=384), _round(held=768)])
    # 6,144 picks a round; 1/16 of them is 384
    assert SPEC.read_metric('moe_picks_held_share', ctx) \
        == pytest.approx(100.0 * (384 + 768) / 2 / 6144)
    assert SPEC.read_metric('moe_picks_held_share',
                            _context(0.01, [_round()])) \
        == pytest.approx(6.25)
    share = SPEC.read_metric('moe_experts_touched_share',
                             _context(0.01, [_round(), _round(8 * 24)]))
    assert share == pytest.approx(100.0 * (10 + 8) / 2 / 16)
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(
        100.0 * (2 * 1_600 + 5 * 128) / (2 * 4096 + 5 * 128))


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_swa'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    # window 8 in slots of 64: a request of 40 tokens wraps its rings
    # five times; experts 4-7 of a router over 16
    cfg = dict(CFG, name='toy-mimo', source='none: toy', vocab_size=512,
               hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=1, swa_num_key_value_heads=2,
               head_dim=24, v_head_dim=16, sliding_window=8,
               num_hidden_layers=4, hybrid_layer_pattern=[0, 1, 1, 0],
               moe_layer_freq=[0, 1, 1, 1], n_routed_experts=4,
               expert_share={'routed': 16, 'first': 4},
               num_experts_per_tok=2, max_position_embeddings=64,
               param_dtype='float32', params=0, reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-mimo.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    # prompts shorter than their bucket, answers of several windows
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30})
    _write(os.path.join(bdir, 'traffic', 'toy-swa.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-swa.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-mimo', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-mimo.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-swa', 'config': 'toy-mimo', 'traffic': 'toy-swa',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-swa')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('moe_experts_touched_share', 'attn_needed_rows_share',
                 *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-swa']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    out, lines = _toy.run_toy(toy_root, 'toy-swa', seed=5000000041,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    # 2 slots x 2 picks over 16, 4 held: a quarter lands here if even
    assert 5.0 <= m['moe_picks_held_share']['value'] <= 60.0
    assert 0.0 < m['moe_experts_touched_share']['value'] <= 50.0
    # two rings of 8 rows and two full layers of 64 (or 32) a slot
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    # needs a device plane: nothing on the CPU, and no error
    assert 'swa_decode_roofline' not in m
    assert 'decode_roofline' not in m and 'moe_decode_roofline' not in m


def test_toy_rehearsal_end_to_end_metrics(toy_root):
    out, _ = _toy.run_toy(toy_root, 'toy-swa', seed=42, seconds=1.5)
    assert out['correct'] is True
    assert set(out['metrics']) == {'tpot_p50_ms', 'setup_s'}


_ALTERED_TOKEN = '''
import numpy as _np
import paddle_tpu.serving.engine as _e
_fetch = _e._from_device
def _altered(x):
    v = _np.array(_fetch(x))
    if v.dtype.kind == "i" and v.ndim == 2 and v.shape == (2, 4):
        v[:, -1] = (v[:, -1] + 1) % 512     # one token of each block altered
    return v
_e._from_device = _altered
'''


def test_toy_rehearsal_altered_served_token_is_not_correct(toy_root):
    out, lines = _toy.run_toy(toy_root, 'toy-swa', seed=43, seconds=2.0,
                              patch=_ALTERED_TOKEN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('mimo-v2.5', 'reason-swa', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | {'moe_experts_touched_share',
                            'attn_needed_rows_share',
                            'decode_substep_ms'} <= layer
    # their counts are the dense blocks', AFMoE's and LFM2's
    assert not {'decode_roofline', 'moe_decode_roofline',
                'hybrid_decode_roofline'} & layer
    for m in SPEC.bench['per_layer']:
        if m['name'] in NEW_PER_LAYER:
            assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['kind'], tr['slots'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == ('serve_backlog', 32, 4096, 4, 4)
    assert tr['buckets'] == [512, 1024]
    assert (tr['prompt']['min'], tr['prompt']['max']) == (256, 1024)
    assert (tr['output']['min'], tr['output']['max']) == (1024, 2560)
    assert tr['prompt']['max'] + tr['output']['max'] <= 3584 \
        < tr['max_length']
    assert max(tr['buckets']) >= tr['prompt']['max']
    # every request's rings wrap 8 to 20 times
    assert tr['output']['min'] // CFG['sliding_window'] == 8
    assert tr['output']['max'] // CFG['sliding_window'] == 20
    limits = SPEC.cell(CELL)['limits']
    assert limits['control'] == 'fp8' and 0 < limits['served_gap'] < 2


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER <= _pin.NEW_DEVICE
