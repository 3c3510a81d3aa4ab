"""The cell `serve-mla-long`: its counts against numbers worked out by
hand, its roofline reader on made-up spans and a made-up trace, a toy
rehearsal of the cell on the CPU, added to a toy root by new files and
entries alone, and the cell's real entries.

Importing this module also extends `test_program_spans.py`'s pin of the
`per_layer` entries (PR 24 pinned them by equality) by the one this cell
brought: that file, `conftest.py` (PR 26's three), `test_lfm2_cell.py`
(PR 30's one), `test_mimo_cell.py` (PR 32's two) and
`test_host_causes.py` (PR 35's six) are the benchmark's and a PR may not
edit them, so the pin is now extended from five places (PERF.md section
7e asks a `benchmark` issue to make it a subset test). Every worker
collects every module before a test runs, so the extension is there when
`test_program_spans`' fixture reads the set.

`test_host_causes.py` (PR 35) pins more than names: that its six entries
are the LAST six of `per_layer`, and the `workloads` of the two scope
shares this cell is appended to. An entry added at the end, where the
contract wants it, fails that as it stands. So that module's `SPEC` is
given, here at import, the benchmark AS IT STOOD before this cell
(`_before_this_cell`: this PR's entries taken out again, nothing else
touched), and a test below holds that view to differ from the real file
by exactly those entries. Run alone, `test_host_causes.py` fails that one
test, as `test_program_spans.py` fails its pin alone (PERF.md 7e)."""
import copy
import json
import os

import pytest

import _toy
import test_host_causes as _host
import test_program_spans as _pin
from benchmarks import counts_dsv3 as CD
from benchmarks import spec

NEW_PER_LAYER = {'mla_decode_roofline'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-mla-long'
APPENDED_TO = {'tpot_p50_ms', 'attn_needed_rows_share',
               'moe_experts_touched_share', 'attn_decode_share',
               'experts_decode_share'}


def _before_this_cell(bench):
    """`BENCHMARK.json` without what this cell added: its configuration,
    its workload, its metric, and its name at the end of five lists."""
    old = copy.deepcopy(bench)
    old['configs'] = [c for c in old['configs']
                      if c['name'] != 'kanana-2-30b-a3b']
    old['workloads'] = [w for w in old['workloads'] if w['name'] != CELL]
    old['per_layer'] = [m for m in old['per_layer']
                        if m['name'] not in NEW_PER_LAYER]
    for m in old['end_to_end'] + old['per_layer']:
        if m['name'] in APPENDED_TO and m['workloads'][-1] == CELL:
            m['workloads'] = m['workloads'][:-1]
    return old


_host.SPEC = spec.Spec()
_host.SPEC.bench = _before_this_cell(_host.SPEC.bench)
CFG = SPEC.cell(CELL)['config']
GIB = 2.0 ** 30
TWO_CUTS = ['num_hidden_layers', 'max_position_embeddings']


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_of_the_cut_as_the_file_states():
    # q 2048 x (32 x 192); kv_a 2048 x (512 + 64); the latent norm; kv_b
    # 512 x (32 x 256); o (32 x 128) x 2048
    assert CD.attention_params(CFG) \
        == 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608 \
        == 26_345_984
    assert CD.dense_mlp_params(CFG) == 37_748_736
    assert CD.expert_params(CFG) == 4_718_592
    assert CD.shared_params(CFG) == 9_437_184
    assert CD.router_params(CFG) == 262_144 + 128
    assert CD.norm_params(CFG) == 4_096
    assert CD.layer_params(CFG, 0) == 64_098_816            # dense
    assert CD.layer_params(CFG, 1) == 640_029_312           # experts
    assert CD.layer_params(CFG, 1, 0) == 36_049_536         # beside them
    assert CD.expert_layers(CFG) == 4
    assert CD.total_params(CFG) == CFG['params'] == 3_149_554_688 \
        == 64_098_816 + 4 * 640_029_312 + 525_336_576 + 2_048
    assert round(2 * CFG['params'] / GIB, 2) == 5.87
    assert round(2 * CFG['params'] / 1e9, 2) == 6.30


def test_parameters_uncut_and_active():
    pub = CFG['published']
    assert CD.total_params(CFG, layers=48) == pub['params'] \
        == 64_098_816 + 47 * 640_029_312 + 525_336_576 + 2_048 \
        == 30_670_815_104
    # top-6 of the 128 and the shared MLP: "30B-A3B"
    assert CD.total_params(CFG, 6, layers=48) == pub['active_params'] \
        == 3_614_408_576
    assert CD.expert_layers(CFG, 48) == 47
    # a fifth expert layer: 7.06 GiB of weights, twice in set-up
    assert round(2 * (CFG['params'] + 640_029_312) / GIB, 2) == 7.06


def test_the_file_holds_the_published_widths_and_the_two_cuts():
    bench = {c['name']: c for c in SPEC.bench['configs']}['kanana-2-30b-a3b']
    assert CFG['reduced'] == bench['reduced'] == TWO_CUTS
    assert set(CFG['changed']) == set(CFG['reduced'])
    widths = dict(hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=32, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
                  head_dim=64, kv_lora_rank=512, q_lora_rank=None,
                  rope_theta=1_000_000, rope_interleave=True,
                  rope_scaling=None, intermediate_size=6144,
                  moe_intermediate_size=768, n_routed_experts=128,
                  num_experts_per_tok=6, n_shared_experts=2,
                  routed_scaling_factor=2.448, norm_topk_prob=True,
                  scoring_func='sigmoid', topk_method='noaux_tc',
                  n_group=1, topk_group=1, first_k_dense_replace=1,
                  moe_layer_freq=1, rms_norm_eps=1e-6,
                  vocab_size=128_256, attention_bias=False,
                  tie_word_embeddings=False)
    assert {k: CFG[k] for k in widths} == widths
    assert (CFG['num_hidden_layers'], CFG['max_position_embeddings']) \
        == (5, 16_384)
    pub = CFG['published']
    assert (pub['num_hidden_layers'], pub['max_position_embeddings']) \
        == (48, 32_768)
    for key in ('deployment', 'assumed', 'changed', 'published'):
        assert CFG[key]
    assert 'no layer is divided' in CFG['deployment'].lower()
    assert (CFG['model_class'], CFG['param_dtype'], CFG['kv_dtype']) \
        == ('DeepseekV3ForCausalLM', 'bfloat16', 'float32')
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'kanana-2-30b-a3b-instruct-2601')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_bytes_of_a_decode_substep_by_hand():
    # always read, in parameters: five attentions, two norms a layer, the
    # dense MLP, four shared MLPs, routers and biases, the final norm,
    # the head
    always = (5 * 26_345_984 + 5 * 4_096 + 37_748_736 + 4 * 9_437_184
              + 4 * 262_272 + 2_048 + 128_256 * 2_048)
    assert CD.always_read_params(CFG) == always == 470_967_296
    assert round(2 * always / 1e9, 2) == 0.94
    # 512 + 64 float32 numbers a row a layer, whatever the heads
    assert CD.latent_row_bytes(CFG) == 576 * 4 == 2_304
    # as K and V by head the same row would be 32 x (192 + 128) x 4
    assert 32 * (192 + 128) * 4 / 2_304 == pytest.approx(17.8, abs=0.03)
    # a made-up round: 16 slots at 8,700 rows on five layers, 67.7 of the
    # 128 experts touched a layer (16 x 6 picks: 1 - (127/128)^96)
    rows = 16 * 5 * 8_700
    need = CD.decode_substep_bytes(CFG, 67.7, rows)
    assert need == pytest.approx(
        2 * (always + 4 * 67.7 * 4_718_592) + rows * 2_304)
    assert round(need / 1e9, 2) == 5.10
    assert round(128 * (1 - (127 / 128) ** 96), 1) == 67.7
    # nothing touched, nothing cached: the other weights alone
    assert CD.decode_substep_bytes(CFG, 0, 0) == 2 * always
    # the pool of the cell: 180 MiB a slot, 2.8125 GiB for 16
    assert CD.slot_bytes(CFG, 16_384) == 5 * 16_384 * 2_304 \
        == 188_743_680
    assert 16 * CD.slot_bytes(CFG, 16_384) == 3_019_898_880 \
        == 2.8125 * GIB


# ---------------------------------------------------------------------------
# the reader, on made-up spans and made-up trace summaries
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
        'jit__prefill_fn(4)': (0.5, 2)}, 'events0': []}
    return spec.ReadContext(
        SPEC.cell(CELL), raw, summary if trace else None,
        SPEC.peaks('TPU v5 lite') if peaks else None, None)


ROWS = 16 * 5 * 8_700


def _round(touched=68 * 16, rows=ROWS):
    return {'active': 16, 'slots': 16, 'real_rows': 16 * 8_700,
            'needed_rows': rows, 'read_rows': 16 * 5 * 16_384,
            'rows': 16_384, 'experts_touched': touched,
            'expert_layer_substeps': 16, 'expert_kernel_substeps': 16,
            'experts': 128, 'latent_layers': 5, 'latent_row_bytes': 11_520}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('mla_decode_roofline')
    need = CD.decode_substep_bytes(CFG, 68.0, ROWS)
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
    # means over rounds: touched per layer and sub-step, rows per round
    mixed = _context(4 * least, [_round(60 * 16, 0),
                                 _round(76 * 16, 2 * ROWS)])
    assert read(mixed, match='decode') == pytest.approx(25.0)
    # a row's bytes are the span's: what the pool holds is what is counted
    # (here the count's own 2,304 a layer; a bf16 pool's rows are half)
    assert _round()['latent_row_bytes'] == 5 * CD.latent_row_bytes(CFG)
    half = CD.decode_substep_bytes(CFG, 68.0, ROWS, 1_152)
    assert need - half == ROWS * 1_152
    assert read(_context(least, [dict(_round(), latent_row_bytes=5_760)]),
                match='decode') == pytest.approx(100.0 * half / need)


def test_reader_reports_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('mla_decode_roofline')
    # a parent's span, or another model's: no latent row bytes
    for missing in ('latent_row_bytes', 'latent_layers', 'needed_rows',
                    'experts_touched'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    assert read(_context(0.01, []), match='decode') is None


def test_the_span_metrics_of_the_cell_on_made_up_rounds():
    share = SPEC.read_metric('moe_experts_touched_share',
                             _context(0.01, [_round(), _round(60 * 16)]))
    assert share == pytest.approx(100.0 * (68 + 60) / 2 / 128)
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(100.0 * 8_700 / 16_384)


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_mla'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    cfg = dict(CFG, name='toy-kanana', source='none: toy', vocab_size=512,
               hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
               v_head_dim=16, head_dim=8, num_hidden_layers=3,
               n_routed_experts=8, num_experts_per_tok=2,
               max_position_embeddings=64, param_dtype='float32',
               params=0, reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-kanana.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30})
    _write(os.path.join(bdir, 'traffic', 'toy-mla.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-mla.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-kanana', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-kanana.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-mla', 'config': 'toy-kanana', 'traffic': 'toy-mla',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-mla')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('moe_experts_touched_share', 'attn_needed_rows_share',
                 'attn_decode_share', 'experts_decode_share',
                 *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-mla']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    out, lines = _toy.run_toy(toy_root, 'toy-mla', seed=5000000041,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    # 2 slots x 2 picks over 8 experts
    assert 0.0 < m['moe_experts_touched_share']['value'] <= 50.0
    # three latent layers of 64 (or 32) rows a slot
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    # these need a device plane: nothing on the CPU, and no error
    assert not {'mla_decode_roofline', 'attn_decode_share',
                'experts_decode_share', 'decode_roofline',
                'moe_decode_roofline'} & set(m)


def test_toy_rehearsal_end_to_end_metrics(toy_root):
    out, _ = _toy.run_toy(toy_root, 'toy-mla', seed=42, seconds=1.5)
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
    out, lines = _toy.run_toy(toy_root, 'toy-mla', seed=43, seconds=2.0,
                              patch=_ALTERED_TOKEN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('kanana-2-30b-a3b', 'long-mla', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | {'moe_experts_touched_share',
                            'attn_needed_rows_share', 'attn_decode_share',
                            'experts_decode_share',
                            'decode_substep_ms'} <= layer
    # their counts are the dense blocks', AFMoE's, LFM2's and MiMo's
    assert not {'decode_roofline', 'moe_decode_roofline',
                'hybrid_decode_roofline', 'swa_decode_roofline'} & layer
    for m in SPEC.bench['per_layer']:
        if m['name'] in NEW_PER_LAYER:
            assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
            assert m['layer'] == ('latent attention and expert layer: '
                                  'nlp/deepseek_v3.py')
    # the entries are the last of their lists: nothing before them moved
    assert SPEC.bench['configs'][-1]['name'] == 'kanana-2-30b-a3b'
    assert SPEC.bench['workloads'][-1]['name'] == CELL
    assert SPEC.bench['per_layer'][-1]['name'] == 'mla_decode_roofline'
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['kind'], tr['slots'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == ('serve_backlog', 16, 16_384, 4, 2)
    assert tr['buckets'] == [6144, 8192, 10_240]
    assert (tr['prompt']['min'], tr['prompt']['max']) == (4096, 10_240)
    assert (tr['output']['min'], tr['output']['max']) == (2048, 4096)
    assert tr['prompt']['max'] + tr['output']['max'] <= 15_872 \
        < tr['max_length'] == CFG['max_position_embeddings']
    assert max(tr['buckets']) >= tr['prompt']['max']
    assert tr['check_requests'] == 2 and tr['trace_s'] == 5.0
    limits = SPEC.cell(CELL)['limits']
    assert limits['control'] == 'fp8' and 0 < limits['served_gap'] < 2


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER <= _pin.NEW_DEVICE


def test_the_view_given_to_the_older_pin_lacks_this_cells_entries_only():
    """What `test_host_causes.py` reads is the real file less exactly
    what this PR appended: one configuration, one workload, one metric,
    all LAST of their lists, and the cell's name LAST of five lists."""
    real, old = SPEC.bench, _host.SPEC.bench
    assert real['configs'][:-1] == old['configs']
    assert real['workloads'][:-1] == old['workloads']
    assert real['per_layer'][-1]['name'] == 'mla_decode_roofline'
    assert [m['name'] for m in real['per_layer'][:-1]] \
        == [m['name'] for m in old['per_layer']]
    appended = set()
    for group in ('end_to_end', 'per_layer'):
        for now, then in zip(real[group], old[group]):
            if now != then:
                assert now == dict(then, workloads=then['workloads']
                                   + [CELL])
                appended.add(now['name'])
    assert appended == APPENDED_TO
    assert {k: v for k, v in real.items()
            if k not in ('configs', 'workloads', 'end_to_end',
                         'per_layer')} \
        == {k: v for k, v in old.items()
            if k not in ('configs', 'workloads', 'end_to_end', 'per_layer')}
