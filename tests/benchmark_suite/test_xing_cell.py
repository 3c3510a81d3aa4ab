"""The cell `serve-mhc-agent`: its counts against numbers worked out by
hand, its roofline reader on made-up spans and a made-up trace, a toy
rehearsal of the cell on the CPU, added to a toy root by new files and
entries alone, and the cell's real entries.

**The pins (PERF.md 7e).** Three older modules of this directory pin
POSITIONS in `BENCHMARK.json` and are the benchmark's, so no PR may edit
them: `test_program_spans.py` (the `per_layer` names by equality),
`test_host_causes.py` (its six entries the LAST six, and the `workloads`
of two scope shares) and `test_kanana_cell.py` (`configs[-1]`,
`workloads[-1]`, `per_layer[-1]`, and its own view of the file differing
from the real one by PR 37's entries only). An entry appended where the
contract wants it fails all three as they stand. So this module, at
import and AFTER importing `test_kanana_cell` (whose own import hands
`test_host_causes` a view), extends `test_program_spans.NEW_DEVICE` by
this cell's two names and gives BOTH `test_kanana_cell.SPEC` and
`test_host_causes.SPEC` the benchmark without this PR's entries (for
`test_host_causes` also without PR 37's, as before); one test below holds
each view to differ from the real file by exactly that. Every worker
collects every module before a test runs, so the views are in place
whichever file a worker is given; run alone, the three older modules
fail their pins, as before.

**This module's own pins are SUBSET pins** — names present, `workloads`
containing the cell, nothing about LAST — so the next cell needs no
fourth hand-over."""
import copy
import json
import os

import pytest

import _toy
import test_host_causes as _host
import test_kanana_cell as _kanana
import test_program_spans as _pin
from benchmarks import counts_xing4 as CX
from benchmarks import spec

NEW_PER_LAYER = {'mhc_decode_share', 'mhc_decode_roofline'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-mhc-agent'
CONFIG = 'xing4.0-29b-a4b'
APPENDED_TO = {'tpot_p50_ms', 'attn_needed_rows_share',
               'moe_experts_touched_share', 'attn_decode_share',
               'experts_decode_share'}


def _before_this_cell(bench):
    """`BENCHMARK.json` without what this cell added: its configuration,
    its workload, its two metrics, and its name in five lists."""
    old = copy.deepcopy(bench)
    old['configs'] = [c for c in old['configs'] if c['name'] != CONFIG]
    old['workloads'] = [w for w in old['workloads'] if w['name'] != CELL]
    old['per_layer'] = [m for m in old['per_layer']
                        if m['name'] not in NEW_PER_LAYER]
    for m in old['end_to_end'] + old['per_layer']:
        if m['name'] in APPENDED_TO:
            m['workloads'] = [w for w in m['workloads'] if w != CELL]
    return old


_kanana.SPEC.bench = _before_this_cell(SPEC.bench)
_host.SPEC.bench = _kanana._before_this_cell(_kanana.SPEC.bench)
CFG = SPEC.cell(CELL)['config']
GIB = 2.0 ** 30
FOUR_CUTS = ['num_hidden_layers', 'first_k_dense_replace', 'vocab_size',
             'max_position_embeddings']


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_of_the_cut_as_the_file_states():
    # q_a 3584 x 768, its norm, q_b 768 x (32 x 192); kv_a 3584 x (512 +
    # 64); the latent norm; kv_b 512 x (32 x 256); o (32 x 128) x 3584
    assert CX.attention_params(CFG) \
        == (2_752_512 + 768 + 4_718_592 + 2_064_384 + 512 + 4_194_304
            + 14_680_064) == 28_411_136
    # Phi (4 x 3584) x (4 + 4 + 16), the biases, the three scalars
    assert CX.hyper_connection_params(CFG) == 14_336 * 24 + 24 + 3 \
        == 344_091
    assert 2 * CX.hyper_connection_params(CFG) == 688_182
    assert CX.dense_mlp_params(CFG) == 99_090_432
    assert CX.expert_params(CFG) == 11_010_048
    assert CX.shared_params(CFG) == 11_010_048
    assert CX.router_params(CFG) == 229_376 + 64
    assert CX.norm_params(CFG) == 7_168
    assert CX.layer_params(CFG, 0) == 128_196_918           # dense
    assert CX.layer_params(CFG, 1) == 744_989_046           # experts
    assert CX.layer_params(CFG, 1, 0) == 40_345_974         # beside them
    assert CX.expert_layers(CFG) == 4
    assert CX.total_params(CFG) == CFG['params'] == 3_343_037_710 \
        == 128_196_918 + 4 * 744_989_046 + 234_881_024 + 3_584
    assert round(2 * CFG['params'] / GIB, 2) == 6.23
    assert round(2 * CFG['params'] / 1e9, 2) == 6.69
    # an expert layer 1.388 GiB; embedding + head whole 1.75 GiB
    assert round(2 * 744_989_046 / GIB, 3) == 1.388
    assert round(2 * 2 * 131_072 * 3_584 / GIB, 2) == 1.75


def test_parameters_uncut_and_active_and_why_the_vocabulary_is_a_quarter():
    pub = CFG['published']
    whole = dict(layers=40, dense=2, vocab=131_072)
    assert CX.total_params(CFG, **whole) == pub['params'] \
        == (2 * 128_196_918 + 38 * 744_989_046 + 939_524_096 + 3_584) \
        == 29_505_505_264
    # top-4 of the 64 and the shared expert: "29B-A4B"
    assert CX.total_params(CFG, 4, **whole) == pub['active_params'] \
        == 4_402_595_824
    assert CX.expert_layers(dict(CFG, first_k_dense_replace=2), 40) == 38
    # whole vocabulary, one dense + four expert layers: 7.54 GiB, which
    # the harness's set-up holds twice (PERF.md 7a): 15.08 of 15.75
    uncut_vocab = CX.total_params(CFG, vocab=131_072)
    assert uncut_vocab == 4_047_680_782
    assert round(2 * uncut_vocab / GIB, 2) == 7.54
    assert round(2 * 2 * uncut_vocab / GIB, 2) == 15.08
    # as cut: twice 6.23 and the pool of 8 x 12,288 rows
    pool = 8 * CX.slot_bytes(CFG, 12_288)
    assert pool == 8 * 5 * 12_288 * 2_304 == 1_132_462_080
    assert round((2 * 2 * CFG['params'] + pool) / GIB, 1) == 13.5


def test_the_file_holds_the_published_widths_and_the_four_cuts():
    bench = {c['name']: c for c in SPEC.bench['configs']}[CONFIG]
    assert CFG['reduced'] == bench['reduced'] == FOUR_CUTS
    assert set(CFG['changed']) == set(CFG['reduced'])
    widths = dict(hidden_size=3584, num_attention_heads=32,
                  num_key_value_heads=32, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                  q_lora_rank=768, rope_theta=10_000, rope_interleave=True,
                  intermediate_size=9216, moe_intermediate_size=1024,
                  n_routed_experts=64, num_experts_per_tok=4,
                  n_shared_experts=1, routed_scaling_factor=2,
                  norm_topk_prob=True, scoring_func='sigmoid',
                  topk_method='noaux_tc', n_group=1, topk_group=1,
                  moe_layer_freq=1, rms_norm_eps=1e-6, hc_mult=4,
                  hc_sinkhorn_iters=20, hc_eps=1e-6,
                  mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
                  attention_bias=False, tie_word_embeddings=False,
                  num_nextn_predict_layers=1, ep_size=1)
    assert {k: CFG[k] for k in widths} == widths
    assert CFG['rope_scaling'] == {
        'beta_fast': 32, 'beta_slow': 1, 'factor': 64, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 4096,
        'type': 'yarn'}
    assert [CFG[k] for k in FOUR_CUTS] == [5, 1, 32_768, 12_288]
    assert [CFG['published'][k] for k in FOUR_CUTS] \
        == [40, 2, 131_072, 262_144]
    for key in ('deployment', 'assumed', 'changed', 'published',
                'left_out'):
        assert CFG[key]
    assert 'no layer is divided' in CFG['deployment'].lower()
    assert 'num_nextn_predict_layers' in CFG['left_out']
    for key in ('sinkhorn', 'flat_norm', 'streams_start', 'streams_end',
                'rope_interleave', 'initializer', 'yarn'):
        assert CFG['assumed'][key]
    assert (CFG['model_class'], CFG['param_dtype'], CFG['kv_dtype']) \
        == ('Xing4ForCausalLM', 'bfloat16', 'float32')
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'Xing4.0-29B-A4B')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_the_generators_kinds_keep_the_maps_far_from_their_start():
    """`Phi` normal, the three gates ONES, the three biases zeros: the
    maps' arguments then have a deviation of 0.02 sqrt(4 x 3584) = 2.4
    (the configuration's `assumed.initializer`)."""
    from benchmarks.reference import xing4 as R
    shapes = R.param_shapes(dict(CFG, num_hidden_layers=2))
    hc = {k.split('.', 2)[2]: kind for k, (_, kind) in shapes.items()
          if k.startswith('l1.hc_mlp.')}
    assert hc == {'phi': 'normal', 'a_pre': 'ones', 'a_post': 'ones',
                  'a_res': 'ones', 'b_pre': 'zeros', 'b_post': 'zeros',
                  'b_res': 'zeros'}
    assert shapes['l1.hc_attn.phi'][0] == (14_336, 24)
    assert round(0.02 * 14_336 ** 0.5, 1) == 2.4
    assert {kind for _, kind in shapes.values()} \
        == {'normal', 'ones', 'zeros'}
    assert sum(int(__import__('math').prod(s)) for s, _ in
               R.param_shapes(CFG).values()) == CFG['params']


def test_bytes_of_a_decode_substep_by_hand():
    # always read, in parameters: five layers beside their experts (the
    # dense one whole), the final norm, the quarter head
    always = (128_196_918 + 4 * 40_345_974 + 3_584 + 32_768 * 3_584)
    assert CX.always_read_params(CFG) == always == 407_024_910
    assert round(2 * always / 1e9, 2) == 0.81
    # of which the hyper-connections' Phi, gates and biases
    assert 10 * CX.hyper_connection_params(CFG) == 3_440_910
    # 512 + 64 float32 numbers a row a layer, whatever the heads
    assert CX.latent_row_bytes(CFG) == 576 * 4 == 2_304
    # the four streams of 8 slots, read and written once a sublayer, ten
    # sublayers: 56 KiB a token a pass
    assert 4 * 3_584 * 4 == 57_344 == 56 * 1024
    assert CX.stream_bytes(CFG, 8) == 10 * 2 * 8 * 57_344 == 9_175_040
    assert CX.stream_bytes(CFG, 8, streams=1) == 9_175_040 / 4
    # a made-up round: 8 slots at 8,300 rows on five layers, 25.8 of the
    # 64 experts touched a layer (8 x 4 picks: 1 - (63/64)^32 = 40%)
    rows = 8 * 5 * 8_300
    need = CX.decode_substep_bytes(CFG, 25.8, rows, active=8)
    assert need == pytest.approx(
        2 * (always + 4 * 25.8 * 11_010_048) + rows * 2_304 + 9_175_040)
    assert round(need / 1e9, 2) == 3.86
    assert round(64 * (1 - (63 / 64) ** 32), 1) == 25.3
    # the experts' part of it: 0.57 GB a layer, not 1.41
    assert round(2 * 25.8 * 11_010_048 / 1e9, 2) == 0.57
    assert round(2 * 64 * 11_010_048 / 1e9, 2) == 1.41
    # nothing touched, nothing cached, nobody decoding: the other weights
    assert CX.decode_substep_bytes(CFG, 0, 0) == 2 * always
    # the program's own row bytes and streams are taken where given
    assert CX.decode_substep_bytes(CFG, 0, 10, 1_152, 1, 2) \
        == 2 * always + 11_520 + 9_175_040 / 8 / 2


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


ROWS = 8 * 5 * 8_300


def _round(touched=26 * 16, rows=ROWS, active=8):
    return {'active': active, 'slots': 8, 'real_rows': 8 * 8_300,
            'needed_rows': rows, 'read_rows': 8 * 5 * 8_704,
            'rows': 12_288, 'experts_touched': touched,
            'expert_layer_substeps': 16, 'expert_kernel_substeps': 16,
            'experts': 64, 'latent_layers': 5, 'latent_row_bytes': 11_520,
            'residual_streams': 4}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('mhc_decode_roofline')
    need = CX.decode_substep_bytes(CFG, 26.0, ROWS, active=8)
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
    # means over rounds: touched per layer and sub-step, rows and active
    # slots per round
    mixed = _context(4 * least, [_round(20 * 16, 0, 6),
                                 _round(32 * 16, 2 * ROWS, 10)])
    assert read(mixed, match='decode') == pytest.approx(25.0)
    # a row's bytes and the number of streams are the span's
    half = CX.decode_substep_bytes(CFG, 26.0, ROWS, 1_152, 8)
    assert need - half == ROWS * 1_152
    assert read(_context(least, [dict(_round(), latent_row_bytes=5_760)]),
                match='decode') == pytest.approx(100.0 * half / need)
    two = CX.decode_substep_bytes(CFG, 26.0, ROWS, active=8, streams=2)
    assert need - two == 9_175_040 / 2
    assert read(_context(least, [dict(_round(), residual_streams=2)]),
                match='decode') == pytest.approx(100.0 * two / need)


def test_reader_reports_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('mhc_decode_roofline')
    # a parent's span, or another model's (kanana's has no streams)
    for missing in ('residual_streams', 'latent_row_bytes', 'latent_layers',
                    'needed_rows', 'experts_touched'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    assert read(_context(0.01, []), match='decode') is None
    # and the count of PR 37's reader knows no streams and no compressed
    # query: this cell is not on its list
    older = {m['name']: m for m in SPEC.bench['per_layer']}
    assert CELL not in older['mla_decode_roofline']['workloads']


def test_the_span_metrics_of_the_cell_on_made_up_rounds():
    share = SPEC.read_metric('moe_experts_touched_share',
                             _context(0.01, [_round(), _round(20 * 16)]))
    assert share == pytest.approx(100.0 * (26 + 20) / 2 / 64)
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(100.0 * 8_300 / 8_704)
    meta = SPEC.data('metrics', 'mhc_decode_share')
    assert meta == {'unit': '%', 'reader': 'decode_scope_share',
                    'args': {'scope': 'mhc'}}
    # no trace, nothing read, no error
    assert SPEC.read_metric('mhc_decode_share',
                            _context(0.01, [_round()], trace=False)) is None


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_mhc'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    cfg = dict(CFG, name='toy-xing', source='none: toy', vocab_size=512,
               hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_hidden_layers=2, n_routed_experts=8,
               num_experts_per_tok=2, max_position_embeddings=64,
               rope_theta=100.0,
               rope_scaling=dict(CFG['rope_scaling'], factor=8, beta_fast=4,
                                 original_max_position_embeddings=16),
               param_dtype='float32', params=0, reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-xing.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30})
    _write(os.path.join(bdir, 'traffic', 'toy-mhc.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-mhc.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-xing', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-xing.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-mhc', 'config': 'toy-xing', 'traffic': 'toy-mhc',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-mhc')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('moe_experts_touched_share', 'attn_needed_rows_share',
                 'attn_decode_share', 'experts_decode_share',
                 *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-mhc']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    """Traced, on the CPU: the reference agrees with what was served
    (YaRN over 16 positions, contexts to 58), the span metrics are read,
    and what needs a device plane reports nothing and raises nothing."""
    out, lines = _toy.run_toy(toy_root, 'toy-mhc', seed=5000000041,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    # 2 slots x 2 picks over 8 experts
    assert 0.0 < m['moe_experts_touched_share']['value'] <= 50.0
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    assert not (NEW_PER_LAYER | {'attn_decode_share',
                                 'experts_decode_share', 'decode_roofline',
                                 'mla_decode_roofline'}) & set(m)


def test_toy_cell_reports_the_two_end_to_end_metrics(toy_root):
    """What an untraced run prints is the cell's `end_to_end` group (a
    third toy process would say no more, and the suite's time is
    short)."""
    toy = spec.Spec(toy_root)
    assert {m['name'] for m in toy.metrics_of('toy-mhc', 'end_to_end')} \
        == {'tpot_p50_ms', 'setup_s'}
    assert NEW_PER_LAYER <= {m['name'] for m in
                             toy.metrics_of('toy-mhc', 'per_layer')}


_NO_SINKHORN = '''
import paddle_tpu.nlp.xing4 as _x
_x.sinkhorn = lambda m, iters, eps: m
'''


def test_toy_rehearsal_without_sinkhorn_is_not_correct(toy_root):
    """One of the three faulty programs the chip's limit has to refuse,
    at the toy size: the benchmark's kinds of leaves (gates ones) keep
    the maps far from constant, so the check sees it."""
    out, lines = _toy.run_toy(toy_root, 'toy-mhc', seed=43, seconds=2.0,
                              patch=_NO_SINKHORN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


# ---------------------------------------------------------------------------
# the real entries: subset pins
# ---------------------------------------------------------------------------
def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == (CONFIG, 'agent-mhc', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | {'moe_experts_touched_share',
                            'attn_needed_rows_share', 'attn_decode_share',
                            'experts_decode_share', 'decode_substep_ms',
                            'serve_device_idle_share'} <= layer
    # their counts are other blocks': no streams, no compressed query
    assert not {'decode_roofline', 'moe_decode_roofline',
                'hybrid_decode_roofline', 'swa_decode_roofline',
                'mla_decode_roofline'} & layer
    entries = {m['name']: m for m in SPEC.bench['per_layer']}
    layers = {'mhc_decode_share': 'residual path: nlp/xing4.py',
              'mhc_decode_roofline': 'latent attention, residual path and '
                                     'expert layer: nlp/xing4.py'}
    better = {'mhc_decode_share': 'lower', 'mhc_decode_roofline': 'higher'}
    for name in NEW_PER_LAYER:
        m = entries[name]
        assert CELL in m['workloads'] and m['moves'] == 'tpot_p50_ms'
        assert (m['unit'], m['source'], m['better'], m['layer']) \
            == ('%', 'device_trace', better[name], layers[name])
    for m in SPEC.bench['end_to_end'] + SPEC.bench['per_layer']:
        if m['name'] in APPENDED_TO:
            assert CELL in m['workloads']
    assert CONFIG in {c['name'] for c in SPEC.bench['configs']}
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['kind'], tr['slots'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == ('serve_backlog', 8, 12_288, 4, 2)
    assert tr['buckets'] == [6144, 8192, 10_240]
    assert (tr['prompt']['min'], tr['prompt']['max']) == (4096, 10_240)
    assert (tr['output']['min'], tr['output']['max']) == (768, 1536)
    assert (tr['warm_output']['min'], tr['warm_output']['max']) == (8, 96)
    # every prompt past the positions YaRN stretches; a context ends at
    # 4,864-11,776, inside the slot
    assert tr['prompt']['min'] >= \
        CFG['rope_scaling']['original_max_position_embeddings']
    assert tr['prompt']['max'] + tr['output']['max'] == 11_776 \
        < tr['max_length'] == CFG['max_position_embeddings']
    assert max(tr['buckets']) >= tr['prompt']['max']
    assert tr['check_requests'] == 2 and tr['trace_s'] == 5.0
    assert 1.0 < tr['finish_per_s_ceiling'] < 4.0
    limits = SPEC.cell(CELL)['limits']
    # between the sound largest and the control's smallest request
    assert limits['control'] == 'fp8' and 1.95 < limits['served_gap'] < 3.12
    assert 'Sinkhorn' in limits['readings']


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER <= _pin.NEW_DEVICE


def _rest(bench):
    return {k: v for k, v in bench.items()
            if k not in ('configs', 'workloads', 'end_to_end', 'per_layer')}


def _differs_by(now, then, configs, cells, metrics, appended_to):
    """`now` is `then` plus exactly: these configurations, these
    workloads, these per-layer metrics, and each cell's name in the
    `workloads` of `appended_to`; the order of what both hold the same."""
    assert [c for c in now['configs'] if c['name'] not in configs] \
        == then['configs']
    assert {c['name'] for c in now['configs']} \
        == {c['name'] for c in then['configs']} | configs
    assert [w for w in now['workloads'] if w['name'] not in cells] \
        == then['workloads']
    assert [m['name'] for m in now['per_layer']
            if m['name'] not in metrics] \
        == [m['name'] for m in then['per_layer']]
    older = {m['name']: m for g in ('end_to_end', 'per_layer')
             for m in then[g]}
    changed = set()
    for group in ('end_to_end', 'per_layer'):
        for m in now[group]:
            if m['name'] in metrics:
                continue
            if m != older[m['name']]:
                was = older[m['name']]
                assert m == dict(was, workloads=m['workloads'])
                assert [w for w in m['workloads'] if w not in cells] \
                    == was['workloads']
                changed.add(m['name'])
    assert changed == appended_to
    assert _rest(now) == _rest(then)


def test_each_view_given_to_an_older_pin_lacks_exactly_these_entries():
    """`test_kanana_cell` reads the real file less what THIS PR appended;
    `test_host_causes` less that and what PR 37 appended."""
    real = spec.Spec().bench
    _differs_by(real, _kanana.SPEC.bench, {CONFIG}, {CELL}, NEW_PER_LAYER,
                APPENDED_TO)
    _differs_by(_kanana.SPEC.bench, _host.SPEC.bench,
                {'kanana-2-30b-a3b'}, {_kanana.CELL}, _kanana.NEW_PER_LAYER,
                _kanana.APPENDED_TO)
    # and with them the three older pins hold
    assert _kanana.SPEC.bench['per_layer'][-1]['name'] \
        == 'mla_decode_roofline'
    assert _kanana.SPEC.bench['workloads'][-1]['name'] == _kanana.CELL
    assert [m['name'] for m in _host.SPEC.bench['per_layer']][-1] \
        == 'conv_decode_share'
