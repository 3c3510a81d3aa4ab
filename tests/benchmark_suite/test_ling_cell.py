"""The cell `serve-kda-reason`: its counts against numbers worked out by
hand, its roofline reader on made-up spans and a made-up trace, a toy
rehearsal of the cell on the CPU, added to a toy root by new files and
entries alone, and the cell's real entries.

**The pins (PERF.md 7e): the hand-over now has FIVE places, and a sixth
this cell found.** Three older modules of this directory pin POSITIONS
in `BENCHMARK.json` (`test_program_spans.py`, `test_host_causes.py`,
`test_kanana_cell.py`), a fourth, `test_xing_cell.py`, holds the views
it hands them to differ from the REAL file by PR 39's entries alone, and
`test_mimo_cell.py` pins the `workloads` of `moe_picks_held_share` to its
own cell by EQUALITY — this cell is the second that holds a share of its
experts and is appended there; all are the benchmark's and no PR may
edit them. So this module, at import and AFTER importing
`test_xing_cell` (whose import hands the older three their views),
extends `test_program_spans.NEW_DEVICE` by this cell's two names and
gives `test_xing_cell` the benchmark without this PR's entries — its
`SPEC.bench`, and the fresh `spec.Spec()` its view test reads, through a
shim under the name `spec` in that module — then rebuilds
`test_kanana_cell.SPEC.bench` and `test_host_causes.SPEC.bench` from that
view with the older modules' OWN `_before_this_cell`; one test below
holds each view to differ from the real file by exactly this PR's
entries; `test_mimo_cell.SPEC` gets the same view as `test_xing_cell`.
Every worker collects every module before a test runs, so the views are
in place whichever file a worker is given; run alone, each of the older
modules fails its pin, as before.

**This module's own pins are SUBSET pins** — names present, `workloads`
containing the cell, nothing about LAST — so the next cell hands over
one view more and changes nothing here."""
import copy
import json
import math
import os
import types

import pytest

import _toy
import test_xing_cell as _xing
import test_host_causes as _host
import test_kanana_cell as _kanana
import test_mimo_cell as _mimo
import test_program_spans as _pin
from benchmarks import counts_ling3 as CL
from benchmarks import spec

NEW_PER_LAYER = {'kda_decode_share', 'kda_decode_roofline'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-kda-reason'
CONFIG = 'ling-3.0-flash'
APPENDED_TO = {'tpot_p50_ms', 'attn_needed_rows_share',
               'moe_experts_touched_share', 'moe_picks_held_share',
               'attn_decode_share', 'experts_decode_share'}


def _before_this_cell(bench):
    """`BENCHMARK.json` without what this cell added: its configuration,
    its workload, its two metrics, and its name in six lists."""
    old = copy.deepcopy(bench)
    old['configs'] = [c for c in old['configs'] if c['name'] != CONFIG]
    old['workloads'] = [w for w in old['workloads'] if w['name'] != CELL]
    old['per_layer'] = [m for m in old['per_layer']
                        if m['name'] not in NEW_PER_LAYER]
    for m in old['end_to_end'] + old['per_layer']:
        if m['name'] in APPENDED_TO:
            m['workloads'] = [w for w in m['workloads'] if w != CELL]
    return old


def _spec_without_this_cell(root=None):
    """`spec.Spec` as `test_xing_cell` may see it: the real file read
    less this PR's entries; a toy root as it is."""
    made = spec.Spec(root)
    if root is None:
        made.bench = _before_this_cell(made.bench)
    return made


_xing.SPEC.bench = _before_this_cell(SPEC.bench)
_mimo.SPEC.bench = _before_this_cell(SPEC.bench)
_xing.spec = types.SimpleNamespace(
    Spec=_spec_without_this_cell, ReadContext=spec.ReadContext)
_kanana.SPEC.bench = _xing._before_this_cell(_xing.SPEC.bench)
_host.SPEC.bench = _kanana._before_this_cell(_kanana.SPEC.bench)
CFG = SPEC.cell(CELL)['config']
GIB, MIB = 2.0 ** 30, 2.0 ** 20
FIVE_CUTS = ['num_hidden_layers', 'first_k_dense_replace', 'num_experts',
             'vocab_size', 'max_position_embeddings']


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_of_the_cut_as_the_file_states():
    # six 2560 x 4096 products (q, k, v, both gates, out), three filters
    # of 4096 x 4 taps, W_b 2560 x 32, A_log, dt_bias, the norm over 128
    assert CL.kda_params(CFG) \
        == 6 * 10_485_760 + 49_152 + 81_920 + 32 + 4_096 + 128 \
        == 63_049_888
    # q 2560 x (32 x 192); kv_a 2560 x (512 + 64); the latent norm; kv_b
    # 512 x (32 x 256); the gate 2560 x 32; o (32 x 128) x 2560
    assert CL.latent_attention_params(CFG) \
        == (15_728_640 + 1_474_560 + 512 + 4_194_304 + 81_920
            + 10_485_760) == 31_965_696
    assert CL.norm_params(CFG) == 5_120
    assert CL.expert_params(CFG) == 5_898_240
    assert CL.router_params(CFG) + CL.shared_params(CFG) \
        == 2_560 * 512 + 512 + 5_898_240 == 7_209_472
    assert CL.dense_mlp_params(CFG) == 47_185_920
    assert CL.layer_params(CFG, 'kda', False) == 110_240_928    # layer 0
    assert CL.layer_params(CFG, 'kda', True) == 447_751_840
    assert CL.layer_params(CFG, 'mla', True) == 416_667_648
    assert CL.layer_params(CFG, 'kda', True, 0) == 70_264_480   # beside them
    assert CL.expert_layers(CFG) == 6
    assert CL.total_params(CFG) == CFG['params'] == 2_966_865_856 \
        == (110_240_928 + 5 * 447_751_840 + 416_667_648 + 201_195_520
            + 2_560)
    assert round(2 * CFG['params'] / GIB, 2) == 5.53
    # twice in set-up (PERF.md 7a) beside the pool: the 64-wide latent
    # leaf padded to 128 lanes on the device, 2,560 B a row; 64 slots as
    # ISSUE 43 reckoned, 48 as the cell runs
    pool = 64 * (CL.state_bytes_per_slot(CFG) + 6_144 * 2_560)
    assert round(pool / GIB, 2) == 1.74
    assert round((2 * 2 * CFG['params'] + pool) / GIB, 1) == 12.8
    assert round(0.75 * pool / GIB, 2) == 1.31


def test_parameters_uncut_and_active_and_why_sixty_four_experts():
    pub = CFG['published']
    assert CL.published_layer_types(CFG, 42).count('mla') == 7
    assert [i for i, t in enumerate(CL.published_layer_types(CFG, 42))
            if t == 'mla'] == [5, 11, 17, 23, 29, 35, 41]
    # the built layers' kinds are the rule's on the published indices
    kinds = CL.published_layer_types(CFG, 42)
    assert [kinds[i] for i in CFG['kept_layers']] == CFG['layer_types'] \
        == ['kda'] * 6 + ['mla']
    assert CL.published_params(CFG) == pub['params'] == 124_414_211_552
    # top-8 of the 512 and the shared expert: "125B-A5.5B"
    assert CL.published_params(CFG, 8) == pub['active_params'] \
        == 5_505_693_152
    # 128 experts held (one of 4 chips): the dense layer and one period
    # are 9.74 GiB, and the harness holds the weights twice
    wider = CL.total_params(dict(CFG, num_experts=128))
    assert wider == 5_231_790_016
    assert round(2 * wider / GIB, 2) == 9.74


def test_the_file_holds_the_published_widths_and_the_five_cuts():
    bench = {c['name']: c for c in SPEC.bench['configs']}[CONFIG]
    assert CFG['reduced'] == bench['reduced'] == FIVE_CUTS
    assert set(CFG['reduced']) <= set(CFG['changed'])
    assert set(CFG['changed']) - set(CFG['reduced']) \
        == {'layer_types', 'kept_layers'}       # ADDED keys, explained
    widths = dict(hidden_size=2560, num_attention_heads=32,
                  num_key_value_heads=32, head_dim=128, kv_lora_rank=512,
                  q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
                  rotary_dim=64, partial_rotary_factor=0.5,
                  rope_theta=6_000_000, rope_interleave=True,
                  rope_scaling=None, intermediate_size=6144,
                  moe_intermediate_size=768,
                  moe_shared_expert_intermediate_size=768,
                  num_experts_per_tok=8, num_shared_experts=1, n_group=8,
                  topk_group=4, routed_scaling_factor=2.5,
                  norm_topk_prob=True, score_function='sigmoid',
                  topk_method='noaux_tc', short_conv_kernel_size=4,
                  kda_safe_gate=True, kda_lower_bound=-5, no_kda_lora=True,
                  use_kda_lora=False, layer_group_size=6,
                  gated_attention_proj_granularity_type='head_wise',
                  rms_norm_eps=1e-6, tie_word_embeddings=False,
                  num_nextn_predict_layers=1, model_type='bailing_hybrid')
    assert {k: CFG[k] for k in widths} == widths
    assert CFG['expert_share'] == {'routed': 512, 'first': 0}
    assert [CFG[k] for k in FIVE_CUTS] == [7, 1, 64, 39_296, 6_144]
    assert [CFG['published'][k] for k in FIVE_CUTS] \
        == [42, 2, 512, 157_184, 262_144]
    assert 39_296 == 307 * 128 == 157_184 // 4
    # routing group 0 whole: 512 / 8 consecutive experts from the first
    assert CFG['num_experts'] == 512 // CFG['n_group']
    # the limits stay whole, one entry a PUBLISHED layer; the layers built
    # have none
    for name in ('expert_swiglu_limit_list',
                 'share_expert_swiglu_limit_list'):
        assert len(CFG[name]) == 42 and any(CFG[name])
        assert not any(CFG[name][i] for i in CFG['kept_layers'])
    for key in ('deployment', 'assumed', 'changed', 'published',
                'left_out'):
        assert CFG[key]
    assert '8 chips share each layer' in CFG['deployment']
    assert 'num_nextn_predict_layers' in CFG['left_out']
    for key in ('layer_rule', 'kda_conv', 'kda_safe_gate', 'kda_no_lora',
                'kda_output', 'mla_gate', 'router', 'swiglu_limits',
                'initializer'):
        assert CFG['assumed'][key]
    assert 'CANNOT see the long-memory regime' in \
        CFG['assumed']['initializer']
    assert (CFG['model_class'], CFG['param_dtype'], CFG['kv_dtype']) \
        == ('Ling3ForCausalLM', 'bfloat16', 'float32')
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'Ling-3.0-flash')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_the_generators_kinds_give_decays_that_differ():
    """Taps ONE, `A_log` ONE, `dt_bias` ZERO: the decay gate's argument
    is e x N(0, 1.01) (the configuration's `assumed.initializer`)."""
    from benchmarks.reference import ling3 as R
    shapes = R.param_shapes(CFG)
    kda = {k.split('.', 1)[1]: kind for k, (_, kind) in shapes.items()
           if k.startswith('l1.')}
    assert {k: kda[k] for k in ('q_conv', 'k_conv', 'v_conv', 'a_log',
                                'dt_bias', 'o_norm', 'f_w', 'b_w')} == {
        'q_conv': 'ones', 'k_conv': 'ones', 'v_conv': 'ones',
        'a_log': 'ones', 'dt_bias': 'zeros', 'o_norm': 'ones',
        'f_w': 'normal', 'b_w': 'normal'}
    assert shapes['l1.q_conv'][0] == (4_096, 4)
    assert shapes['l6.gate_w'][0] == (2_560, 32)
    assert shapes['l1.router_w'][0] == (2_560, 512)
    assert shapes['l1.experts_gate'][0] == (64, 2_560, 768)
    assert round(0.02 * 2_560 ** 0.5, 2) == 1.01
    # the median channel: g = -2.5, alpha 0.08; a fifth slower than 0.63
    assert round(math.exp(-5 * 0.5), 2) == 0.08
    assert {kind for _, kind in shapes.values()} \
        == {'normal', 'ones', 'zeros'}
    assert sum(math.prod(s) for s, _ in shapes.values()) == CFG['params']


def test_bytes_of_a_decode_substep_by_hand():
    # always read, in parameters: the dense layer whole, six expert
    # layers beside their experts, the final norm, the quarter head
    always = (110_240_928 + 5 * 70_264_480 + 39_180_288 + 2_560
              + 39_296 * 2_560)
    assert CL.always_read_params(CFG) == always == 601_343_936
    assert round(2 * always / 1e9, 2) == 1.20
    # 512 + 64 float32 numbers a row of the ONE latent layer
    assert CL.latent_row_bytes(CFG) == 576 * 4 == 2_304
    # a KDA layer's entry: 32 x 128 x 128 float32 and the last 3 inputs
    # of 12,288 channels
    assert CL.state_bytes_per_layer(CFG) == 2_097_152 + 147_456 \
        == 2 * MIB + 144 * 1024
    assert CL.state_bytes_per_slot(CFG) == 6 * 2_244_608 == 12.84375 * MIB
    assert CL.slot_bytes(CFG, 6_144) == 13_467_648 + 6_144 * 2_304
    assert round(64 * CL.slot_bytes(CFG, 6_144) / GIB, 2) == 1.65
    # a made-up round: 64 slots at 3,300 rows on the one latent layer, 40
    # of the 64 held experts touched a layer, every state read and
    # written once
    rows, state = 64 * 3_300, 64 * 13_467_648 * 2
    need = CL.decode_substep_bytes(CFG, 40.0, rows, state)
    assert need == pytest.approx(
        2 * (always + 6 * 40.0 * 5_898_240) + rows * 2_304 + state)
    assert round(state / 1e9, 2) == 1.72
    assert round(2 * 6 * 40.0 * 5_898_240 / 1e9, 2) == 2.83
    assert round(rows * 2_304 / 1e9, 2) == 0.49
    assert round(need / 1e9, 2) == 6.24
    # the state and the group-routed share: about 70% of it
    assert round((state + 2 * 6 * 40.0 * 5_898_240) / need, 2) == 0.73
    # were the slots' picks independent: 64 slots x 8 picks, an eighth
    # held, over 64 experts, one pick an expert: 63% touched (ISSUE 43's
    # reckoning; the chip reads 40%: the generator's weights make the
    # tokens' router inputs alike, PERF.md section 6)
    assert round(64 * (1 - (1 - 1 / 64) ** 64)) == 41
    # nothing touched, nothing cached, nobody decoding: the other weights
    assert CL.decode_substep_bytes(CFG, 0, 0, 0) == 2 * always
    # the program's own row bytes are taken where given
    assert CL.decode_substep_bytes(CFG, 0, 10, 0, 1_152) \
        == 2 * always + 11_520


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
        'jit__state_prefill_fn(4)': (0.5, 2)}, 'events0': []}
    return spec.ReadContext(
        SPEC.cell(CELL), raw, summary if trace else None,
        SPEC.peaks('TPU v5 lite') if peaks else None, None)


ROWS = 64 * 3_300
STATE = 64 * 13_467_648 * 2 * 4         # a round's: four sub-steps


def _round(touched=40 * 24, rows=ROWS, state=STATE, held=1_536):
    return {'active': 64, 'slots': 64, 'real_rows': ROWS,
            'needed_rows': rows, 'read_rows': 64 * 3_584, 'rows': 6_144,
            'experts_touched': touched, 'expert_layer_substeps': 24,
            'expert_kernel_substeps': 24, 'experts': 64,
            'picks': 64 * 8 * 24, 'picks_held': held, 'attn_layers': 1,
            'state_layers': 6, 'state_bytes': state, 'latent_layers': 1,
            'latent_row_bytes': 2_304}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('kda_decode_roofline')
    need = CL.decode_substep_bytes(CFG, 40.0, ROWS, STATE / 4)
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
    # means over rounds: touched per layer and sub-step; rows and the
    # state's bytes per round, the state's over the block's sub-steps
    mixed = _context(4 * least, [_round(30 * 24, 0, STATE / 2),
                                 _round(50 * 24, 2 * ROWS, 1.5 * STATE)])
    assert read(mixed, match='decode') == pytest.approx(25.0)
    # a row's bytes are the span's
    half = CL.decode_substep_bytes(CFG, 40.0, ROWS, STATE / 4, 1_152)
    assert need - half == ROWS * 1_152
    assert read(_context(least, [dict(_round(), latent_row_bytes=1_152)]),
                match='decode') == pytest.approx(100.0 * half / need)


def test_reader_reports_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('kda_decode_roofline')
    # a parent's span, or another model's (kanana's has no state, lfm2's
    # no latent entry)
    for missing in ('state_bytes', 'latent_row_bytes', 'latent_layers',
                    'needed_rows', 'experts_touched'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    assert read(_context(0.01, []), match='decode') is None
    # and the older rooflines' counts know no matrix state beside latent
    # rows: this cell is on none of their lists
    older = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('mla_decode_roofline', 'hybrid_decode_roofline',
                 'swa_decode_roofline', 'mhc_decode_roofline'):
        assert CELL not in older[name]['workloads']


def test_the_span_metrics_of_the_cell_on_made_up_rounds():
    ctx = _context(0.01, [_round(), _round(30 * 24, held=1_024)])
    share = SPEC.read_metric('moe_experts_touched_share', ctx)
    assert share == pytest.approx(100.0 * (40 + 30) / 2 / 64)
    held = SPEC.read_metric('moe_picks_held_share', ctx)
    assert held == pytest.approx(100.0 * (1_536 + 1_024) / (2 * 12_288))
    assert 100.0 * 1_536 / 12_288 == 12.5       # even routing, 1 of 8
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(100.0 * 3_300 / 3_584)
    meta = SPEC.data('metrics', 'kda_decode_share')
    assert meta == {'unit': '%', 'reader': 'decode_scope_share',
                    'args': {'scope': 'kda'}}
    assert SPEC.data('metrics', 'kda_decode_roofline') == {
        'unit': '%', 'reader': 'kda_decode_roofline',
        'args': {'match': 'decode'}}
    # no trace, nothing read, no error
    assert SPEC.read_metric('kda_decode_share',
                            _context(0.01, [_round()], trace=False)) is None


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_kda'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    cfg = dict(CFG, name='toy-ling', source='none: toy', vocab_size=512,
               hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, num_hidden_layers=3,
               layer_types=['kda', 'kda', 'mla'], kept_layers=[0, 1, 2],
               num_experts=8, expert_share={'routed': 16, 'first': 0},
               n_group=4, topk_group=2, num_experts_per_tok=2,
               max_position_embeddings=64, rope_theta=100.0,
               param_dtype='float32', params=0, reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-ling.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30})
    _write(os.path.join(bdir, 'traffic', 'toy-kda.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-kda.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-ling', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-ling.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-kda', 'config': 'toy-ling', 'traffic': 'toy-kda',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-kda')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('moe_experts_touched_share', 'attn_needed_rows_share',
                 'moe_picks_held_share', 'attn_decode_share',
                 'experts_decode_share', *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-kda']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    """Traced, on the CPU: the reference (the recurrence token by token)
    agrees with what was served (chunks, a state handed off at the
    prompt's length, contexts to 58), the span metrics are read, and
    what needs a device plane reports nothing and raises nothing."""
    out, lines = _toy.run_toy(toy_root, 'toy-kda', seed=5000000043,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    # 2 slots x 2 picks over a router of 16, 8 of them held
    assert 0.0 < m['moe_experts_touched_share']['value'] <= 50.0
    assert 0.0 < m['moe_picks_held_share']['value'] < 100.0
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    assert not (NEW_PER_LAYER | {'attn_decode_share',
                                 'experts_decode_share', 'decode_roofline',
                                 'mla_decode_roofline',
                                 'hybrid_decode_roofline'}) & set(m)


def test_toy_cell_reports_the_two_end_to_end_metrics(toy_root):
    toy = spec.Spec(toy_root)
    assert {m['name'] for m in toy.metrics_of('toy-kda', 'end_to_end')} \
        == {'tpot_p50_ms', 'setup_s'}
    assert NEW_PER_LAYER <= {m['name'] for m in
                             toy.metrics_of('toy-kda', 'per_layer')}


_PADS_FOLDED_IN = '''
import paddle_tpu.nlp.ling3 as _l
_l.folded_tokens = lambda s: s
'''


def test_toy_rehearsal_with_the_pads_folded_in_is_not_correct(toy_root):
    """One of the three faulty programs the chip's limit has to refuse,
    at the toy size: the state seated as the padded bucket leaves it."""
    out, lines = _toy.run_toy(toy_root, 'toy-kda', seed=43, seconds=2.0,
                              patch=_PADS_FOLDED_IN)
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


# ---------------------------------------------------------------------------
# the real entries: subset pins
# ---------------------------------------------------------------------------
def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == (CONFIG, 'reason-kda', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | APPENDED_TO - {'tpot_p50_ms'} \
        | {'decode_substep_ms', 'serve_device_idle_share'} <= layer
    # their counts are other blocks': no matrix state beside latent rows
    assert not {'decode_roofline', 'moe_decode_roofline',
                'hybrid_decode_roofline', 'swa_decode_roofline',
                'mla_decode_roofline', 'mhc_decode_roofline'} & layer
    entries = {m['name']: m for m in SPEC.bench['per_layer']}
    better = {'kda_decode_share': 'lower', 'kda_decode_roofline': 'higher'}
    for name in NEW_PER_LAYER:
        m = entries[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
        assert (m['unit'], m['source'], m['better'], m['layer']) \
            == ('%', 'device_trace', better[name],
                'linear-attention layers: nlp/ling3.py')
    for m in SPEC.bench['end_to_end'] + SPEC.bench['per_layer']:
        if m['name'] in APPENDED_TO:
            assert CELL in m['workloads']
    assert CONFIG in {c['name'] for c in SPEC.bench['configs']}
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['kind'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == ('serve_backlog', 6_144, 4, 4)
    # ISSUE 43's fallback: at 64 slots `tpot_p50_ms` spread by 3.9% in
    # a set of six (one seed alone 15.92-16.87), at 48 by 1.3%
    assert tr['slots'] == 48
    assert tr['buckets'] == [2048, 3072, 4096]
    assert (tr['prompt']['min'], tr['prompt']['max']) == (1024, 4096)
    assert (tr['output']['min'], tr['output']['max']) == (768, 2048)
    assert (tr['warm_output']['min'], tr['warm_output']['max']) == (8, 96)
    # a context ends at 1,792-6,144, inside the slot
    assert tr['prompt']['max'] + tr['output']['max'] == 6_144 \
        == tr['max_length'] == CFG['max_position_embeddings']
    assert max(tr['buckets']) >= tr['prompt']['max']
    assert tr['check_requests'] == 2 and tr['trace_s'] == 5.0
    limits = SPEC.cell(CELL)['limits']
    assert limits['control'] == 'fp8' and limits['served_gap'] > 0
    for word in ('pads folded', 'erase term', 'group limit', 'fp8'):
        assert word in limits['readings']


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER | _xing.NEW_PER_LAYER <= _pin.NEW_DEVICE


def test_each_view_given_to_an_older_pin_lacks_exactly_these_entries():
    """`test_xing_cell` reads the real file less what THIS PR appended —
    in its `SPEC` and in every `spec.Spec()` it makes —, and the three
    views behind it are rebuilt from that with the older modules' own
    `_before_this_cell`, so each still differs from the next by its own
    PR's entries alone."""
    real = spec.Spec().bench
    _xing._differs_by(real, _xing.SPEC.bench, {CONFIG}, {CELL},
                      NEW_PER_LAYER, APPENDED_TO)
    assert _xing.spec.Spec().bench == _xing.SPEC.bench == _mimo.SPEC.bench
    held = {m['name']: m for m in _mimo.SPEC.bench['per_layer']}
    assert held['moe_picks_held_share']['workloads'] == [_mimo.CELL]
    assert _xing.spec.Spec is not spec.Spec
    _xing._differs_by(_xing.SPEC.bench, _kanana.SPEC.bench, {_xing.CONFIG},
                      {_xing.CELL}, _xing.NEW_PER_LAYER, _xing.APPENDED_TO)
    _xing._differs_by(_kanana.SPEC.bench, _host.SPEC.bench,
                      {'kanana-2-30b-a3b'}, {_kanana.CELL},
                      _kanana.NEW_PER_LAYER, _kanana.APPENDED_TO)
    # and with them the older pins hold
    assert _xing.SPEC.bench['per_layer'][-1]['name'] \
        == 'mhc_decode_roofline'
    assert _kanana.SPEC.bench['per_layer'][-1]['name'] \
        == 'mla_decode_roofline'
    assert _kanana.SPEC.bench['workloads'][-1]['name'] == _kanana.CELL
    assert [m['name'] for m in _host.SPEC.bench['per_layer']][-1] \
        == 'conv_decode_share'
