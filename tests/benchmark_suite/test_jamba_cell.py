"""The cell `serve-ssm-reason`: its counts against the model's own
parameters at a tiny size and against numbers worked out by hand at the
published one, its roofline reader on made-up spans and a made-up trace,
a toy rehearsal of the cell on the CPU, added to a toy root by new files
and entries alone, and the cell's real entries.

**The pins (PERF.md 7e): the hand-over now has SIX places.** Three older
modules of this directory pin POSITIONS in `BENCHMARK.json`
(`test_program_spans.py`, `test_host_causes.py`, `test_kanana_cell.py`),
`test_xing_cell.py` and `test_ling_cell.py` each hold the views they
hand on to differ from the REAL file by their own PR's entries alone,
and `test_mimo_cell.py` pins the `workloads` of `moe_picks_held_share`
by EQUALITY; all are the benchmark's and no PR may edit them. So this
module, at import and AFTER importing `test_ling_cell` (whose import
hands the older five their views), extends
`test_program_spans.NEW_DEVICE` by this cell's one name and gives
`test_ling_cell` the benchmark without this PR's entries — its
`SPEC.bench`, and the fresh `spec.Spec()` its view test reads, through a
shim under the name `spec` in that module (the shim `test_ling_cell`
gave `test_xing_cell` calls it, so that one follows) — then rebuilds the
views behind it with the older modules' OWN `_before_this_cell`; one
test below holds each view to differ from the real file by exactly this
PR's entries. Every worker collects every module before a test runs, so
the views are in place whichever file a worker is given.

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
import jamba_faults
import test_ling_cell as _ling
import test_xing_cell as _xing
import test_host_causes as _host
import test_kanana_cell as _kanana
import test_mimo_cell as _mimo
import test_program_spans as _pin
from benchmarks import counts_jamba as CJ
from benchmarks import spec

# (`ssm_decode_share`, a `decode_scope_share` of scope `ssm`, is NOT an
# entry: the cell places 83% of its decode programs' op time, under the
# reader's 90% — PERF.md 7f — so neither it nor `attn_decode_share`
# would be reported here; the run's log has the shares by scope)
NEW_PER_LAYER = {'ssm_decode_roofline'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
CELL = 'serve-ssm-reason'
CONFIG = 'jamba2-3b'
APPENDED_TO = {'tpot_p50_ms', 'attn_needed_rows_share'}


def _before_this_cell(bench):
    """`BENCHMARK.json` without what this cell added: its configuration,
    its workload, its one metric, and its name in two lists."""
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
    """`spec.Spec` as `test_ling_cell` may see it: the real file read
    less this PR's entries; a toy root as it is."""
    made = spec.Spec(root)
    if root is None:
        made.bench = _before_this_cell(made.bench)
    return made


_ling.SPEC.bench = _before_this_cell(SPEC.bench)
_ling.spec = types.SimpleNamespace(
    Spec=_spec_without_this_cell, ReadContext=spec.ReadContext)
_xing.SPEC.bench = _ling._before_this_cell(_ling.SPEC.bench)
_mimo.SPEC.bench = _ling._before_this_cell(_ling.SPEC.bench)
_kanana.SPEC.bench = _xing._before_this_cell(_xing.SPEC.bench)
_host.SPEC.bench = _kanana._before_this_cell(_kanana.SPEC.bench)
CFG = SPEC.cell(CELL)['config']
GIB, MIB = 2.0 ** 30, 2.0 ** 20


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_parameters_as_the_issue_reckons_them():
    # in 2560 x 10240; 4 taps and a bias x 5120; x 5120 x 192; the three
    # inner norms; dt 160 x 5120 and its bias; A_log 5120 x 16; D; out
    assert CJ.mixer_params(CFG) \
        == (26_214_400 + 20_480 + 5_120 + 983_040 + 819_200 + 5_120
            + 81_920 + 5_120 + 192 + 13_107_200) == 41_241_792
    # q and o 2560 x (20 x 128), k and v 2560 x (1 x 128)
    assert CJ.attention_params(CFG) == 2 * 6_553_600 + 2 * 327_680 \
        == 13_762_560
    assert CJ.mlp_params(CFG) == 62_914_560 and CJ.norm_params(CFG) == 5_120
    assert CJ.layer_params(CFG, 'mamba') == 104_161_472
    assert CJ.layer_params(CFG, CJ.FULL) == 76_682_240
    assert (CJ.mamba_layers(CFG), CJ.attention_layers(CFG)) == (26, 2)
    assert [i for i, t in enumerate(CJ.layer_types(CFG)) if t == CJ.FULL] \
        == [7, 21]
    assert CJ.total_params(CFG) == CFG['params'] == 3_029_337_472 \
        == (26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2_560)
    assert round(2 * CFG['params'] / 1e9, 2) == 6.06
    assert round(2 * CFG['params'] / GIB, 2) == 5.64
    # trained at 16 B a parameter: 48 GB whole, 23 GB one period of 14
    assert round(16 * CFG['params'] / 1e9) == 48
    assert round(16 * (13 * 104_161_472 + 76_682_240) / 1e9) == 23


def test_the_counts_are_the_models_own_parameters_at_a_tiny_size():
    """`counts_jamba` against `named_parameters()` of the program's
    model, and the reference's shapes against both."""
    from benchmarks.models import adapter
    from benchmarks.reference import jamba as R
    tiny = dict(CFG, vocab_size=128, hidden_size=32, intermediate_size=64,
                num_hidden_layers=5, attn_layer_offset=1,
                attn_layer_period=4, num_attention_heads=4,
                num_key_value_heads=1, mamba_d_state=8, mamba_dt_rank=6)
    model = adapter('JambaForCausalLM').build(tiny)
    got = {n: math.prod(p.shape) for n, p in model.named_parameters()}
    assert sum(got.values()) == CJ.total_params(tiny)
    layer = lambda i: sum(v for n, v in got.items()          # noqa: E731
                          if n.startswith(f'model.layers.{i}.'))
    assert layer(0) == CJ.layer_params(tiny, 'mamba')
    assert layer(1) == CJ.layer_params(tiny, CJ.FULL)
    assert sum(v for n, v in got.items() if '.mamba.' in n) \
        == 4 * CJ.mixer_params(tiny)
    shapes = R.param_shapes(tiny)
    assert sum(math.prod(s) for s, _ in shapes.values()) \
        == CJ.total_params(tiny)
    assert R.layer_types(tiny) == CJ.layer_types(tiny)
    cache = model.init_cache(3, 16)
    state = sum(leaf.nbytes for i, entry in enumerate(cache)
                if isinstance(entry, dict) for leaf in entry.values())
    assert state == 3 * CJ.state_bytes_per_slot(tiny)
    assert sum(leaf.nbytes for entry in cache for leaf in (
        entry.values() if isinstance(entry, dict) else entry)) \
        == 3 * CJ.slot_bytes(tiny, 16)


def test_the_file_holds_every_published_key_and_one_cut():
    bench = {c['name']: c for c in SPEC.bench['configs']}[CONFIG]
    assert CFG['reduced'] == bench['reduced'] == ['max_position_embeddings']
    assert set(CFG['changed']) == {'max_position_embeddings'}
    widths = dict(hidden_size=2560, intermediate_size=8192,
                  num_attention_heads=20, num_key_value_heads=1,
                  mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                  mamba_dt_rank=160, mamba_conv_bias=True,
                  mamba_proj_bias=False, num_experts=1,
                  num_experts_per_tok=1, num_hidden_layers=28,
                  vocab_size=65_536, attn_layer_offset=7,
                  attn_layer_period=14, rms_norm_eps=1e-6,
                  tie_word_embeddings=True, sliding_window=None,
                  hidden_act='silu', model_type='jamba')
    assert {k: CFG[k] for k in widths} == widths
    assert CFG['max_position_embeddings'] == 5_120
    assert CFG['published']['max_position_embeddings'] == 262_144
    assert CFG['left_out'] == 'nothing'
    assert 'no layer is divided' in CFG['deployment']
    for key in ('layer_order', 'inner_norms', 'dt', 'conv', 'state',
                'head_dim', 'attention', 'initializer'):
        assert CFG['assumed'][key]
    assert 'CANNOT see the long-memory regime' in \
        CFG['assumed']['initializer']
    assert 'layers 7 and 21' in CFG['assumed']['layer_order']
    assert (CFG['model_class'], CFG['param_dtype'], CFG['kv_dtype']) \
        == ('JambaForCausalLM', 'bfloat16', 'float32')
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):     # every other key as the source has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'AI21-Jamba2-3B')
        assert CFG['source'] == bench['source'] == row['source_url']
        assert {k for k, v in row['config'].items() if CFG[k] != v} \
            == set(CFG['reduced'])


def test_the_generators_kinds_give_a_channel_that_forgets_in_a_few_tokens():
    """Taps ONE and `b_conv` ZERO, `A_log` ONE, `b_dt` ZERO, `D` ONE
    (the configuration's `assumed.initializer`): dt = softplus(N(0,
    0.25)) is about 0.7, and exp(-0.7 e) = 0.15 a token."""
    from benchmarks.reference import jamba as R
    shapes = R.param_shapes(CFG)
    mixer = {k.split('.', 1)[1]: kind for k, (_, kind) in shapes.items()
             if k.startswith('l0.')}
    assert {k: mixer[k] for k in ('conv_w', 'conv_b', 'a_log', 'dt_b', 'd',
                                  'dt_norm', 'b_norm', 'c_norm', 'in_w',
                                  'x_w', 'dt_w', 'out_w')} == {
        'conv_w': 'ones', 'conv_b': 'zeros', 'a_log': 'ones',
        'dt_b': 'zeros', 'd': 'ones', 'dt_norm': 'ones', 'b_norm': 'ones',
        'c_norm': 'ones', 'in_w': 'normal', 'x_w': 'normal',
        'dt_w': 'normal', 'out_w': 'normal'}
    assert shapes['l0.a_log'][0] == (5_120, 16)
    assert shapes['l0.x_w'][0] == (5_120, 192)
    assert shapes['l7.k_w'][0] == (2_560, 128)
    assert 'l7.in_w' not in shapes and 'l0.q_w' not in shapes
    assert not any('q_norm' in k or 'head' in k for k in shapes)
    # r is normed to unit RMS over 160: dt's argument is N(0, 0.02^2 160)
    assert round(0.02 * 160 ** 0.5, 2) == 0.25
    assert round(math.log(2), 2) == 0.69            # softplus(0)
    assert round(math.exp(-0.69 * math.e), 2) == 0.15
    assert {kind for _, kind in shapes.values()} \
        == {'normal', 'ones', 'zeros'}
    assert sum(math.prod(s) for s, _ in shapes.values()) == CFG['params']


def test_bytes_of_a_slot_and_of_a_decode_substep_by_hand():
    # a Mamba layer's entry: 16 x 5120 float32 and the last 3 inputs
    assert CJ.state_bytes_per_layer(CFG) == 327_680 + 61_440 == 389_120
    assert CJ.state_bytes_per_slot(CFG) == CFG['state_bytes_per_slot'] \
        == 26 * 389_120 == 10_117_120
    assert round(10_117_120 / MIB, 2) == 9.65
    # held [5120, 16], the 16 padded to 128 lanes: eight times the state
    assert round(26 * (8 * 327_680 + 61_440) / MIB) == 67
    # K and V of the ONE head of 128, float32, on two layers
    assert CJ.kv_row_bytes_per_layer(CFG) == 1_024
    assert CJ.slot_bytes(CFG, 5_120) == 10_117_120 + 5_120 * 2_048
    state, rows = 128 * 10_117_120, 128 * 5_120 * 2_048
    assert (round(state / GIB, 2), round(rows / GIB, 2)) == (1.21, 1.25)
    assert round(128 * CJ.slot_bytes(CFG, 5_120) / GIB, 2) == 2.46
    serving = 2 * CFG['params'] + 128 * CJ.slot_bytes(CFG, 5_120)
    assert round(serving / GIB, 2) == 8.10          # 51% of the chip
    assert round((serving + 2 * CFG['params']) / GIB, 2) == 13.74
    # the fallback, 96 slots
    assert round((2 * CFG['params'] + 96 * CJ.slot_bytes(CFG, 5_120))
                 / GIB, 2) == 7.48
    # a sub-step at 128 slots and a mean context of 2,500: every weight,
    # the state read once and written once, the rows of two layers
    need = CJ.decode_substep_bytes(CFG, 128 * 2 * 2_500, 2 * state)
    assert need == 2 * CFG['params'] + 128 * 2 * 2_500 * 1_024 + 2 * state
    assert round(2 * state / 1e9, 2) == 2.59
    assert round(128 * 2 * 2_500 * 1_024 / 1e9, 2) == 0.66
    assert round(need / 1e9, 1) == 9.3
    assert round(1e3 * need / 819e9, 1) == 11.4
    # of which the mixers (their weights and the state) are 51%, the 26
    # Mamba layers whole 86%, the two attention layers' rows 7%
    mixers = 2 * 26 * CJ.mixer_params(CFG)
    assert round(mixers / 1e9, 2) == 2.14
    assert round((mixers + 2 * state) / need, 2) == 0.51
    assert round((2 * 26 * CJ.layer_params(CFG, 'mamba') + 2 * state)
                 / need, 2) == 0.86
    assert round(128 * 2 * 2_500 * 1_024 / need, 2) == 0.07
    # the matrix unit beside it: three bf16 passes, 128 rows
    assert round(1e3 * 3 * 2 * CFG['params'] * 128 / 197e12, 1) == 11.8
    # nothing cached, nobody decoding: the weights
    assert CJ.decode_substep_bytes(CFG, 0, 0) == 2 * CFG['params']


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


ROWS = 128 * 2 * 2_500
STATE = 128 * 10_117_120 * 2 * 4        # a round's: four sub-steps


def _round(rows=ROWS, state=STATE):
    return {'active': 128, 'slots': 128, 'real_rows': ROWS // 2,
            'needed_rows': rows, 'read_rows': 128 * 2 * 2_560, 'rows': 5_120,
            'attn_layers': 2, 'state_layers': 26, 'state_bytes': state}


def test_roofline_reader_on_made_up_spans_and_trace():
    read = SPEC.reader('ssm_decode_roofline')
    need = CJ.decode_substep_bytes(CFG, ROWS, STATE / 4)
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
    # means over rounds: rows and the state's bytes per round, the
    # state's over the block's sub-steps
    mixed = _context(4 * least, [_round(0, STATE / 2),
                                 _round(2 * ROWS, 1.5 * STATE)])
    assert read(mixed, match='decode') == pytest.approx(25.0)


def test_reader_reports_nothing_where_there_is_nothing_to_read():
    read = SPEC.reader('ssm_decode_roofline')
    # a parent's span, or a model's that keeps K and V only
    for missing in ('state_bytes', 'state_layers', 'attn_layers',
                    'needed_rows'):
        attrs = {k: v for k, v in _round().items() if k != missing}
        assert read(_context(0.01, [attrs]), match='decode') is None
    assert read(_context(0.01, [_round()], trace=False),
                match='decode') is None
    assert read(_context(0.01, [_round()], peaks=False),
                match='decode') is None
    assert read(_context(0.01, [_round()]), match='no_such_program') is None
    assert read(_context(0.01, []), match='decode') is None
    # and the older rooflines' counts know no diagonal state beside K
    # and V of one head: this cell is on none of their lists, nor on the
    # expert layer's
    older = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('mla_decode_roofline', 'hybrid_decode_roofline',
                 'swa_decode_roofline', 'mhc_decode_roofline',
                 'kda_decode_roofline', 'moe_decode_roofline',
                 'moe_experts_touched_share', 'moe_picks_held_share',
                 'experts_decode_share', 'attn_decode_share',
                 'decode_roofline'):
        assert CELL not in older[name]['workloads']


def test_the_span_metrics_of_the_cell_on_made_up_rounds():
    rows = SPEC.read_metric('attn_needed_rows_share',
                            _context(0.01, [_round()]))
    assert rows == pytest.approx(100.0 * 2_500 / 2_560)
    assert SPEC.data('metrics', 'ssm_decode_roofline') == {
        'unit': '%', 'reader': 'ssm_decode_roofline',
        'args': {'match': 'decode'}}
    # no trace, nothing read, no error
    assert SPEC.read_metric('ssm_decode_roofline',
                            _context(0.01, [_round()], trace=False)) is None


# ---------------------------------------------------------------------------
# a toy rehearsal of the cell, added by files and entries alone
# ---------------------------------------------------------------------------
def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    root = _toy.make_root(tmp_path_factory.mktemp('toy_ssm'), copy=True)
    bdir = os.path.join(root, 'benchmarks')
    # 1024 wide, not 64: the generator's one deviation (0.02) gives a
    # projection a gain of 0.02 sqrt(1024) = 0.64 — at 64 it is 0.16, the
    # layers add a few percent to the residual stream, the tied head
    # answers every token with itself, and no fault changes a token
    cfg = dict(CFG, name='toy-jamba', source='none: toy', vocab_size=512,
               hidden_size=1024, intermediate_size=2048,
               num_hidden_layers=4, attn_layer_offset=1,
               attn_layer_period=3, num_attention_heads=8,
               num_key_value_heads=1, mamba_d_state=8, mamba_dt_rank=64,
               max_position_embeddings=64, param_dtype='float32', params=0,
               reduced=[])
    _write(os.path.join(bdir, 'configs', 'toy-jamba.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        traffic = json.load(f)
    # eight requests through the reference: a faulty program is seen
    # where a served TOKEN is not the reference's, and over a handful of
    # tokens the toy's small logits may well keep their order
    traffic.update(slots=2, prompt={'kind': 'uniform', 'min': 1, 'max': 28},
                   output={'kind': 'uniform', 'min': 12, 'max': 30},
                   check_requests=8)
    _write(os.path.join(bdir, 'traffic', 'toy-ssm.json'), traffic)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-ssm.json'), json.load(f))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-jamba', 'source': 'none: toy', 'reduced': [],
        'file': 'benchmarks/configs/toy-jamba.json', 'why': 'toy'})
    bench['workloads'].append({
        'name': 'toy-ssm', 'config': 'toy-jamba', 'traffic': 'toy-ssm',
        'chips': 1, 'why': 'toy'})
    for m in bench['end_to_end']:
        if m['name'] == 'tpot_p50_ms':      # as the real cell
            m['workloads'].append('toy-ssm')
    real = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in ('attn_needed_rows_share', *sorted(NEW_PER_LAYER)):
        bench['per_layer'].append(dict(real[name], workloads=['toy-ssm']))
    _write(path, bench)
    return root


def test_toy_rehearsal_is_correct_and_reports_the_span_metrics(toy_root):
    """Traced, on the CPU: the reference (the recurrence token by token
    from zeros) agrees with what was served (chunks of a scan, a state
    handed off at the prompt's length, contexts to 58), the span metric
    is read, and what needs a device plane reports nothing and raises
    nothing."""
    out, lines = _toy.run_toy(toy_root, 'toy-ssm', seed=5000000046,
                              seconds=2.0, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    m = out['metrics']
    assert 0.0 < m['attn_needed_rows_share']['value'] <= 100.0
    assert not (NEW_PER_LAYER | {'attn_decode_share', 'decode_roofline',
                                 'kda_decode_roofline',
                                 'hybrid_decode_roofline'}) & set(m)


def test_toy_cell_reports_the_two_end_to_end_metrics(toy_root):
    toy = spec.Spec(toy_root)
    assert {m['name'] for m in toy.metrics_of('toy-ssm', 'end_to_end')} \
        == {'tpot_p50_ms', 'setup_s'}
    assert NEW_PER_LAYER <= {m['name'] for m in
                             toy.metrics_of('toy-ssm', 'per_layer')}


# the toy's inner norms are 64, 8 and 8 wide, and no other norm is
_FAULTS = jamba_faults.faults(64, 8)


@pytest.mark.parametrize('fault', sorted(_FAULTS))
def test_toy_rehearsal_of_a_faulty_program_is_not_correct(toy_root, fault):
    """The three faulty programs the chip's limit has to refuse, at the
    toy size: the state seated as the padded bucket leaves it; the three
    inner norms dropped (the toy's are 64, 8 and 8 wide, and no other
    norm is); `D u` dropped."""
    out, lines = _toy.run_toy(toy_root, 'toy-ssm', seed=46, seconds=3.0,
                              patch=_FAULTS[fault])
    assert out['correct'] is False
    assert any('served_logit_gap_widest' in ln and 'NOT CORRECT' in ln
               for ln in lines)


def test_toy_rehearsal_of_the_near_tie_witness(toy_root):
    """`jamba_faults.py near_ties` wraps the reference pass and patches
    nothing of the program: the verdict stands, and a line a checked
    request says where its tokens left the reference's argmax (on the CPU
    `high` IS float32: the reference at the program's precision is off
    nowhere)."""
    out, lines = _toy.run_toy(
        toy_root, 'toy-ssm', seed=5000000046, seconds=2.0,
        patch=f'sys.path.insert(0, {_toy.HERE!r})\n'
              'import jamba_faults\njamba_faults.near_ties()\n')
    assert out['correct'] is True, lines[-12:]
    said = _toy.logged(lines, 'near_ties request')
    checked = int(_toy.logged(lines, 'reference:')[0].split()[0])
    assert len(said) == checked > 0     # as many as finished in 2 s, of 8
    assert all('the reference at `high` off at 0:' in ln for ln in said)


# ---------------------------------------------------------------------------
# the real entries: subset pins
# ---------------------------------------------------------------------------
def test_real_benchmark_entries_of_the_cell():
    cell = SPEC.workload(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == (CONFIG, 'reason-ssm', 1)
    assert len(cell['why']) <= 200
    e2e = {m['name'] for m in SPEC.metrics_of(CELL, 'end_to_end')}
    assert e2e == {'tpot_p50_ms', 'setup_s'}
    layer = {m['name'] for m in SPEC.metrics_of(CELL, 'per_layer')}
    assert NEW_PER_LAYER | APPENDED_TO - {'tpot_p50_ms'} \
        | {'decode_substep_ms', 'serve_device_idle_share'} <= layer
    # their counts are other blocks', and there is no expert layer
    assert not {m for m in layer if m.startswith('moe_')
                or m.endswith('_roofline')} - NEW_PER_LAYER
    assert 'experts_decode_share' not in layer
    entries = {m['name']: m for m in SPEC.bench['per_layer']}
    for name in NEW_PER_LAYER:
        m = entries[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
        assert (m['unit'], m['source'], m['better'], m['layer']) \
            == ('%', 'device_trace', 'higher',
                'state-space layers: nlp/jamba.py')
    # under 90% of the decode programs' op time is placed (PERF.md 7f):
    # no share by scope is an entry of this cell
    assert 'ssm_decode_share' not in entries
    assert CELL not in entries['attn_decode_share']['workloads']
    assert CELL not in entries['experts_decode_share']['workloads']
    for m in SPEC.bench['end_to_end'] + SPEC.bench['per_layer']:
        if m['name'] in APPENDED_TO:
            assert CELL in m['workloads']
    assert CONFIG in {c['name'] for c in SPEC.bench['configs']}
    tr = SPEC.cell(CELL)['traffic']
    assert (tr['kind'], tr['max_length'], tr['decode_block'],
            tr['queue_depth']) == ('serve_backlog', 5_120, 4, 8)
    # ISSUE 46's size, not its fallback of 96: `tpot_p50_ms` spread by
    # 0.3% over three seeds at 128 (18.01-18.06) and at 96 (16.05-16.09)
    assert tr['slots'] == 128
    assert tr['buckets'] == [512, 768, 1024]
    assert (tr['prompt']['min'], tr['prompt']['max']) == (256, 1024)
    assert (tr['output']['min'], tr['output']['max']) == (1024, 4096)
    assert (tr['warm_output']['min'], tr['warm_output']['max']) == (8, 96)
    # a context ends at 1,280-5,120, inside the slot
    assert tr['prompt']['max'] + tr['output']['max'] == 5_120 \
        == tr['max_length'] == CFG['max_position_embeddings']
    assert max(tr['buckets']) >= tr['prompt']['max']
    assert tr['check_requests'] == 2 and tr['trace_s'] == 5.0
    limits = SPEC.cell(CELL)['limits']
    assert limits['control'] == 'fp8' and limits['served_gap'] > 0
    for word in ('pads folded', 'inner norms', 'D u', 'fp8'):
        assert word in limits['readings']


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER | _ling.NEW_PER_LAYER | _xing.NEW_PER_LAYER \
        <= _pin.NEW_DEVICE


def test_each_view_given_to_an_older_pin_lacks_exactly_these_entries():
    """`test_ling_cell` reads the real file less what THIS PR appended —
    in its `SPEC` and in every `spec.Spec()` it makes —, and the views
    behind it are rebuilt from that with the older modules' own
    `_before_this_cell`, so each still differs from the next by its own
    PR's entries alone."""
    real = spec.Spec().bench
    _xing._differs_by(real, _ling.SPEC.bench, {CONFIG}, {CELL},
                      NEW_PER_LAYER, APPENDED_TO)
    assert _ling.spec.Spec().bench == _ling.SPEC.bench
    assert _ling.spec.Spec is not spec.Spec
    _xing._differs_by(_ling.SPEC.bench, _xing.SPEC.bench, {_ling.CONFIG},
                      {_ling.CELL}, _ling.NEW_PER_LAYER, _ling.APPENDED_TO)
    assert _xing.spec.Spec().bench == _xing.SPEC.bench == _mimo.SPEC.bench
    _xing._differs_by(_xing.SPEC.bench, _kanana.SPEC.bench, {_xing.CONFIG},
                      {_xing.CELL}, _xing.NEW_PER_LAYER, _xing.APPENDED_TO)
    _xing._differs_by(_kanana.SPEC.bench, _host.SPEC.bench,
                      {'kanana-2-30b-a3b'}, {_kanana.CELL},
                      _kanana.NEW_PER_LAYER, _kanana.APPENDED_TO)
    # and with them the older pins hold
    assert _ling.SPEC.bench['per_layer'][-1]['name'] \
        == 'kda_decode_roofline'
    assert _ling.SPEC.bench['workloads'][-1]['name'] == _ling.CELL
    assert _xing.SPEC.bench['per_layer'][-1]['name'] \
        == 'mhc_decode_roofline'
    assert _kanana.SPEC.bench['per_layer'][-1]['name'] \
        == 'mla_decode_roofline'
    assert [m['name'] for m in _host.SPEC.bench['per_layer']][-1] \
        == 'conv_decode_share'
