"""A decode round's host time by cause (ISSUE 35): the two spans every
stored program's call opens, as the benchmark's timeline and its readers
see them; the count of the router's slow-step records; the decode
programs' op time by named scope; and a toy rehearsal that reports the
three host metrics.

Importing this module also extends `test_program_spans.py`'s pin of the
`per_layer` entries (PR 24 pinned them by equality; PERF.md section 7e)
by the six this PR brought, as `test_lfm2_cell.py` and
`test_mimo_cell.py` do: the three host metrics ARE reported by a toy
cell on a CPU, so they join that file's `NEW_HOST`; the three shares
need a device plane and join `NEW_DEVICE`."""
import json
import os

import pytest

import _toy
import test_program_spans as _pin
from benchmarks import spec, xtrace as X
from benchmarks.readers import scope_time

NEW_HOST = {'engine_resolve_ms', 'engine_call_ms', 'router_slow_steps'}
NEW_SHARES = {'experts_decode_share', 'attn_decode_share',
              'conv_decode_share'}
for _cell in _pin.NEW_HOST:
    _pin.NEW_HOST[_cell] = _pin.NEW_HOST[_cell] | NEW_HOST
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_SHARES

SPEC = spec.Spec()
_read, _ctx, _span, _ev = _pin._read, _pin._ctx, _pin._span, _pin._ev


def test_the_per_layer_pin_is_extended_at_import():
    assert all(NEW_HOST <= names for names in _pin.NEW_HOST.values())
    assert NEW_SHARES <= _pin.NEW_DEVICE
    assert not NEW_HOST & _pin.NEW_DEVICE


def test_the_six_entries_as_the_benchmark_has_them():
    entries = {m['name']: m for m in SPEC.bench['per_layer']}
    assert list(entries)[-6:] == [
        'engine_resolve_ms', 'engine_call_ms', 'router_slow_steps',
        'experts_decode_share', 'attn_decode_share', 'conv_decode_share']
    for name in NEW_HOST | NEW_SHARES:
        assert entries[name]['moves'] == 'tpot_p50_ms'
    # the host metrics wherever a cell reports tpot_p50_ms; the shares
    # only where the reader's own rule lets the op time be placed
    assert all('workloads' not in entries[name] for name in NEW_HOST)
    both = ['serve-moe-docs', 'serve-hybrid-reason']
    assert entries['experts_decode_share']['workloads'] == both
    assert entries['attn_decode_share']['workloads'] == both
    assert entries['conv_decode_share']['workloads'] == both[1:]
    for cell in ('serve-chat', 'serve-docs', 'serve-swa-reason', *both):
        names = {m['name'] for m in SPEC.metrics_of(cell, 'per_layer')}
        assert NEW_HOST <= names
        assert (NEW_SHARES & names) == {
            n for n in NEW_SHARES if cell in entries[n]['workloads']}
    assert not (NEW_HOST | NEW_SHARES) & {
        m['name'] for m in SPEC.metrics_of('train-1chip', 'per_layer')}


# ---------------------------------------------------------------------------
# hand-made spans and events: the pin's rounds, with a dispatch's children
# ---------------------------------------------------------------------------

def _round(base_id, t0, resolve_ms, call_ms, prefill=False):
    """The pin's router step at t0 ms (no request seated: its dispatch
    starts 2 ms in and lasts 3) plus the dispatch's two children; with
    `prefill`, a prefill whose own two calls are long ones."""
    out = _pin._round(base_id, t0, 0, 0.0, 2, 512)
    t = t0 + 2.0
    out[1:1] = [
        _span('serving.program_resolve', base_id + 8, base_id + 5, t + 0.1,
              resolve_ms, leaves=41, host_leaves=9),
        _span('serving.program_call', base_id + 9, base_id + 5,
              t + 0.1 + resolve_ms, call_ms)]
    if prefill:     # before the round, under the admit span
        out[0:0] = [
            _span('serving.program_resolve', base_id + 11, base_id + 10,
                  t0 + 1.1, 0.2, leaves=29, host_leaves=0),
            _span('serving.program_call', base_id + 12, base_id + 10,
                  t0 + 1.3, 0.5),
            _span('serving.prefill', base_id + 10, base_id + 2, t0 + 1.0,
                  0.9, request_id=3)]
    return out


def _slow_step(t_ms):
    return {'name': 'serving_slow_step', 'ph': 'i', 'ts': t_ms * 1e-3,
            'tid': 1, 'attrs': {'dur_s': 1.5, 'under': 'serving.d2h'}}


@pytest.fixture
def spans():
    from paddle_tpu import observability as obs
    log = obs.get_event_log()
    log.clear()
    for ev in (_round(100, 0.0, 9.0, 9.0) + [_slow_step(50.0)]  # before
               + _round(200, 100.0, 0.8, 1.6)
               + _round(300, 200.0, 1.0, 1.8, prefill=True)
               + [_slow_step(290.0)]
               + _round(400, 300.0, 1.2, 1.7)):
        log.append(ev)
    yield log
    log.clear()


RAW = dict(_pin.RAW, decode_rounds=3)


def test_resolve_and_call_are_medians_over_every_serving_program(spans):
    # three rounds' calls and one prefill's: the median is the decode
    # block's, dispatched several times as often as a prefill
    assert _read('engine_resolve_ms', _ctx(RAW)) == pytest.approx(0.9)
    assert _read('engine_call_ms', _ctx(RAW)) == pytest.approx(1.65)
    assert _read('engine_dispatch_ms', _ctx(RAW)) == pytest.approx(3.0)


def test_slow_steps_are_counted_since_the_window_opened(spans):
    assert _read('router_slow_steps', _ctx(RAW)) == 1.0
    assert _read('router_slow_steps', _ctx(dict(RAW, decode_rounds=4))) \
        == 2.0
    spans.append(_slow_step(390.0))
    assert _read('router_slow_steps', _ctx(RAW)) == 2.0
    assert _read('router_slow_steps', _ctx(dict(RAW, decode_rounds=1))) \
        == 1.0


def test_a_window_without_a_slow_step_reads_zero_not_nothing(spans):
    assert _read('router_slow_steps', _ctx(dict(RAW, decode_rounds=1))) \
        == 0.0


def test_the_host_readers_report_nothing_where_nothing_can_be_read(
        spans, monkeypatch):
    from paddle_tpu import observability as obs
    # more steps counted than the log holds: part of the window is gone
    gone = _ctx(dict(RAW, decode_rounds=5))
    assert _read('engine_resolve_ms', gone) is None
    assert _read('router_slow_steps', gone) is None
    # a cell of another kind
    assert _read('engine_call_ms', _ctx({})) is None
    assert _read('router_slow_steps', _ctx({})) is None
    # the parent commit: the steps are there; the two spans are not, and
    # the event is not declared, so no step could have counted
    schema = dict(obs.EVENT_SCHEMA)
    del schema['serving_slow_step']
    monkeypatch.setattr(obs, 'EVENT_SCHEMA', schema)
    assert _read('router_slow_steps', _ctx(RAW)) is None
    spans.clear()
    for ev in _pin._round(20, 100.0, 1, 20.0, 2, 512):
        spans.append(ev)
    parent = _ctx(dict(RAW, decode_rounds=1))
    assert _read('engine_dispatch_ms', parent) == pytest.approx(3.0)
    assert _read('engine_resolve_ms', parent) is None
    assert _read('engine_call_ms', parent) is None


# ---------------------------------------------------------------------------
# a hand-made trace and scope table: the decode programs' op time by scope
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _fresh_scope_cache():
    scope_time._cache.clear()       # keyed by id(): no stale table
    yield
    scope_time._cache.clear()


_D = 'jit(_decode_block_fn)/while/body/'
TABLE = {
    'serving.decode_block': {
        'fusion.1': (_D + 'moe/experts/pallas_call', 'f32_8_64'),
        'fusion.2': (_D + 'attention/dot_general', 'f32_8_64'),
        'fusion.3': (_D + 'attention/norm/mul', 'f32_8_32'),
        'fusion.4': (_D + 'attention/kv_write/scatter', 'f32_8_16'),
        'fusion.5': (_D + 'conv/state_write/dynamic_update_slice',
                     'f32_8_8', (), 'callee'),
        'fusion.6': (_D + 'lm_head/dot_general', 'f32_8_128'),
        'copy-done.7': (_D + 'mlp/dot_general', 'f32_8_4', (), 'user'),
        'copy.8': ('', 'f32_8_2'),
        'fusion.9': ('jit(_decode_block_fn)/add', 'f32_8_1')},
    'serving.decode_block_r32': {
        'fusion.1': (_D + 'moe/experts/pallas_call', 'f32_4_64')},
    'serving.prefill_64': {
        'fusion.1': ('jit(_prefill_fn)/attention/dot_general',
                     'bf16_64_64')}}


def _decode_ctx(monkeypatch, borrowed_us=20, unnamed_us=30, table=TABLE):
    """1000 us of the whole-length decode block and 1000 of the half one,
    then a prefill of 3000 us that no share may see."""
    import paddle_tpu.programs as programs
    if table is not None:
        monkeypatch.setattr(programs, 'scope_table', lambda: table)
    rest = 1000 - 400 - 150 - 30 - 20 - 40 - 100 - borrowed_us - unnamed_us
    ops, t = [], 0
    for name, shape, opcode, us in (
            ('fusion.1', 'f32[8,64]', 'fusion', 400),
            ('fusion.2', 'f32[8,64]', 'fusion', 150),
            ('fusion.3', 'f32[8,32]', 'fusion', 30),
            ('fusion.4', 'f32[8,16]', 'fusion', 20),
            ('fusion.5', 'f32[8,8]', 'fusion', 40),
            ('fusion.6', 'f32[8,128]', 'fusion', 100),
            ('copy-done.7', 'f32[8,4]', 'copy-done', borrowed_us),
            ('copy.8', 'f32[8,2]', 'copy', unnamed_us),
            ('fusion.9', 'f32[8,1]', 'fusion', rest),
            ('fusion.1', 'f32[4,64]', 'fusion', 1000),
            ('fusion.1', 'bf16[64,64]', 'fusion', 3000)):
        ops.append(_ev(f'%{name} = {shape}{{1,0}} {opcode}(%p)', t, us))
        t += us
    return _ctx({}, _pin._summary(ops))


def test_decode_scope_share_is_of_the_decode_programs_alone(monkeypatch):
    ctx = _decode_ctx(monkeypatch)
    # of 2000 us in the two decode programs (the prefill's 3000 are no
    # part of it): the experts' 400 + 1000
    assert _read('experts_decode_share', ctx) == pytest.approx(70.0)
    # attention by its OUTERMOST scope: the products, the norm of Q and
    # K inside it and its cache write, 150 + 30 + 20
    assert _read('attn_decode_share', ctx) == pytest.approx(10.0)
    # conv with the update of its state, named by what the fusion holds
    assert _read('conv_decode_share', ctx) == pytest.approx(2.0)


def test_decode_scope_share_counts_a_borrowed_name_while_it_is_little(
        monkeypatch):
    assert scope_time.BORROWED_LIMIT == 3.0 and scope_time.MIN_PLACED == 90.0
    # 1% borrowed counts, under `mlp`; the other scopes are as they were
    ctx = _decode_ctx(monkeypatch, borrowed_us=20)
    assert _read('experts_decode_share', ctx) == pytest.approx(70.0)
    # 4% borrowed is a guess too large: unplaced, with the 1.5% that has
    # no name at all 5.5% — still over 90% placed, and the shares of the
    # scopes that kept their own names do not move
    ctx = _decode_ctx(monkeypatch, borrowed_us=80)
    assert _read('experts_decode_share', ctx) == pytest.approx(70.0)
    assert _read('attn_decode_share', ctx) == pytest.approx(10.0)


def test_decode_scope_share_reports_nothing_under_90_percent_placed(
        monkeypatch):
    # 4% borrowed and 7% without a name: 89% placed
    ctx = _decode_ctx(monkeypatch, borrowed_us=80, unnamed_us=140)
    for name in NEW_SHARES:
        assert _read(name, ctx) is None
    # without the large guess the same trace places 93%
    ctx = _decode_ctx(monkeypatch, borrowed_us=0, unnamed_us=140)
    assert _read('experts_decode_share', ctx) == pytest.approx(70.0)


def test_decode_scope_share_reports_nothing_without_trace_table_or_program(
        monkeypatch):
    import paddle_tpu.programs as programs
    assert _read('experts_decode_share', _ctx({})) is None      # no trace
    # a store that holds no decode program: none of the op time is one's
    only_prefill = {'serving.prefill_64': TABLE['serving.prefill_64']}
    assert _read('attn_decode_share',
                 _decode_ctx(monkeypatch, table=only_prefill)) is None
    scope_time._cache.clear()
    monkeypatch.delattr(programs, 'scope_table')    # the parent of PR 24
    assert _read('conv_decode_share',
                 _decode_ctx(monkeypatch, table=None)) is None


# ---------------------------------------------------------------------------
# the two spans on the profiler's timeline
# ---------------------------------------------------------------------------

def test_gap_under_a_program_call_is_attributed_to_it():
    ops = [_ev('fusion.1', 0, 100), _ev('fusion.2', 400, 100)]
    host = [_ev('bench.router_step', 0, 1000),
            _ev('serving.router_step', 10, 980),
            _ev('serving.decode_round', 30, 500),
            _ev('serving.decode_dispatch', 30, 400),
            _ev('serving.program_resolve', 40, 100),
            _ev('serving.program_call', 150, 270)]
    # the gap's midpoint (250 us) lies in the call: the innermost span
    assert dict(_pin._summary(ops, host)['idle_gaps']) == {
        'serving.program_call': pytest.approx(300e-6)}


def test_cpu_profile_holds_the_two_spans_inside_the_benchmarks(tmp_path):
    """Under a jax.profiler trace (here of a CPU), `xtrace.load` finds
    `serving.program_resolve` and `serving.program_call` on the host
    plane — their names begin with `serving.`, the wrapper's kind —
    inside a `serving.decode_dispatch` or a `serving.prefill`, and those
    inside the span the benchmark draws around the router step."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ReplicaSet, Router, SamplingParams
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig.tiny()).eval()
    router = Router(ReplicaSet(model, 1, num_slots=2, max_length=64,
                               decode_block=2))

    def drive():
        router.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4,
                                                      eos_token_id=-1))
        while router._live:
            with jax.profiler.TraceAnnotation('bench.router_step'):
                router.step()
    drive()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    spans = X.host_spans(X.load(X.find_xplane(str(tmp_path))))

    def inside(s, names):
        return any(o[0] in names and o[1] <= s[1] + 1e-6
                   and s[1] + s[2] <= o[1] + o[2] + 1e-6 for o in spans)
    mine = [s for s in spans if s[0] in ('serving.program_resolve',
                                         'serving.program_call')]
    assert {s[0] for s in mine} == {'serving.program_resolve',
                                    'serving.program_call'}
    # two rounds' dispatches, and the prefill with the seat of its row
    assert len(mine) == 2 * (2 + 2)
    for s in mine:
        assert inside(s, ('serving.decode_dispatch', 'serving.prefill')), s
        assert inside(s, ('bench.router_step',)), s


# ---------------------------------------------------------------------------
# the toy rehearsal
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """The toy BENCHMARK.json plus every `per_layer` entry the real one
    has and the toy one lacks, as the pin's own fixture builds it (that
    one holds only beside the modules that extend the pin; this file
    runs alone too)."""
    root = _toy.make_root(tmp_path_factory.mktemp('toy_host_causes'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        toy = json.load(f)
    have = {m['name'] for m in toy['per_layer']}
    toy['per_layer'] += [m for m in SPEC.bench['per_layer']
                         if m['name'] not in have]
    with open(path, 'w') as f:
        json.dump(toy, f)
    return root


def test_traced_rehearsal_reports_the_three_host_metrics(root):
    out, lines = _toy.run_toy(root, 'toy-chat', seed=35000011, trace=1)
    assert out['correct'] is True, lines[-12:]
    m = {k: v['value'] for k, v in out['metrics'].items()}
    assert NEW_HOST <= set(m) and not NEW_SHARES & set(m)
    assert {out['metrics'][k]['unit'] for k in ('engine_resolve_ms',
                                                'engine_call_ms')} == {'ms'}
    # the two children lie inside the dispatch (medians, so nearly)
    assert 0 < m['engine_resolve_ms'] < m['engine_dispatch_ms']
    assert 0 < m['engine_call_ms'] < m['engine_dispatch_ms']
    assert m['router_slow_steps'] >= 0 \
        and m['router_slow_steps'] == int(m['router_slow_steps'])


def test_untraced_rehearsal_reports_none_of_them(root):
    out, _ = _toy.run_toy(root, 'toy-docs', seed=35000013)
    assert not (NEW_HOST | NEW_SHARES) & set(out['metrics'])
