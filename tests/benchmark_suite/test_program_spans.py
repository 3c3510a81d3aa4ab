"""The per-layer metrics that read the program's own spans, counts and
named scopes (ISSUE 24): each new reader on hand-made spans or a
hand-made trace plus scope table, the program's spans on the profiler's
timeline as `xtrace` sees them, and a toy rehearsal through a toy
benchmark that carries the new `per_layer` entries."""
import json
import os

import pytest

import _toy
from benchmarks import counts, spec, xtrace as X

SPEC = spec.Spec()
NEW_HOST = {
    'toy-chat': {'engine_host_gap_ms', 'engine_dispatch_ms',
                 'engine_emit_ms', 'engine_admit_ms', 'prefill_wall_ms',
                 'ttft_tail_prefill_share', 'decode_batch_occupancy',
                 'engine_kv_real_rows_share'},
    'toy-docs': {'engine_host_gap_ms', 'engine_dispatch_ms',
                 'engine_emit_ms', 'decode_batch_occupancy',
                 'engine_kv_real_rows_share'},
}
NEW_DEVICE = {'train_forward_share', 'train_backward_share',
              'train_optimizer_kernels_share'}


def _ctx(raw, trace=None):
    cell = {'config': {}, 'traffic': {}, 'chips': 1}
    return spec.ReadContext(cell, raw, trace, None, counts)


def _read(name, ctx):
    return SPEC.read_metric(name, ctx)


# ---------------------------------------------------------------------------
# hand-made spans: two router steps since window open, one before it
# ---------------------------------------------------------------------------

def _span(name, span_id, parent, start_ms, dur_ms, **attrs):
    ev = {'name': name, 'ph': 'X', 'ts': start_ms * 1e-3,
          'dur': dur_ms * 1e-3, 'tid': 1, 'depth': 1, 'id': span_id,
          'parent': parent}
    if attrs:
        ev['attrs'] = attrs
    return ev


def _round(base_id, t0, admitted, prefill_ms, active, real_rows):
    """One router step at t0 ms, children ending before their parents."""
    i = base_id
    out = []
    t = t0 + 1.0
    if admitted:
        out.append(_span('serving.prefill', i + 3, i + 2, t + 0.5,
                         prefill_ms, request_id=7))
    admit_ms = 1.0 + (prefill_ms if admitted else 0.0)
    out.append(_span('serving.admit', i + 2, i + 1, t, admit_ms,
                     admitted=admitted))
    t += admit_ms
    out.append(_span('serving.decode_dispatch', i + 5, i + 4, t, 3.0))
    out.append(_span('serving.d2h', i + 6, i + 4, t + 3.0, 80.0))
    out.append(_span('serving.decode_round', i + 4, i + 1, t, 83.5,
                     active=active, slots=4, real_rows=real_rows))
    t += 83.5
    out.append(_span('serving.emit', i + 7, i + 1, t, 2.0))
    out.append(_span('serving.step', i + 1, i, t0 + 0.5, t + 2.5 - t0 - 0.5))
    out.append(_span('serving.router_step', i, 0, t0, t + 3.0 - t0))
    return out


@pytest.fixture
def spans():
    from paddle_tpu import observability as obs
    log = obs.get_event_log()
    log.clear()
    for ev in (_round(10, 0.0, 0, 0.0, 4, 100)          # before the window
               + _round(20, 100.0, 1, 20.0, 2, 512)
               + _round(30, 220.0, 0, 0.0, 3, 1024)):
        log.append(ev)
    yield log
    log.clear()


RAW = {'decode_rounds': 2, 'max_length': 256, 'slots': 4}


def test_program_span_duration_self_and_gap(spans):
    ctx = _ctx(RAW)
    assert _read('engine_dispatch_ms', ctx) == pytest.approx(3.0)
    assert _read('engine_emit_ms', ctx) == pytest.approx(2.0)
    assert _read('prefill_wall_ms', ctx) == pytest.approx(20.0)
    # self time of the one admit that seated a request: 21 ms less its
    # 20 ms prefill child; the step that admitted nothing is left out
    assert _read('engine_admit_ms', ctx) == pytest.approx(1.0)
    # end of round 1's d2h (205 ms) to end of round 2's dispatch (225 ms)
    assert _read('engine_host_gap_ms', ctx) == pytest.approx(20.0)


def test_span_count_ratios(spans):
    ctx = _ctx(RAW)
    assert _read('decode_batch_occupancy', ctx) == pytest.approx(
        100.0 * (2 / 4 + 3 / 4) / 2)
    assert _read('engine_kv_real_rows_share', ctx) == pytest.approx(
        100.0 * (512 + 1024) / 2 / (4 * 256))


def test_readers_report_nothing_without_the_whole_range(spans):
    # more steps counted than the log holds router steps: part is gone
    assert _read('engine_dispatch_ms', _ctx(dict(RAW, decode_rounds=4))) \
        is None
    assert _read('decode_batch_occupancy',
                 _ctx(dict(RAW, decode_rounds=4))) is None
    # a program that names no router step (the parent commit)
    spans.clear()
    spans.append(_span('serving.decode_round', 1, 0, 0.0, 5.0))
    assert _read('engine_host_gap_ms', _ctx(RAW)) is None
    assert _read('ttft_tail_prefill_share', _ctx(RAW)) is None
    # a cell of another kind
    assert _read('engine_emit_ms', _ctx({})) is None


def test_a_full_ring_that_lost_children_reports_nothing(monkeypatch):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.events import EventLog
    small = EventLog(capacity=12)
    for ev in (_round(10, 0.0, 0, 0.0, 4, 100)
               + _round(20, 100.0, 0, 0.0, 2, 512)):
        small.append(ev)            # 14 events into 12: two are dropped
    monkeypatch.setattr(obs, 'get_event_log', lambda: small)
    assert small.dropped == 2
    assert _read('engine_dispatch_ms', _ctx(dict(RAW, decode_rounds=2))) \
        is None
    assert _read('engine_dispatch_ms', _ctx(dict(RAW, decode_rounds=1))) \
        == pytest.approx(3.0)


def test_ledger_tail_share(spans, monkeypatch):
    from paddle_tpu.observability import reqledger
    recs = [{'ts': 0.05, 'ttft_s': 9.0, 'ttft_phases': {'prefill': 9.0}}]
    recs += [{'ts': 0.11 + i * 1e-3, 'ttft_s': 0.1 + i * 1e-3,
              'ttft_phases': {'queue_wait': 0.05, 'prefill': 0.02}}
             for i in range(38)]
    recs += [{'ts': 0.2, 'ttft_s': 0.4, 'ttft_phases': {
        'queue_wait': 0.1, 'prefill': 0.06, 'prefill_wait': 0.14}},
        {'ts': 0.21, 'ttft_s': None, 'ttft_phases': {}}]
    monkeypatch.setattr(reqledger.get_ledger(), 'window_records',
                        lambda: recs, raising=False)
    # the record from before the window and the unanswered one are out;
    # of 39, the p95 cut leaves the 0.4 s request and the slowest other
    got = _read('ttft_tail_prefill_share', _ctx(RAW))
    assert got == pytest.approx(
        100.0 * (0.06 + 0.14 + 0.02) / (0.4 + 0.137))


# ---------------------------------------------------------------------------
# a hand-made trace and scope table
# ---------------------------------------------------------------------------

def _ev(name, start_us, dur_us):
    return [name, start_us * 1e-6, dur_us * 1e-6, {}]


def _summary(ops, host=()):
    return X.reduce({'planes': [
        {'name': '/device:TPU:0', 'lines': [{'name': 'XLA Ops',
                                             'events': list(ops)}]},
        {'name': '/host:CPU', 'lines': [{'name': 'python',
                                         'events': list(host)}]}]})


def test_scope_time_train_parts(monkeypatch):
    import paddle_tpu.programs as programs
    table = {'train_step': {
        'fusion.1': ('jit(step_fn)/jvp(mlp)/dot_general', 'bf16_4_8'),
        'fusion.2': ('jit(step_fn)/transpose(jvp(mlp))/dot_general',
                     'bf16_4_8', ('mlp', 'optimizer'), 'own'),
        'flash_attention.3': (
            'jit(step_fn)/jvp(attention)/jit(flash_attention)/pallas_call',
            'bf16_4_8'),
        'fusion.4': ('jit(step_fn)/optimizer/sub', 'bf16_4_8'),
        'copy.5': ('', 'bf16_4_8'),
        'fusion.6': ('jit(step_fn)/transpose(jvp(attention))/mul',
                     'bf16_4_8', ('attention',), 'callee')}}
    monkeypatch.setattr(programs, 'scope_table', lambda: table)
    ops = [_ev('%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p)', 0, 100),
           _ev('%flash_attention.3 = bf16[4,8]{1,0} custom-call(%fusion.1)',
               100, 100),
           _ev('%fusion.6 = bf16[4,8]{1,0} fusion(%u)', 200, 100),
           _ev('%fusion.2 = bf16[4,8]{1,0} fusion(%q)', 300, 400),
           _ev('%fusion.4 = bf16[4,8]{1,0} fusion(%r)', 700, 200),
           _ev('%copy.5 = bf16[4,8]{1,0} copy(%s)', 900, 50),
           _ev('%fusion.99 = f32[2]{0} fusion(%t)', 950, 50)]
    ctx = _ctx({}, _summary(ops))
    assert _read('train_forward_share', ctx) == pytest.approx(20.0)
    assert _read('train_backward_share', ctx) == pytest.approx(10.0)
    # a kernel that holds the update counts with the optimizer's, whatever
    # op_name the compiler left on it: fusion.2's is the matmul's
    assert _read('train_optimizer_kernels_share', ctx) == pytest.approx(60.0)
    # the rest is unplaced: an instruction without op_name, and one no
    # program knows — it belongs to no part


def _borrowing_ctx(monkeypatch, copy_us):
    """A step whose prefetch copy has only its user's name."""
    import paddle_tpu.programs as programs
    bwd = 'jit(step_fn)/transpose(jvp(mlp))/dot_general'
    table = {'train_step': {
        'fusion.1': ('jit(step_fn)/jvp(mlp)/dot_general', 'f32_12_8', (),
                     'own'),
        'copy-done.2': (bwd, 'f32_12_8', (), 'user'),
        'fusion.3': (bwd, 'f32_12_8', (), 'callee')}}
    monkeypatch.setattr(programs, 'scope_table', lambda: table)
    ops = [_ev('%fusion.1 = f32[12,8]{1,0} fusion(%a)', 0, 500),
           _ev('%copy-done.2 = f32[12,8]{1,0} copy-done(%b)', 500, copy_us),
           _ev('%fusion.3 = f32[12,8]{1,0} fusion(%c)', 700,
               500 - copy_us)]
    return _ctx({}, _summary(ops))


def test_scope_time_counts_a_borrowed_name_while_it_is_little(monkeypatch):
    from benchmarks.readers import scope_time
    assert scope_time.BORROWED_LIMIT == 3.0
    # 2% of the program's time by its user's name: counted with it
    ctx = _borrowing_ctx(monkeypatch, 20)
    assert _read('train_backward_share', ctx) == pytest.approx(50.0)


def test_scope_time_leaves_a_large_borrowed_share_unplaced(monkeypatch):
    # 5% is over the limit: a guess of that size is unplaced, in no part
    ctx = _borrowing_ctx(monkeypatch, 50)
    assert _read('train_backward_share', ctx) == pytest.approx(45.0)
    assert _read('train_forward_share', ctx) == pytest.approx(50.0)


def test_scope_time_reports_nothing_when_too_little_is_placed(monkeypatch):
    from benchmarks.readers import scope_time
    assert scope_time.MIN_PLACED == 90.0
    ctx = _borrowing_ctx(monkeypatch, 120)      # 12% unplaced
    assert _read('train_backward_share', ctx) is None


def test_scope_time_places_shared_names_by_shape_and_neighbours(monkeypatch):
    import paddle_tpu.programs as programs
    table = {
        'train_step': {
            'fusion.1': ('jit(f)/jvp(mlp)/dot_general', 'f32_12_1024'),
            'fusion.2': ('jit(f)/transpose(jvp(attention))/mul',
                         'f32_12_8'),
            'fusion.3': ('jit(f)/jvp(mlp)/dot_general', 'bf16_12_8')},
        'eval_step': {
            'fusion.1': ('jit(g)/mlp/dot_general', 'bf16_1_64'),
            'fusion.3': ('jit(g)/attention/dot_general', 'bf16_12_8')}}
    monkeypatch.setattr(programs, 'scope_table', lambda: table)
    ops = [
        # the step: fusion.1 by its shape, then fusion.3, shared with the
        # other program in name AND shape, goes to the program running
        _ev('%fusion.1 = f32[12,1024]{1,0} fusion(%a)', 0, 300),
        _ev('%fusion.2 = f32[12,8]{1,0} fusion(%b)', 300, 100),
        _ev('%fusion.3 = bf16[12,8]{1,0} fusion(%c)', 400, 400),
        # fusion.1 with the other program's shape switches programs
        _ev('%fusion.1 = bf16[1,64]{1,0} fusion(%d)', 800, 100),
        _ev('%fusion.3 = bf16[12,8]{1,0} fusion(%e)', 900, 100)]
    ctx = _ctx({}, _summary(ops))
    # of all op time: the step's two forward kernels, and its backward one
    assert _read('train_forward_share', ctx) == pytest.approx(70.0)
    assert _read('train_backward_share', ctx) == pytest.approx(10.0)


def test_scope_time_reports_nothing_without_table_or_trace(monkeypatch):
    import paddle_tpu.programs as programs
    assert _read('train_optimizer_kernels_share', _ctx({})) is None
    monkeypatch.setattr(programs, 'scope_table', lambda: {})
    ops = [_ev('%fusion.1 = bf16[4,8]{1,0} fusion(%p)', 0, 100)]
    ctx = _ctx({}, _summary(ops))
    assert _read('train_optimizer_kernels_share', ctx) is None
    monkeypatch.delattr(programs, 'scope_table')     # the parent commit
    assert _read('train_forward_share', _ctx({}, _summary(ops))) is None


# ---------------------------------------------------------------------------
# the program's spans on the profiler's timeline
# ---------------------------------------------------------------------------

def test_gap_under_a_program_span_is_attributed_to_it():
    ops = [_ev('fusion.1', 0, 100), _ev('fusion.2', 400, 100),
           _ev('fusion.3', 900, 100)]
    host = [_ev('bench.router_step', 0, 1000),
            _ev('serving.router_step', 10, 980),
            _ev('serving.step', 20, 900),
            _ev('serving.decode_round', 30, 500),
            _ev('serving.decode_dispatch', 30, 60),
            _ev('serving.d2h', 90, 440),
            _ev('serving.emit', 540, 350)]
    gaps = dict(_summary(ops, host)['idle_gaps'])
    assert gaps == {'serving.d2h': pytest.approx(300e-6),
                    'serving.emit': pytest.approx(400e-6)}


def test_cpu_profile_holds_program_spans_inside_the_benchmarks(tmp_path):
    """Under a jax.profiler trace (here of a CPU), `xtrace.load` finds
    the toy engine's `serving.*` spans, nested inside the span the
    benchmark draws around the router step."""
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
    names = {s[0] for s in spans}
    assert {'bench.router_step', 'serving.router_step', 'serving.step',
            'serving.admit', 'serving.prefill', 'serving.decode_round',
            'serving.decode_dispatch', 'serving.d2h', 'serving.emit',
            'serving.reap'} <= names
    assert 'serving.queue' not in names      # it nests in nothing
    outer = [s for s in spans if s[0] == 'bench.router_step']
    for s in spans:
        if s[0].startswith('serving.'):
            assert any(o[1] <= s[1] and s[1] + s[2] <= o[1] + o[2] + 1e-6
                       for o in outer), s


# ---------------------------------------------------------------------------
# the toy rehearsal, through a toy benchmark with the new entries
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """The toy BENCHMARK.json plus the `per_layer` entries the real one
    has and the toy one lacks (`data/toy_BENCHMARK.json` stays as it
    is)."""
    root = _toy.make_root(tmp_path_factory.mktemp('toy_spans'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        toy = json.load(f)
    have = {m['name'] for m in toy['per_layer']}
    new = [m for m in SPEC.bench['per_layer'] if m['name'] not in have]
    assert {m['name'] for m in new} == NEW_HOST['toy-chat'] | NEW_DEVICE
    toy['per_layer'] += new
    with open(path, 'w') as f:
        json.dump(toy, f)
    return root


@pytest.mark.parametrize('cell', sorted(NEW_HOST))
def test_traced_rehearsal_reports_every_host_span_metric(root, cell):
    out, lines = _toy.run_toy(root, cell, seed=12, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert NEW_HOST[cell] <= set(out['metrics'])
    m = {k: v['value'] for k, v in out['metrics'].items()}
    assert 0 < m['decode_batch_occupancy'] <= 100
    assert 0 < m['engine_kv_real_rows_share'] <= 100
    # the pool's own book against the driver's count from outside: they
    # differ by the rows of the round in flight, a few points at toy size
    assert abs(m['engine_kv_real_rows_share']
               - m['kv_real_rows_share']) < 15
    assert m['engine_host_gap_ms'] > 0 and m['engine_dispatch_ms'] > 0
    # a CPU trace has no device plane: no device-scope metric
    assert not NEW_DEVICE & set(out['metrics'])


def test_untraced_rehearsal_reports_none_of_them(root):
    out, _ = _toy.run_toy(root, 'toy-chat', seed=13)
    assert not (NEW_HOST['toy-chat'] | NEW_DEVICE) & set(out['metrics'])
    assert set(out['metrics']) == {'ttft_p95_ms', 'tpot_p50_ms', 'setup_s'}
