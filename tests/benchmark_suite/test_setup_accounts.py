"""Set-up accounts for itself (ISSUE 48): the two readers that read what
a process's set-up cost from the program's own counters — on hand-made
registries and logs —, the nine `per_layer` entries that move `setup_s`,
and both toy rehearsals reporting all nine on the CPU.

**The pins (PERF.md 7e): the hand-over now has SEVEN places.** This PR
adds no cell, only nine metrics, so the view it hands on is the real
file less those nine. At import, and AFTER importing `test_jamba_cell`
(whose import hands the older six their views), this module extends
`test_program_spans.NEW_DEVICE` — the entries of the real file a toy
cell does not report: each of the nine lists the real cells as its
`workloads` — and gives `test_jamba_cell` the benchmark without them:
its `SPEC.bench`, and the fresh `spec.Spec()` its view test reads,
through a shim under the name `spec` in that module (the shims behind it
call it, so they follow); then it rebuilds the views behind with the
older modules' OWN `_before_this_cell`. One test below holds each view
to differ from the real file by exactly these nine.

**Why the nine carry a `workloads` list** although they are reported
wherever `setup_s` is: `spec.metrics_of` follows a per-layer metric
without the key to the end-to-end metric it moves, and raises `KeyError:
'moves'` when THAT one has no `workloads` either — `setup_s` is the one
such metric, and no per-layer metric moved it before. The file is the
benchmark's and no PR but a `benchmark` one may edit it (PERF.md section
7), so the list names every cell, a later cell appends itself, and one
test here holds the list to be all the cells there are.

This module's own pins are SUBSET pins."""
import copy
import json
import os
import types

import pytest

import _toy
import test_jamba_cell as _jamba
import test_ling_cell as _ling
import test_xing_cell as _xing
import test_host_causes as _host
import test_kanana_cell as _kanana
import test_mimo_cell as _mimo
import test_program_spans as _pin
from benchmarks import counts, spec

NEW_PER_LAYER = {
    'setup_import_s', 'setup_engine_init_s', 'setup_program_build_s',
    'setup_program_trace_s', 'setup_program_lower_s',
    'setup_program_backend_s', 'setup_program_first_call_s',
    'setup_stepping_s', 'setup_programs_built'}
_pin.NEW_DEVICE = _pin.NEW_DEVICE | NEW_PER_LAYER

SPEC = spec.Spec()
LAYER = ('program store and set-up: programs/store.py, serving/engine.py, '
         'jit.TrainStep')


def _before_this_pr(bench):
    """`BENCHMARK.json` without what this PR added: nine metrics."""
    old = copy.deepcopy(bench)
    old['per_layer'] = [m for m in old['per_layer']
                        if m['name'] not in NEW_PER_LAYER]
    return old


def _spec_without_this_pr(root=None):
    """`spec.Spec` as `test_jamba_cell` may see it: the real file read
    less this PR's entries; a toy root as it is."""
    made = spec.Spec(root)
    if root is None:
        made.bench = _before_this_pr(made.bench)
    return made


_jamba.SPEC.bench = _before_this_pr(SPEC.bench)
_jamba.spec = types.SimpleNamespace(
    Spec=_spec_without_this_pr, ReadContext=spec.ReadContext)
_ling.SPEC.bench = _jamba._before_this_cell(_jamba.SPEC.bench)
_xing.SPEC.bench = _ling._before_this_cell(_ling.SPEC.bench)
_mimo.SPEC.bench = _ling._before_this_cell(_ling.SPEC.bench)
_kanana.SPEC.bench = _xing._before_this_cell(_xing.SPEC.bench)
_host.SPEC.bench = _kanana._before_this_cell(_kanana.SPEC.bench)


def _ctx(raw):
    cell = {'config': {}, 'traffic': {}, 'chips': 1}
    return spec.ReadContext(cell, raw, None, None, counts)


# ---------------------------------------------------------------------------
# the readers, on a hand-made registry and a hand-made log
# ---------------------------------------------------------------------------

@pytest.fixture
def registry(monkeypatch):
    """A registry of its own in the program's place."""
    from paddle_tpu import observability as obs
    reg = obs.MetricsRegistry(process_index=0)
    monkeypatch.setattr(obs, 'get_registry', lambda: reg)
    return reg


def _step(span_id, name, start_s, dur_s):
    return {'name': name, 'ph': 'X', 'ts': start_s, 'dur': dur_s, 'tid': 1,
            'depth': 1, 'id': span_id, 'parent': 0}


@pytest.fixture
def steps(registry):
    """Five router steps — 4 s and 2.5 s of warm-up with builds inside,
    then three of 0.1 s, the last two since the window opened — in the
    log and, as every span is, in `paddle_span_seconds`."""
    from paddle_tpu import observability as obs
    log = obs.get_event_log()
    log.clear()
    hist = registry.histogram('paddle_span_seconds', 'span wall time',
                              ('name',))
    t = 0.0
    for i, dur in enumerate((4.0, 2.5, 0.1, 0.1, 0.1)):
        log.append(_step(10 + i, 'serving.router_step', t, dur))
        hist.labels(name='serving.router_step').observe(dur)
        # a child: counted under its own name, never under the step's
        log.append(_step(100 + i, 'serving.step', t, dur / 2))
        hist.labels(name='serving.step').observe(dur / 2)
        t += dur + 0.01
    registry.counter('paddle_setup_seconds_total', 'set-up by phase',
                     ('phase',)).labels(phase='import').inc(1.5)
    yield log
    log.clear()


def test_registry_value_sums_the_children_whose_labels_match(registry):
    read = SPEC.reader('registry_value')
    fam = registry.counter('made_up_seconds_total', 'by phase and source',
                           ('phase', 'source'))
    fam.labels(phase='wall', source='compile').inc(3.0)
    fam.labels(phase='wall', source='disk').inc(0.5)
    fam.labels(phase='trace', source='compile').inc(1.25)
    registry.gauge('made_up_gauge', 'no labels').set(7.0)
    ctx = _ctx({})
    assert read(ctx, 'made_up_seconds_total') == 4.75
    assert read(ctx, 'made_up_seconds_total', {'phase': 'wall'}) == 3.5
    assert read(ctx, 'made_up_seconds_total',
                {'phase': 'wall', 'source': 'disk'}) == 0.5
    # declared, and nothing booked under that label yet: a true zero
    assert read(ctx, 'made_up_seconds_total', {'phase': 'lower'}) == 0.0
    assert read(ctx, 'made_up_gauge') == 7.0


def test_registry_value_of_a_family_the_program_lacks_is_none(registry):
    read = SPEC.reader('registry_value')
    ctx = _ctx({})
    # the parent of this PR declares none of the three families
    for name in NEW_PER_LAYER - {'setup_stepping_s'}:
        assert SPEC.read_metric(name, ctx) is None
    # a label the family does not have; a histogram
    registry.counter('made_up_total', '', ('phase',)).labels(
        phase='wall').inc()
    assert read(ctx, 'made_up_total', {'source': 'disk'}) is None
    registry.histogram('made_up_hist', '').observe(1.0)
    assert read(ctx, 'made_up_hist') is None


def test_the_nine_read_what_the_program_booked(registry):
    build = registry.counter('paddle_program_build_seconds_total', '',
                             ('phase',))
    for phase, secs in (('wall', 9.0), ('trace', 3.0), ('lower', 2.5),
                        ('backend', 2.0), ('cache_retrieval', 1.5),
                        ('first_call', 0.75)):
        build.labels(phase=phase).inc(secs)
    built = registry.counter('paddle_program_builds_total', '', ('source',))
    built.labels(source='compile').inc(2)
    built.labels(source='memory').inc(5)
    setup = registry.counter('paddle_setup_seconds_total', '', ('phase',))
    setup.labels(phase='import').inc(4.5)
    setup.labels(phase='construct').inc(0.25)
    got = {name: SPEC.read_metric(name, _ctx({}))
           for name in NEW_PER_LAYER - {'setup_stepping_s'}}
    assert got == {
        'setup_import_s': 4.5, 'setup_engine_init_s': 0.25,
        'setup_program_build_s': 9.0, 'setup_program_trace_s': 3.0,
        'setup_program_lower_s': 2.5, 'setup_program_backend_s': 2.0,
        'setup_program_first_call_s': 0.75, 'setup_programs_built': 7.0}


def test_stepping_before_the_window_is_the_total_less_the_windows(steps):
    # two steps since the window opened: 6.8 s in all less their 0.2 s
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 2})) == pytest.approx(6.6)
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 3})) == pytest.approx(6.5)
    # by one name as by the list of both cells' names
    read = SPEC.reader('span_seconds_before_window')
    assert read(_ctx({'decode_rounds': 2}), 'serving.router_step') \
        == pytest.approx(6.6)


def test_stepping_of_a_train_cell_reads_the_train_step(registry):
    from paddle_tpu import observability as obs
    log = obs.get_event_log()
    log.clear()
    hist = registry.histogram('paddle_span_seconds', '', ('name',))
    registry.counter('paddle_setup_seconds_total', '', ('phase',))
    for i, dur in enumerate((30.0, 0.5, 0.5, 0.5)):
        log.append(_step(10 + i, 'train.step', 40.0 * i, dur))
        hist.labels(name='train.step').observe(dur)
    try:
        assert SPEC.read_metric('setup_stepping_s', _ctx(
            {'steps_in_window': 1, 'traced_steps': 1})) \
            == pytest.approx(30.5)
    finally:
        log.clear()


def test_stepping_reads_nothing_without_the_whole_window(steps, registry):
    # more steps counted than the log holds: part of the window is gone
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 9})) is None
    # a cell of another kind
    assert SPEC.read_metric('setup_stepping_s', _ctx({})) is None


def test_stepping_reads_nothing_from_a_program_that_keeps_no_account(
        steps, registry):
    """The parent has the spans and their histogram and none of the
    set-up's counters: one term of a sum whose others it lacks."""
    del registry._families['paddle_setup_seconds_total']
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 2})) is None


def test_a_full_ring_that_lost_part_of_the_window_reads_nothing(
        registry, monkeypatch):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.events import EventLog
    small = EventLog(capacity=4)
    hist = registry.histogram('paddle_span_seconds', '', ('name',))
    registry.counter('paddle_setup_seconds_total', '', ('phase',))
    t = 0.0
    for i in range(3):              # six events into four
        small.append(_step(100 + i, 'serving.step', t, 0.5))
        small.append(_step(10 + i, 'serving.router_step', t, 1.0))
        hist.labels(name='serving.router_step').observe(1.0)
        t += 1.5
    monkeypatch.setattr(obs, 'get_event_log', lambda: small)
    assert small.dropped == 2
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 2})) is None
    assert SPEC.read_metric('setup_stepping_s', _ctx(
        {'decode_rounds': 1})) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# the real entries: subset pins
# ---------------------------------------------------------------------------

def test_the_nine_entries_move_setup_s_in_every_cell():
    entries = {m['name']: m for m in SPEC.bench['per_layer']}
    assert NEW_PER_LAYER <= set(entries)
    cells = [w['name'] for w in SPEC.bench['workloads']]
    for name in NEW_PER_LAYER:
        m = entries[name]
        assert (m['moves'], m['better'], m['layer']) \
            == ('setup_s', 'lower', LAYER)
        assert m['unit'] == ('count' if name == 'setup_programs_built'
                             else 's')
        assert m['source'] == ('program_span' if name == 'setup_stepping_s'
                               else 'program_counter')
        # every cell there is (see the module's text): `setup_s` has no
        # list, and a metric that moves it may not go without
        assert set(m['workloads']) == set(cells)
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        meta = SPEC.data('metrics', name)
        assert meta['unit'] == m['unit']
        assert meta['reader'] == (
            'span_seconds_before_window' if name == 'setup_stepping_s'
            else 'registry_value')
    assert 'workloads' not in next(
        m for m in SPEC.bench['end_to_end'] if m['name'] == 'setup_s')
    for cell in cells:
        layer = {m['name'] for m in SPEC.metrics_of(cell, 'per_layer')}
        assert NEW_PER_LAYER <= layer
        assert 'setup_s' in {m['name']
                             for m in SPEC.metrics_of(cell, 'end_to_end')}
    # what each of the eight counters reads
    assert SPEC.data('metrics', 'setup_import_s')['args'] == {
        'metric': 'paddle_setup_seconds_total',
        'labels': {'phase': 'import'}}
    assert SPEC.data('metrics', 'setup_engine_init_s')['args'][
        'labels'] == {'phase': 'construct'}
    for name, phase in (('build', 'wall'), ('trace', 'trace'),
                        ('lower', 'lower'), ('backend', 'backend'),
                        ('first_call', 'first_call')):
        assert SPEC.data('metrics', f'setup_program_{name}_s')['args'] == {
            'metric': 'paddle_program_build_seconds_total',
            'labels': {'phase': phase}}
    assert SPEC.data('metrics', 'setup_programs_built')['args'] == {
        'metric': 'paddle_program_builds_total'}
    assert SPEC.data('metrics', 'setup_stepping_s')['args'] == {
        'span': ['serving.router_step', 'train.step']}


def test_a_metric_that_moves_setup_s_may_not_go_without_its_list():
    """The reason for the lists, held: should `spec.metrics_of` come to
    follow such a metric (a `benchmark` PR's edit), this fails and the
    lists may go."""
    made = spec.Spec()
    made.bench = copy.deepcopy(made.bench)
    for m in made.bench['per_layer']:
        if m['name'] in NEW_PER_LAYER:
            del m['workloads']
    with pytest.raises(KeyError, match='moves'):
        made.metrics_of('serve-chat', 'per_layer')


def test_the_per_layer_pin_is_extended_at_import():
    assert NEW_PER_LAYER | _jamba.NEW_PER_LAYER | _ling.NEW_PER_LAYER \
        <= _pin.NEW_DEVICE


def test_each_view_given_to_an_older_pin_lacks_exactly_these_nine():
    real = spec.Spec().bench
    _xing._differs_by(real, _jamba.SPEC.bench, set(), set(), NEW_PER_LAYER,
                      set())
    assert [m for m in real['per_layer']
            if m['name'] in NEW_PER_LAYER] == real['per_layer'][-9:]
    assert _jamba.spec.Spec().bench == _jamba.SPEC.bench
    assert _jamba.spec.Spec is not spec.Spec
    _xing._differs_by(_jamba.SPEC.bench, _ling.SPEC.bench, {_jamba.CONFIG},
                      {_jamba.CELL}, _jamba.NEW_PER_LAYER,
                      _jamba.APPENDED_TO)
    assert _ling.spec.Spec().bench == _ling.SPEC.bench
    _xing._differs_by(_ling.SPEC.bench, _xing.SPEC.bench, {_ling.CONFIG},
                      {_ling.CELL}, _ling.NEW_PER_LAYER, _ling.APPENDED_TO)
    assert _xing.spec.Spec().bench == _xing.SPEC.bench == _mimo.SPEC.bench
    _xing._differs_by(_xing.SPEC.bench, _kanana.SPEC.bench, {_xing.CONFIG},
                      {_xing.CELL}, _xing.NEW_PER_LAYER, _xing.APPENDED_TO)
    _xing._differs_by(_kanana.SPEC.bench, _host.SPEC.bench,
                      {'kanana-2-30b-a3b'}, {_kanana.CELL},
                      _kanana.NEW_PER_LAYER, _kanana.APPENDED_TO)
    # and with them the older pins hold
    assert _jamba.SPEC.bench['per_layer'][-1]['name'] \
        == 'ssm_decode_roofline'
    assert _ling.SPEC.bench['per_layer'][-1]['name'] \
        == 'kda_decode_roofline'
    assert _xing.SPEC.bench['per_layer'][-1]['name'] \
        == 'mhc_decode_roofline'
    assert _kanana.SPEC.bench['per_layer'][-1]['name'] \
        == 'mla_decode_roofline'
    assert [m['name'] for m in _host.SPEC.bench['per_layer']][-1] \
        == 'conv_decode_share'


# ---------------------------------------------------------------------------
# both toy rehearsals report all nine, on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def toy_root(tmp_path_factory):
    """The toy BENCHMARK.json plus the nine, listing the toy cells."""
    root = _toy.make_root(tmp_path_factory.mktemp('toy_setup'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        toy = json.load(f)
    cells = [w['name'] for w in toy['workloads']]
    toy['per_layer'] += [dict(m, workloads=cells)
                         for m in SPEC.bench['per_layer']
                         if m['name'] in NEW_PER_LAYER]
    with open(path, 'w') as f:
        json.dump(toy, f)
    return root


# a traced run's line has no `setup_s` (an end-to-end metric): the
# rehearsal has the harness log it where it is taken
_LOG_SETUP_S = '''
from benchmarks import log as _log, run as _run
_opens = _run.Run.window_opens
def _opens_and_logs(self, t_open):
    _opens(self, t_open)
    _log('setup_s_of_this_run', self.raw['setup_s'])
_run.Run.window_opens = _opens_and_logs
'''


@pytest.mark.parametrize('cell', ['toy-chat', 'toy-train'])
def test_toy_rehearsal_reports_all_nine(toy_root, cell):
    out, lines = _toy.run_toy(toy_root, cell, seed=48, trace=1,
                              patch=_LOG_SETUP_S)
    assert out['correct'] is True, lines[-12:]
    assert NEW_PER_LAYER <= set(out['metrics'])
    m = {k: v['value'] for k, v in out['metrics'].items()}
    for name in NEW_PER_LAYER:
        assert out['metrics'][name]['unit'] == (
            'count' if name == 'setup_programs_built' else 's')
        assert m[name] > 0, name
    split = (m['setup_program_trace_s'] + m['setup_program_lower_s']
             + m['setup_program_backend_s'])
    assert m['setup_program_build_s'] >= split
    # the builds lie inside the stepping; the stepping, the constructor
    # and the import inside set-up
    assert m['setup_program_build_s'] < m['setup_stepping_s']
    setup_s, = map(float, _toy.logged(lines, 'setup_s_of_this_run'))
    assert (m['setup_import_s'] + m['setup_engine_init_s']
            + m['setup_stepping_s']) <= setup_s
    # the harness's own jits (`fill`, the float32 reference after the
    # window) are no builds: as many as the program holds programs
    assert m['setup_programs_built'] == int(m['setup_programs_built']) \
        and 1 <= m['setup_programs_built'] <= 16


def test_untraced_toy_run_reports_none_of_them(toy_root):
    out, _ = _toy.run_toy(toy_root, 'toy-chat', seed=49)
    assert not NEW_PER_LAYER & set(out['metrics'])
    assert 'setup_s' in out['metrics']
