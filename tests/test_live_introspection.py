"""Live introspection (ISSUE 5): HTTP observability endpoint,
per-program XLA cost attribution (ProgramCatalog), always-on flight
recorder, and the satellite fixes — histogram non-finite guard,
event-drop visibility, dict-backed observability_summary, and strict
Prometheus exposition conformance.
"""
import json
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import debug, observability as obs, resilience
from paddle_tpu.serving import FAILED, InferenceEngine, SamplingParams
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import FatalError, RetryPolicy

from fault_injection import FaultInjector

_NO_SLEEP = RetryPolicy(base_delay=0.0, sleep=lambda d: None)


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.enable(True)
    obs.get_event_log().clear()
    yield
    obs.enable(was)


@pytest.fixture
def flight(tmp_path):
    """Point the always-on recorder at a test dir with no debounce and
    a FRESH dumps list (earlier suite tests may have auto-dumped)."""
    fr = obs.get_flight_recorder()
    saved = (fr.dump_dir, fr.min_interval_s, fr._last_dump_t, fr.dumps)
    fr.dump_dir = str(tmp_path)
    fr.min_interval_s = 0.0
    fr._last_dump_t = None
    fr.dumps = []
    yield fr
    fr.dump_dir, fr.min_interval_s, fr._last_dump_t, fr.dumps = saved


@pytest.fixture(scope='module')
def server():
    srv = obs.start_server(0)
    yield srv
    srv.stop()


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _get(srv, route, timeout=10):
    """(status, body) even for non-2xx responses."""
    try:
        r = urllib.request.urlopen(srv.url + route, timeout=timeout)
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# satellite: Histogram.observe() non-finite guard
# ---------------------------------------------------------------------------

class TestHistogramNonFinite:
    def test_nan_inf_dropped_not_summed(self):
        reg = obs.MetricsRegistry(process_index=0)
        h = reg.histogram('loss_seconds', buckets=(1.0, 10.0))
        h.observe(0.5)
        for bad in (float('nan'), float('inf'), float('-inf')):
            h.observe(bad)
        # sum/count/buckets untouched by the non-finite observations
        assert h.count == 1
        assert math.isfinite(h.sum) and h.sum == 0.5
        # ... and the drops are visible, labeled by metric
        assert reg.value('paddle_metrics_nonfinite_dropped_total',
                         metric='loss_seconds') == 3
        # the histogram still works after (regression: a NaN loss seen
        # before an FT rollback must not poison the family forever)
        h.observe(2.0)
        assert h.count == 2 and h.sum == 2.5

    def test_labeled_histogram_drops_counted_per_family(self):
        reg = obs.MetricsRegistry(process_index=0)
        fam = reg.histogram('span_seconds', '', ('name',))
        fam.labels(name='a').observe(float('nan'))
        assert reg.value('paddle_metrics_nonfinite_dropped_total',
                         metric='span_seconds') == 1


# ---------------------------------------------------------------------------
# satellite: EventLog drop visibility
# ---------------------------------------------------------------------------

class TestEventDropVisibility:
    def test_dropped_total_mirrors_default_log(self):
        log = obs.get_event_log()
        log.clear()
        for i in range(log.capacity + 7):
            log.append({'name': f'e{i}', 'ph': 'i', 'ts': float(i)})
        assert log.dropped == 7
        reg = obs.get_registry()
        reg.snapshot()   # runs the mirror collector
        assert reg.value('paddle_events_dropped_total') == 7
        text = obs.to_prometheus_text()
        assert re.search(r'^paddle_events_dropped_total\{[^}]*\} 7$',
                         text, re.M), 'drop counter missing from scrape'
        log.clear()


# ---------------------------------------------------------------------------
# satellite: dict-backed observability_summary
# ---------------------------------------------------------------------------

class TestSummaryDict:
    def test_dict_and_text_agree_on_headline_counters(self):
        d = debug.observability_summary(as_dict=True)
        text = debug.observability_summary()
        assert f'steps: {d["steps"]["total"]} total' in text
        assert f'jit: {d["jit"]["compiles"]} compiles' in text
        assert f'dispatch: {d["dispatch"]["calls"]} calls' in text
        assert f'{d["resilience"]["rollbacks"]} rollbacks' in text
        assert (f'serving: {d["serving"]["submitted"]} requests'
                in text)
        assert f'({d["events"]["dropped"]} dropped' in text

    def test_dict_is_json_able_and_structured(self):
        d = debug.observability_summary(as_dict=True)
        json.dumps(d)   # must serialize (the /summary?format=json body)
        for section in ('process_index', 'dispatch', 'jit', 'collectives',
                        'offload', 'steps', 'memory', 'resilience',
                        'checkpoints', 'serving', 'programs', 'spans',
                        'events'):
            assert section in d, section
        assert isinstance(d['programs'], list)


# ---------------------------------------------------------------------------
# satellite: strict Prometheus exposition conformance
# ---------------------------------------------------------------------------

_NAME = r'[a-zA-Z_:][a-zA-Z0-9_:]*'
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
_VALUE = r'(?:[-+]?[0-9]+(?:\.[0-9]+)?(?:[eE][-+]?[0-9]+)?|[+-]Inf|NaN)'
HELP_RE = re.compile(rf'^# HELP ({_NAME}) (?:[^\\\n]|\\\\|\\n)*$')
TYPE_RE = re.compile(rf'^# TYPE ({_NAME}) (counter|gauge|histogram)$')
SAMPLE_RE = re.compile(
    rf'^({_NAME})(?:\{{{_LABEL}(?:,{_LABEL})*\}})? {_VALUE}$')


def assert_prometheus_conformant(text: str):
    """Parse EVERY line with the strict grammar promtool/Grafana apply;
    check HELP/TYPE ordering and histogram invariants."""
    lines = text.splitlines()
    assert lines and text.endswith('\n')
    current = None          # metric whose block we are inside
    types = {}
    seen_samples = {}       # family -> list of (labels_str, value_str)
    for ln in lines:
        h = HELP_RE.match(ln)
        t = TYPE_RE.match(ln)
        s = SAMPLE_RE.match(ln)
        assert h or t or s, f'unparseable exposition line: {ln!r}'
        if h:
            current = h.group(1)
        elif t:
            assert t.group(1) == current, \
                f'TYPE for {t.group(1)} not directly after its HELP'
            types[t.group(1)] = t.group(2)
        else:
            name = s.group(1)
            base = re.sub(r'_(bucket|sum|count)$', '', name)
            fam = name if name in types else base
            assert fam in types, f'sample {name} before TYPE'
            assert fam == current or base == current, \
                f'sample {name} outside its metric block'
            seen_samples.setdefault(fam, []).append(ln)
    # histogram invariants: +Inf bucket present, _count == +Inf count
    for fam, typ in types.items():
        if typ != 'histogram':
            continue
        rows = seen_samples.get(fam, [])
        infs = {}
        counts = {}
        for ln in rows:
            name, labels, value = re.match(
                rf'^({_NAME})(\{{.*\}})? ({_VALUE})$', ln).groups()
            labels = labels or ''
            if name == fam + '_bucket' and 'le="+Inf"' in labels:
                key = re.sub(r'le="\+Inf",?', '', labels)
                infs[key] = value
            elif name == fam + '_count':
                counts[labels.rstrip('}') + ('}' if labels else '')] = value
        assert infs, f'{fam} has no +Inf bucket'
        for key, v in infs.items():
            key = re.sub(r',\}$', '}', key)
            assert counts.get(key) == v, \
                f'{fam}_count != +Inf bucket for {key}: ' \
                f'{counts} vs {infs}'


class TestPrometheusConformance:
    def _nasty(self):
        reg = obs.MetricsRegistry(process_index=0)
        reg.counter('req_total',
                    'help with \\ backslash and\nnewline and "quotes"',
                    ('path',)).labels(
            path='a"b\\c\nd').inc(3)
        reg.gauge('temp_ratio').set(float('inf'))
        reg.gauge('empty_help')
        h = reg.histogram('lat_seconds', 'latency', ('op',),
                          buckets=(0.1, 1.0))
        h.labels(op='x').observe(0.05)
        h.labels(op='x').observe(0.5)
        h.labels(op='x').observe(5.0)
        reg.histogram('unlabeled_seconds', buckets=(1.0,)).observe(2.0)
        return reg

    def test_nasty_labels_and_histograms_conform(self):
        assert_prometheus_conformant(obs.to_prometheus_text(self._nasty()))

    def test_escaping_roundtrip(self):
        text = obs.to_prometheus_text(self._nasty())
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith('req_total{')]
        assert 'path="a\\"b\\\\c\\nd"' in line
        # HELP escapes only backslash + newline; quotes stay literal
        (help_line,) = [ln for ln in text.splitlines()
                        if ln.startswith('# HELP req_total')]
        assert '"quotes"' in help_line
        assert '\\\\ backslash' in help_line

    def test_nonfinite_gauge_formats_as_inf(self):
        text = obs.to_prometheus_text(self._nasty())
        assert re.search(r'^temp_ratio\{[^}]*\} \+Inf$', text, re.M)

    def test_live_registry_conforms(self):
        _ = paddle.ones([4]) + 1.0   # populate some real metrics
        with obs.span('conformance_probe'):
            pass
        assert_prometheus_conformant(obs.to_prometheus_text())

    def test_windowed_quantile_exposition_conforms(self):
        """The `{name}_wq` gauge family (windowed p50/p95/p99) rides a
        SEPARATE name so histogram families stay bucket/sum/count-only;
        the strict parser must accept it and the labels must carry a
        quantile per configured point."""
        text = obs.to_prometheus_text(self._nasty())
        assert_prometheus_conformant(text)
        assert '# TYPE lat_seconds_wq gauge' in text
        wq = [ln for ln in text.splitlines()
              if ln.startswith('lat_seconds_wq{')]
        # one sample per (child x quantile point)
        assert len(wq) == len(obs.QUANTILES)
        for q in obs.QUANTILES:
            assert any(f'quantile="{q:g}"' in ln for ln in wq), (q, wq)
        # the nasty label value survives inside the _wq family too
        assert all('op="x"' in ln for ln in wq)
        # no quantile lines leak into the histogram family itself
        assert not any('quantile=' in ln for ln in text.splitlines()
                       if ln.startswith('lat_seconds_bucket'))


# ---------------------------------------------------------------------------
# tentpole: HTTP observability endpoint
# ---------------------------------------------------------------------------

class TestServerEndpoints:
    def test_metrics_scrape_conforms(self, server):
        with obs.span('scrape_probe'):
            pass
        status, body = _get(server, '/metrics')
        assert status == 200
        assert_prometheus_conformant(body)

    def test_healthz_ok(self, server):
        status, body = _get(server, '/healthz')
        assert status == 200
        h = json.loads(body)
        assert h['status'] == 'ok'
        assert h['pid'] == os.getpid()
        assert 'seconds_since_progress' in h

    def test_summary_text_and_json(self, server):
        status, body = _get(server, '/summary')
        assert status == 200
        assert 'observability summary' in body
        status, body = _get(server, '/summary?format=json')
        assert status == 200
        d = json.loads(body)
        assert 'steps' in d and 'programs' in d

    def test_events_jsonl_tail(self, server):
        for i in range(10):
            obs.emit('server_probe', i=i)
        status, body = _get(server, '/events?n=5')
        assert status == 200
        lines = [json.loads(ln) for ln in body.splitlines()]
        assert 0 < len(lines) <= 5
        assert all('name' in e for e in lines)

    def test_trace_chrome_json(self, server):
        with obs.span('traced_region'):
            pass
        status, body = _get(server, '/trace')
        assert status == 200
        doc = json.loads(body)
        assert any(e['name'] == 'traced_region'
                   for e in doc['traceEvents'])

    def test_programs_report(self, server):
        status, body = _get(server, '/programs')
        assert status == 200
        assert 'program catalog' in body
        status, body = _get(server, '/programs?format=json')
        assert json.loads(body)['programs'] is not None

    def test_unknown_route_404(self, server):
        status, _ = _get(server, '/nope')
        assert status == 404

    def test_concurrent_scrape_stays_parseable(self, server):
        """/metrics served from the daemon thread while this thread
        mutates the registry: every scrape body must parse."""
        stop = threading.Event()
        errors = []

        def writer():
            reg = obs.get_registry()
            i = 0
            while not stop.is_set():
                reg.counter('concurrency_probe_total', 'x',
                            ('lane',)).labels(lane=str(i % 5)).inc()
                reg.histogram('concurrency_probe_seconds').observe(
                    0.001 * (i % 7))
                i += 1

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            for _ in range(20):
                status, body = _get(server, '/metrics')
                assert status == 200
                try:
                    assert_prometheus_conformant(body)
                except AssertionError as e:
                    errors.append(str(e))
        finally:
            stop.set()
            t.join(timeout=5)
        assert not errors, errors[:3]


class TestHealthzHang:
    def test_healthz_non_200_during_hang_then_recovers(self, server,
                                                       flight):
        wd = resilience.StepWatchdog(deadline_s=0.05, poll_interval=0.01)
        wd.start()
        wd.arm()
        try:
            deadline = time.time() + 5
            while wd.fired == 0 and time.time() < deadline:
                time.sleep(0.01)
            assert wd.fired >= 1, 'watchdog never fired'
            status, body = _get(server, '/healthz')
            assert status == 503
            h = json.loads(body)
            assert h['status'] == 'hang_suspected'
            assert h['hangs'] and 'deadline_s' in h['hangs'][0]
            # /metrics keeps serving, parseable, while hung
            status, metrics = _get(server, '/metrics')
            assert status == 200
            assert_prometheus_conformant(metrics)
        finally:
            wd.disarm()
            wd.stop()
        status, body = _get(server, '/healthz')
        assert status == 200
        assert json.loads(body)['status'] == 'ok'


# ---------------------------------------------------------------------------
# tentpole: flight recorder
# ---------------------------------------------------------------------------

def _bundle_files(path):
    return set(os.listdir(path))


class TestFlightRecorder:
    def test_injected_hang_produces_postmortem_bundle(self, flight):
        with obs.span('train.step', step=41):
            pass
        wd = resilience.StepWatchdog(deadline_s=0.03, poll_interval=0.01)
        wd.start()
        wd.arm()
        try:
            deadline = time.time() + 5
            while not flight.dumps and time.time() < deadline:
                time.sleep(0.01)
        finally:
            wd.disarm()
            wd.stop()
        assert flight.dumps, 'hang did not trigger a flight dump'
        path = flight.dumps[-1]
        files = _bundle_files(path)
        assert {'flight.json', 'events.jsonl', 'trace.json',
                'metrics.json', 'programs.json',
                'summary.txt'} <= files
        meta = json.load(open(os.path.join(path, 'flight.json')))
        assert meta['reason'] == 'hang_suspected'
        assert meta['trigger']['name'] == 'hang_suspected'
        events = [json.loads(ln) for ln in
                  open(os.path.join(path, 'events.jsonl'))]
        names = {e['name'] for e in events}
        assert 'hang_suspected' in names      # the triggering event
        assert 'train.step' in names          # the surrounding span
        # the program report rides along
        assert 'programs' in json.load(
            open(os.path.join(path, 'programs.json')))
        assert 'program catalog' in open(
            os.path.join(path, 'summary.txt')).read()

    def test_injected_loss_spike_produces_bundle(self, flight):
        """A fault-injected loss spike inside FaultTolerantStep lands a
        bundle via the LossSpikeDetector's loss_spike event."""
        inj = FaultInjector(nth=8, mutate=lambda loss: 1e6)

        def plain_step():
            with obs.span('ft.step'):
                return 1.0 + np.random.RandomState(0).rand() * 0.01

        ft = resilience.FaultTolerantStep(
            inj.wrap(plain_step), snapshot_fn=lambda: None,
            restore_fn=lambda s: None, spike_min_steps=3,
            spike_sigma=3.0, skip_budget=5)
        for _ in range(10):
            ft()
        assert inj.fired == 1
        assert ft.rollbacks == 1
        assert flight.dumps, 'loss spike did not trigger a flight dump'
        path = flight.dumps[-1]
        meta = json.load(open(os.path.join(path, 'flight.json')))
        assert meta['reason'] in ('loss_spike', 'bad_step')
        events = [json.loads(ln) for ln in
                  open(os.path.join(path, 'events.jsonl'))]
        names = {e['name'] for e in events}
        assert 'loss_spike' in names
        assert 'ft.step' in names
        assert {'programs.json', 'summary.txt'} <= _bundle_files(path)

    def test_skip_budget_exhausted_dumps_before_raise(self, flight):
        ft = resilience.FaultTolerantStep(
            lambda: float('nan'), snapshot_fn=lambda: None,
            restore_fn=lambda s: None, skip_budget=0)
        with pytest.raises(resilience.SkipBudgetExhausted):
            ft()
        reasons = [json.load(open(os.path.join(p, 'flight.json')))['reason']
                   for p in flight.dumps]
        assert 'skip_budget_exhausted' in reasons

    def test_serving_request_failure_dumps(self, flight, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, retry_policy=_NO_SLEEP)
        inj = FaultInjector(nth=1, exc=FatalError('injected device loss'))
        with inj.patch(engine_mod, '_to_device'):
            h = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4,
                                                     eos_token_id=-1))
            eng.run()
        assert h.status == FAILED
        reasons = [json.load(open(os.path.join(p, 'flight.json')))['reason']
                   for p in flight.dumps]
        assert 'serving_request_failed' in reasons

    def test_auto_dumps_are_debounced(self, flight):
        flight.min_interval_s = 60.0
        flight._last_dump_t = None
        obs.emit('loss_spike', step=1, loss=1e9)
        obs.emit('loss_spike', step=2, loss=1e9)
        obs.emit('loss_spike', step=3, loss=1e9)
        assert len(flight.dumps) == 1, 'anomaly storm must not dump-storm'

    def test_manual_dump_records_ring_samples(self, flight):
        tel = obs.StepTelemetry(window=4)
        for i in range(5):
            tel.step(loss=2.0 - 0.1 * i, tokens=64)
        path = flight.dump(reason='manual_test')
        meta = json.load(open(os.path.join(path, 'flight.json')))
        assert meta['reason'] == 'manual_test'
        assert len(meta['steps']) >= 5
        assert any(s.get('loss') is not None for s in meta['steps'])
        assert meta['memory'], 'no device-memory samples in the ring'
        assert 'paddle_steps_total' in meta['counters']


# ---------------------------------------------------------------------------
# tentpole: ProgramCatalog cost attribution
# ---------------------------------------------------------------------------

class TestProgramCatalog:
    def _top(self, name):
        rows = obs.program_catalog().top_programs(n=100)
        match = [r for r in rows if r['name'] == name]
        assert match, f'{name} not in catalog: {[r["name"] for r in rows]}'
        return match[0]

    def test_train_gpt_example_attributes_train_step(self):
        """Acceptance: the GPT example's train step shows up with
        nonzero FLOPs/bytes and its invocation count — and producing
        the report itself compiles NOTHING."""
        import runpy
        inv_before = self._safe_invocations('train_step')
        mod = runpy.run_path(os.path.join(
            os.path.dirname(__file__), '..', 'examples', 'train_gpt.py'))
        mod['main'](steps=4)
        reg = obs.get_registry()
        compiles_before = reg.value('paddle_jit_compiles_total')
        row = self._top('train_step')
        report = obs.program_catalog().report()
        debug.observability_summary()          # programs section renders
        assert row['invocations'] >= inv_before + 4
        assert row['flops'] > 0
        assert row['bytes_accessed'] > 0
        assert row['peak_memory_bytes'] > 0
        assert row['compile_count'] >= 1
        assert row['host_seconds'] > 0
        assert 'train_step' in report
        # zero new compiles attributable to the catalog's reporting
        assert reg.value('paddle_jit_compiles_total') == compiles_before

    def _safe_invocations(self, name):
        rows = obs.program_catalog().top_programs(n=200)
        for r in rows:
            if r['name'] == name:
                return r['invocations']
        return 0

    def test_serving_attributes_decode_and_prefill_buckets(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2)
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10]]   # 2 buckets
        eng.generate_many(prompts, [SamplingParams(max_new_tokens=4,
                                                   eos_token_id=-1)] * 2)
        reg = obs.get_registry()
        compiles_before = reg.value('paddle_jit_compiles_total')
        # ten rows of 64 at most: every round ran the half-length
        # program (the whole one is built beside it and never called)
        decode = self._top('serving.decode_block_r32')
        assert decode['invocations'] >= 2
        assert decode['flops'] > 0
        assert decode['bytes_accessed'] > 0
        buckets = {b for b in (eng.pool.bucket_for(len(p))
                               for p in prompts)}
        for b in buckets:
            row = self._top(f'serving.prefill_{b}')
            assert row['invocations'] >= 1
            assert row['flops'] > 0
        # reporting costs zero compiles (the existing zero-recompile
        # serving guards stay meaningful with the catalog enrolled)
        obs.program_catalog().report()
        assert reg.value('paddle_jit_compiles_total') == compiles_before

    def test_to_static_programs_enrolled(self):
        @paddle.jit.to_static
        def affine(x):
            return x @ x + 1.0
        x = paddle.ones([8, 8])
        affine(x)
        affine(x)
        row = self._top('to_static:affine')
        assert row['invocations'] >= 2
        assert row['flops'] > 0

    def test_dispatch_cache_entries_mirrored(self):
        debug.clear_dispatch_cache()
        debug.reset_dispatch_stats()
        x = paddle.ones([16, 16])
        for _ in range(4):
            x = x * 1.0 + 0.5
        rows = obs.program_catalog().top_programs(n=300,
                                                  kind='dispatch')
        eager = {r['name']: r for r in rows}
        hot = [r for r in eager.values() if r['invocations'] > 0]
        assert hot, f'no eager programs mirrored: {list(eager)[:5]}'
        # the cold miss path recorded trace+compile wall time
        assert any(r['compile_seconds'] > 0 for r in eager.values())

    def test_program_metrics_on_scrape(self):
        _ = paddle.ones([4]) + 1.0
        reg = obs.get_registry()
        reg.snapshot()
        fam = reg.get('paddle_program_invocations_total')
        assert fam is not None and fam._children
        text = obs.to_prometheus_text()
        assert 'paddle_program_invocations_total' in text
        assert 'paddle_program_flops' in text
        assert_prometheus_conformant(text)

    def test_wrapped_jit_falls_back_gracefully(self):
        """A target without an AOT path still serves calls and counts."""
        class NoAot:
            def __call__(self, x):
                return x + 1
        from paddle_tpu import programs
        wrapped = programs.get_store().wrap_jit(
            NoAot(), name='no_aot_prog')
        assert wrapped(np.float32(1.0)) == 2.0
        assert wrapped(np.float32(2.0)) == 3.0
        row = self._top('no_aot_prog')
        assert row['invocations'] == 2
        assert row['note'] == 'aot_unavailable'


# ---------------------------------------------------------------------------
# tier-1 guard: scrape-under-load overhead < 3%
# ---------------------------------------------------------------------------

def test_scrape_overhead_under_3pct():
    """A background client scraping /metrics at 4 Hz during the eager
    MLP loop stays within 3% (same best-of-N + retry protocol as the
    instrumentation guard — the true cost is ~0)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'bench', os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    res = None
    for _ in range(3):
        res = bench.scrape_overhead_ab(steps=30, trials=3)
        if res['overhead_pct'] < 3.0:
            break
    assert res['overhead_pct'] < 3.0, res
    assert res['scrapes'] > 0
    assert res['scrape_failures'] == 0, res
