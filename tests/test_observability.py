"""Unified observability layer (paddle_tpu/observability/): registry
semantics, span/EventLog tracing, exporters, instrumented runtime
(dispatch/jit/collectives/offload/steps), profiler fixes, and the
zero-overhead + <3% obs-overhead guards."""
import json
import math
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import debug, observability as obs

P = paddle.profiler


@pytest.fixture(autouse=True)
def _obs_on():
    """Each test gets observability enabled and a clean log; the shared
    registry's values are reset (families survive — instrument sites
    hold child references)."""
    was = obs.enabled()
    obs.enable(True)
    obs.get_event_log().clear()
    yield
    obs.enable(was)


def fresh():
    return obs.MetricsRegistry(process_index=0)


class TestCounter:
    def test_inc_and_default_amount(self):
        c = fresh().counter('c_total', 'help')
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_negative_inc_rejected(self):
        c = fresh().counter('c_total')
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labels_route_to_distinct_children(self):
        fam = fresh().counter('c_total', '', ('op', 'axis'))
        fam.labels(op='a', axis='dp').inc(3)
        fam.labels(op='b', axis='dp').inc()
        assert fam.labels(op='a', axis='dp').value == 3
        assert fam.labels(op='b', axis='dp').value == 1
        # same label values -> the same child object
        assert fam.labels(op='a', axis='dp') is fam.labels(op='a',
                                                           axis='dp')

    def test_label_names_enforced(self):
        fam = fresh().counter('c_total', '', ('op',))
        with pytest.raises(ValueError):
            fam.labels(wrong='x')
        with pytest.raises(ValueError):
            fam.inc()   # labeled family has no sole child

    def test_type_conflict_rejected(self):
        reg = fresh()
        reg.counter('m')
        with pytest.raises(ValueError):
            reg.gauge('m')
        with pytest.raises(ValueError):
            reg.counter('m', labelnames=('x',))
        # same signature is create-or-get
        assert reg.counter('m') is reg.counter('m')


class TestGauge:
    def test_set_inc_dec(self):
        g = fresh().gauge('g')
        g.set(10)
        g.inc(5)
        g.dec(3)
        assert g.value == 12

    def test_set_to_max_is_a_watermark(self):
        g = fresh().gauge('g')
        g.set_to_max(5)
        g.set_to_max(3)
        assert g.value == 5
        g.set_to_max(9)
        assert g.value == 9


class TestHistogram:
    def test_buckets_sum_count(self):
        h = fresh().histogram('h', buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5
        assert math.isclose(h.sum, 56.05)
        # non-cumulative internal counts: one per bucket + overflow
        assert h._sole().bucket_counts == [1, 2, 1, 1]

    def test_snapshot_buckets_are_cumulative(self):
        reg = fresh()
        h = reg.histogram('h', buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(1.5)
        snap = reg.snapshot()
        (m,) = [m for m in snap['metrics'] if m['name'] == 'h']
        assert m['samples'][0]['buckets'] == {'1.0': 1, '2.0': 2,
                                              '+Inf': 2}


class TestRegistry:
    def test_value_and_reset(self):
        reg = fresh()
        reg.counter('a').inc(4)
        reg.gauge('b', '', ('k',)).labels(k='x').set(7)
        assert reg.value('a') == 4
        assert reg.value('b', k='x') == 7
        assert reg.value('missing', default=-1) == -1
        reg.reset()
        assert reg.value('a') == 0
        assert reg.value('b', k='x') == 0

    def test_collector_runs_at_snapshot_only(self):
        reg = fresh()
        calls = []

        @reg.register_collector
        def sync(r):
            calls.append(1)
            r.gauge('from_collector').set(42)

        reg.counter('x').inc()
        assert not calls
        snap = reg.snapshot()
        assert calls == [1]
        assert any(m['name'] == 'from_collector'
                   for m in snap['metrics'])

    def test_snapshot_carries_process_index(self):
        assert fresh().snapshot()['process_index'] == 0


class TestSpansAndEventLog:
    def test_span_nesting_records_depth_and_order(self):
        log = obs.get_event_log()
        with obs.span('outer'):
            time.sleep(0.002)
            with obs.span('inner'):
                time.sleep(0.001)
        evs = {e['name']: e for e in log.events()}
        assert evs['inner']['depth'] == 2
        assert evs['outer']['depth'] == 1
        # real timeline: inner begins after outer and ends before it
        assert evs['inner']['ts'] >= evs['outer']['ts']
        assert (evs['inner']['ts'] + evs['inner']['dur']
                <= evs['outer']['ts'] + evs['outer']['dur'] + 1e-4)
        assert evs['outer']['dur'] >= 0.002

    def test_span_feeds_histogram(self):
        with obs.span('timed_region'):
            pass
        fam = obs.get_registry().get('paddle_span_seconds')
        child = fam.labels(name='timed_region')
        assert child.count >= 1

    def test_event_log_bounded_and_counts_drops(self):
        log = obs.EventLog(capacity=4)
        for i in range(10):
            log.append({'name': f'e{i}', 'ph': 'i', 'ts': float(i)})
        assert len(log) == 4
        assert log.dropped == 6
        assert [e['name'] for e in log.events()] == ['e6', 'e7', 'e8',
                                                     'e9']

    def test_emit_instant_event(self):
        log = obs.get_event_log()
        obs.emit('loss_spike', step=3, loss=99.0)
        (ev,) = [e for e in log.events() if e['name'] == 'loss_spike']
        assert ev['ph'] == 'i'
        assert ev['attrs'] == {'step': 3, 'loss': 99.0}

    def test_disabled_records_nothing(self):
        obs.enable(False)
        log = obs.get_event_log()
        with obs.span('ghost'):
            pass
        obs.emit('ghost_event')
        assert not [e for e in log.events()
                    if e['name'].startswith('ghost')]


class TestExporters:
    def _populated(self):
        reg = fresh()
        reg.counter('req_total', 'requests', ('op',)).labels(
            op='matmul').inc(5)
        reg.gauge('mem_bytes').set(1024)
        reg.histogram('lat_seconds', buckets=(0.1, 1.0)).observe(0.5)
        return reg

    def test_prometheus_text(self):
        text = obs.to_prometheus_text(self._populated())
        assert '# TYPE req_total counter' in text
        assert 'req_total{op="matmul",process="0"} 5' in text
        assert 'mem_bytes{process="0"} 1024' in text
        assert 'lat_seconds_bucket{le="1.0",process="0"} 1' in text
        assert 'lat_seconds_count{process="0"} 1' in text

    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / 'm.jsonl')
        obs.to_jsonl(self._populated(), path)
        recs = obs.read_jsonl(path)
        by_name = {r['name']: r for r in recs}
        assert by_name['req_total']['value'] == 5
        assert by_name['req_total']['labels'] == {'op': 'matmul'}
        assert by_name['mem_bytes']['value'] == 1024
        assert by_name['lat_seconds']['count'] == 1
        assert all(r['process'] == 0 for r in recs)

    def test_chrome_trace_true_timeline(self, tmp_path):
        log = obs.EventLog()
        with obs.Span('a', _log=log):
            time.sleep(0.002)
        time.sleep(0.002)   # a real gap the export must preserve
        with obs.Span('b', _log=log):
            time.sleep(0.001)
        path = str(tmp_path / 'trace.json')
        doc = obs.to_chrome_trace(log, path)
        a, b = [e for e in doc['traceEvents'] if e['ph'] == 'X']
        assert (a['name'], b['name']) == ('a', 'b')
        # track labels: perfetto names the process/thread rows from 'M'
        # metadata, not from pids — the export must emit them
        meta = [e for e in doc['traceEvents'] if e['ph'] == 'M']
        assert any(e['name'] == 'process_name' for e in meta)
        assert any(e['name'] == 'thread_name' and e['pid'] == a['pid']
                   and e['tid'] == a['tid'] for e in meta)
        # true timestamps: b begins AFTER a's end plus the sleep gap,
        # not back-to-back at a fabricated running sum
        assert b['ts'] >= a['ts'] + a['dur'] + 1500
        assert json.load(open(path))['traceEvents'] == doc['traceEvents']


class TestMergeSnapshots:
    def _snap(self, proc, n):
        reg = obs.MetricsRegistry(process_index=proc)
        reg.counter('calls_total').inc(n)
        reg.gauge('watermark').set(n * 10)
        return reg.snapshot()

    def test_distinct_processes_sum_counters_max_gauges(self):
        merged = obs.merge_snapshots([self._snap(0, 2), self._snap(1, 3)])
        assert merged['processes'] == [0, 1]
        by_name = {m['name']: m for m in merged['metrics']}
        assert by_name['calls_total']['samples'][0]['value'] == 5
        assert by_name['watermark']['samples'][0]['value'] == 30

    def test_duplicate_process_deduped(self):
        # all_gather_object on a single controller returns world-size
        # copies of the one local snapshot; merging must not multiply
        snap = self._snap(0, 2)
        merged = obs.merge_snapshots([snap] * 8)
        by_name = {m['name']: m for m in merged['metrics']}
        assert by_name['calls_total']['samples'][0]['value'] == 2


class TestProfilerFixes:
    def test_chrome_tracing_real_timestamps(self, tmp_path):
        handler = P.export_chrome_tracing(str(tmp_path))
        outs = []
        prof = P.Profiler(scheduler=(0, 1),
                          on_trace_ready=lambda p: outs.append(handler(p)))
        prof.start()
        with P.RecordEvent('first'):
            time.sleep(0.002)
        time.sleep(0.002)
        with P.RecordEvent('second'):
            time.sleep(0.001)
        prof.step()
        prof.stop()
        (path,) = outs
        evs = {e['name']: e for e in P.load_profiler_result(
            path)['traceEvents']}
        # real begin/duration per event: the gap between regions shows
        assert evs['second']['ts'] >= (evs['first']['ts']
                                       + evs['first']['dur'] + 1500)
        assert evs['first']['dur'] >= 1500

    def test_per_event_not_aggregated(self, tmp_path):
        handler = P.export_chrome_tracing(str(tmp_path))
        outs = []
        prof = P.Profiler(scheduler=(0, 1),
                          on_trace_ready=lambda p: outs.append(handler(p)))
        prof.start()
        for _ in range(3):
            with P.RecordEvent('tick'):
                pass
        prof.step()
        prof.stop()
        evs = [e for e in P.load_profiler_result(outs[0])['traceEvents']
               if e['name'] == 'tick']
        assert len(evs) == 3   # one event per occurrence
        assert [e['args']['calls'] for e in evs] == [1, 2, 3]

    def test_stop_flushes_open_window(self):
        fired = []
        prof = P.Profiler(scheduler=(2, 100),
                          on_trace_ready=lambda p: fired.append(1))
        prof.start()
        for _ in range(5):   # window opens at step 2, never closes
            prof.step()
        assert not fired
        prof.stop()
        assert len(fired) == 1
        prof.stop()          # idempotent: no double fire
        assert len(fired) == 1

    def test_stop_without_open_window_does_not_fire(self):
        fired = []
        prof = P.Profiler(scheduler=(1, 2),
                          on_trace_ready=lambda p: fired.append(1))
        prof.start()
        for _ in range(10):   # window [1, 2) closed by step()
            prof.step()
        prof.stop()
        assert len(fired) == 1


class TestLossSpikeDetector:
    def test_spike_excluded_from_baseline(self):
        d = debug.LossSpikeDetector(window=10, threshold_sigma=3.0,
                                    min_steps=3)
        for v in [1.0, 1.01, 0.99, 1.0, 1.02]:
            assert not d.update(v)
        assert d.update(50.0)
        # the spike must NOT have contaminated the trailing window: a
        # second identical level shift is still flagged
        assert 50.0 not in d.window
        assert d.update(50.0)
        assert len(d.spikes) == 2

    def test_nonfinite_excluded_and_flagged(self):
        d = debug.LossSpikeDetector(window=5, min_steps=2)
        d.update(1.0)
        d.update(1.0)
        assert d.update(float('nan'))
        assert all(math.isfinite(v) for v in d.window)

    def test_emits_loss_spike_event(self):
        log = obs.get_event_log()
        d = debug.LossSpikeDetector(window=10, threshold_sigma=3.0,
                                    min_steps=2)
        for v in [1.0, 1.0, 1.0]:
            d.update(v)
        d.update(100.0)
        spikes = [e for e in log.events() if e['name'] == 'loss_spike']
        assert len(spikes) == 1
        assert spikes[0]['attrs']['loss'] == 100.0


class TestStepTelemetry:
    def test_rates_and_watermark(self):
        keep = paddle.ones([64, 64])   # live device bytes for the
        tel = obs.StepTelemetry(window=4)  # CPU live-array fallback
        for i in range(5):
            tel.step(loss=2.0 - i * 0.1, tokens=128)
            time.sleep(0.001)
        s = tel.summary()
        assert s['steps'] >= 5
        assert s['tokens'] >= 5 * 128
        assert s['steps_per_sec'] > 0
        assert s['tokens_per_sec'] > 0
        assert abs(s['loss_last'] - 1.6) < 1e-6
        assert s['memory_watermark_bytes'] > 0

    def test_disabled_is_noop(self):
        tel = obs.StepTelemetry()
        obs.get_registry().reset()
        obs.enable(False)
        tel.step(loss=1.0, tokens=10)
        assert obs.get_registry().value('paddle_steps_total') == 0


class TestRuntimeInstrumentation:
    def test_dispatch_collector_mirrors_stats(self):
        debug.reset_dispatch_stats()
        x = paddle.ones([4, 4])
        for _ in range(3):
            x = x + 1.0
        reg = obs.get_registry()
        reg.snapshot()   # runs the dispatch collector
        s = debug.dispatch_stats()
        assert reg.value('paddle_dispatch_calls_total',
                         result='hits') == s['hits']
        assert reg.value('paddle_dispatch_calls_total',
                         result='misses') == s['misses']
        assert reg.value('paddle_dispatch_cache_entries') \
            == s['cache_size']

    def test_jit_compile_metrics_recorded(self):
        import jax
        import jax.numpy as jnp
        reg = obs.get_registry()
        before = reg.value('paddle_jit_compiles_total')

        @jax.jit
        def f(v):
            return v * 3.0 + 1.0
        f(jnp.ones((3,)))
        assert reg.value('paddle_jit_compiles_total') >= before + 1
        assert reg.value('paddle_jit_compile_seconds_total') > 0

    @pytest.mark.parametrize('case', ['flat', 'nested', 'outside'])
    def test_a_builds_phases_from_jaxs_four_durations(self, case,
                                                      monkeypatch):
        """`_on_jax_duration` books trace, lowering, compile-or-fetch
        and the cache's retrieval to the build that is open — an
        interval that lies inside a later one once, with the outer — and
        to nothing new when none is (ISSUE 48). Made-up events on a
        made-up clock."""
        from paddle_tpu.observability import cost, telemetry
        now = [100.0]
        import types
        monkeypatch.setattr(telemetry, 'time', types.SimpleNamespace(
            perf_counter=lambda: now[0]))   # this module's clock alone

        def fire(event, at, secs):
            now[0] = at                 # jax fires a region as it ends
            telemetry._on_jax_duration(f'/jax/core/compile/{event}', secs,
                                       fun_name='f')

        reg = obs.get_registry()
        rec = cost.ProgramRecord('made.up', 'serving')
        if case == 'outside':
            telemetry.note_build_seconds('trace', 0.0)      # declared
            fam = reg.get('paddle_program_build_seconds_total')
            before = dict((k, c.value) for k, c in fam.children())
            traced = reg.value('paddle_jit_trace_seconds_total')
            fire('jaxpr_trace_duration', 101.0, 1.0)
            fire('jaxpr_to_mlir_module_duration', 102.0, 1.0)
            fire('an_event_of_no_phase', 103.0, 1.0)
            assert dict((k, c.value) for k, c in fam.children()) == before
            assert reg.value('paddle_jit_trace_seconds_total') \
                == traced + 1.0
            return
        with telemetry.ProgramBuild(rec) as build:
            assert build.live
            if case == 'nested':
                # two layers traced inside the outer trace, and an eager
                # op compiled while it ran: 0.5 + 0.25 + 0.125 s inside
                fire('jaxpr_trace_duration', 100.75, 0.5)
                fire('backend_compile_duration', 101.0, 0.125)
                fire('jaxpr_trace_duration', 101.5, 0.25)
            fire('jaxpr_trace_duration', 102.0, 1.75)
            fire('jaxpr_to_mlir_module_duration', 103.0, 1.0)
            now[0] = 104.5
            telemetry._on_jax_duration(
                '/jax/compilation_cache/cache_retrieval_time_sec', 1.25)
            fire('backend_compile_duration', 105.0, 1.5)
            now[0] = 106.0
        assert build.seconds == {
            'wall': 6.0, 'trace': 1.75, 'lower': 1.0, 'backend': 1.5,
            'cache_retrieval': 1.25, 'first_call': 0.0}
        assert (rec.build_seconds, rec.trace_seconds, rec.lower_seconds,
                rec.backend_seconds, rec.cache_retrieval_seconds) \
            == (6.0, 1.75, 1.0, 1.5, 1.25)
        assert rec.compile_seconds == 6.0 and not telemetry._open_builds
        # a build opened inside another on the same thread is the outer
        # one's: it books nothing of its own
        with telemetry.ProgramBuild(rec) as outer:
            with telemetry.ProgramBuild(
                    cost.ProgramRecord('inner', 'jit')) as inner:
                fire('jaxpr_trace_duration', 107.0, 0.5)
            assert not inner.live and inner.seconds['trace'] == 0.0
        assert outer.seconds['trace'] == 0.5

    def test_observability_summary_sections(self):
        text = debug.observability_summary()
        for field in ('dispatch:', 'hit_rate', 'jit:', 'compiles',
                      'collectives:', 'offload:', 'H2D', 'steps:',
                      'tokens/s', 'memory: watermark', 'host spans:'):
            assert field in text, field


class TestZeroOverheadWhenDisabled:
    def test_no_registry_calls_on_eager_hot_path(self, monkeypatch):
        """Metrics disabled ⇒ the per-op eager path performs NO registry
        mutations (dispatch telemetry flows through the scrape-time
        collector instead)."""
        calls = []
        for cls, meths in ((obs.Counter, ('inc',)),
                           (obs.Gauge, ('set', 'inc', 'set_to_max')),
                           (obs.Histogram, ('observe',))):
            for meth in meths:
                orig = getattr(cls, meth)

                def spy(self, *a, _o=orig, _m=meth, **kw):
                    calls.append(_m)
                    return _o(self, *a, **kw)
                monkeypatch.setattr(cls, meth, spy)
        obs.enable(False)
        x = paddle.ones([8, 8])
        y = paddle.ones([8, 8])
        y.stop_gradient = False
        loss = (x @ y).sum()
        loss.backward()
        assert calls == []

    def test_enabled_hot_path_also_collector_based(self, monkeypatch):
        """Even ENABLED, plain eager ops write nothing per-op — dispatch
        metrics are mirrored at snapshot time only."""
        _ = paddle.ones([4]) + 1.0   # warm: a first call may jit-compile
        calls = []
        orig = obs.Counter.inc
        monkeypatch.setattr(
            obs.Counter, 'inc',
            lambda self, *a, **kw: (calls.append(1), orig(self, *a, **kw))[1])
        _ = paddle.ones([4]) + 1.0   # cached dispatch: zero registry writes
        assert calls == []


def test_obs_overhead_under_3pct():
    """Tier-1 guard: instrumentation on vs off on the eager MLP loop
    stays within 3%. Single short runs swing ±7% on a loaded CPU box,
    so the guard takes best-of-N per arm and retries the whole A/B up
    to 3 times — the true overhead is ~0, so a genuine per-op
    regression (collector design broken) still fails every attempt."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        'bench', os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    res = None
    for _ in range(3):
        res = bench.obs_overhead_ab(steps=30, trials=3)
        if res['overhead_pct'] < 3.0:
            break
    assert res['overhead_pct'] < 3.0, res


class TestFleetAndCollectives:
    @pytest.fixture(autouse=True)
    def _mesh(self):
        from paddle_tpu.distributed import env
        env.init_parallel_env((8,), ('dp',))
        yield

    def test_collective_calls_and_bytes_counted(self):
        import paddle_tpu.distributed as dist
        reg = obs.get_registry()
        before = reg.value('paddle_collective_calls_total',
                           op='all_reduce', axis='dp')
        x = np.ones((8, 4), np.float32)
        t = paddle.to_tensor(x)
        dist.all_reduce(t, group='dp')
        assert reg.value('paddle_collective_calls_total',
                         op='all_reduce', axis='dp') == before + 1
        got = reg.value('paddle_collective_bytes_total',
                        op='all_reduce', axis='dp')
        assert got >= x.nbytes
        # disabled ⇒ not counted
        obs.enable(False)
        dist.all_reduce(t, group='dp')
        obs.enable(True)
        assert reg.value('paddle_collective_calls_total',
                         op='all_reduce', axis='dp') == before + 1

    def test_gather_registry_merges_without_multiplying(self):
        from paddle_tpu.distributed import fleet_utils
        import paddle_tpu.distributed as dist
        reg = obs.get_registry()
        t = paddle.to_tensor(np.ones((8, 2), np.float32))
        dist.all_reduce(t, group='dp')
        local = reg.value('paddle_collective_calls_total',
                          op='all_reduce', axis='dp')
        merged = fleet_utils.gather_registry(group='dp')
        by_name = {m['name']: m for m in merged['metrics']}
        samples = by_name['paddle_collective_calls_total']['samples']
        (row,) = [s for s in samples
                  if s['labels'] == {'op': 'all_reduce', 'axis': 'dp'}]
        assert row['value'] == local   # deduped, not x8
        assert merged['processes'] == [0]


class TestOffloadBytes:
    def test_h2d_d2h_counted(self):
        import paddle_tpu.nn as nn
        reg = obs.get_registry()
        h2d0 = reg.value('paddle_offload_h2d_bytes_total')
        d2h0 = reg.value('paddle_offload_d2h_bytes_total')
        model = nn.Linear(4, 4)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters(),
                                     offload='host')
        from paddle_tpu.jit import TrainStep
        import paddle_tpu.nn.functional as F
        step = TrainStep(
            model, lambda out, lab: F.mse_loss(out, lab), opt)
        x = np.ones((2, 4), np.float32)
        step(x, x)
        assert reg.value('paddle_offload_h2d_bytes_total') > h2d0
        assert reg.value('paddle_offload_d2h_bytes_total') > d2h0


class TestEndToEnd:
    def test_train_loop_populates_unified_summary(self):
        """The acceptance check: a smoke train loop + one
        observability_summary() showing dispatch, jit, steps, and
        memory from the single shared registry."""
        import runpy
        import os
        obs.get_registry().reset()
        # the program store shares executables process-wide: drop its
        # memory tier so this run really compiles (the compile counters
        # below are the point of the test)
        from paddle_tpu import programs
        programs.get_store().clear_memory()
        mod = runpy.run_path(os.path.join(
            os.path.dirname(__file__), '..', 'examples', 'train_gpt.py'))
        mod['main'](steps=6)
        reg = obs.get_registry()
        assert reg.value('paddle_steps_total') == 6
        assert reg.value('paddle_tokens_total') == 6 * 8 * 64
        assert reg.value('paddle_jit_compiles_total') >= 1
        assert reg.value('paddle_jit_compile_seconds_total') > 0
        assert reg.value('paddle_memory_watermark_bytes') > 0
        text = debug.observability_summary()
        assert 'steps: 6 total' in text


class TestMetricsLoggerCallback:
    def test_fit_streams_step_telemetry(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.io import TensorDataset

        obs.get_registry().reset()
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(
                learning_rate=0.01, parameters=net.parameters()),
            loss=nn.loss_layers.CrossEntropyLoss())
        xs = np.random.randn(16, 4).astype(np.float32)
        ys = np.random.randint(0, 2, (16, 1))
        cb = paddle.callbacks.MetricsLoggerCallback(
            tokens_per_batch=4, log_dir=str(tmp_path), export_freq=2)
        model.fit(TensorDataset([paddle.to_tensor(xs),
                                 paddle.to_tensor(ys)]),
                  batch_size=4, epochs=1, verbose=0, callbacks=[cb])
        reg = obs.get_registry()
        assert reg.value('paddle_steps_total') == 4
        assert reg.value('paddle_tokens_total') == 16
        recs = obs.read_jsonl(str(tmp_path / 'metrics.jsonl'))
        assert any(r['name'] == 'paddle_steps_total' for r in recs)
