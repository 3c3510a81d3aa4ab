"""Tier-1 enforcement of `paddle_tpu.analysis` — the JAX-aware static
analysis framework.

Three layers:

- the real tree must lint CLEAN modulo the committed baseline (zero
  unsuppressed findings, zero stale baseline entries — the shrink-only
  rule: fixing a grandfathered finding forces deleting its entry);
- every pass proves both directions on the fixture corpus under
  tests/analysis_fixtures/ (>=3 true-positive and >=3 true-negative
  snippets per pass);
- the two historical bug classes that motivated the framework — the
  PR 1 closure-over-tracer custom_vjp break and the PR 10
  `or`-on-falsy-EventLog reroute — are re-introduced in scratch files
  and must be flagged (meta-tests), plus the CLI exit-code contract
  (0 clean / 1 findings / 2 internal error).
"""
import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.analysis import core
from paddle_tpu.analysis.passes import obs_schema

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / 'tests' / 'analysis_fixtures'

ALL_PASSES = ('falsy-guard', 'host-sync', 'lock-order', 'obs-schema',
              'raw-lock', 'swallowed-exception', 'trace-hazard')

#: FIXTURE_SPECS entries whose "pass" is a RUNTIME checker: the fixture
#: modules are EXECUTED under the report-mode sanitizer instead of
#: parsed by a static pass
RUNTIME_FIXTURE_PASSES = {'lockset'}


def run_on(path, passes, baseline=None):
    files = [core.SourceFile(pathlib.Path(path), root=ROOT)]
    return core.run_analysis(files=files, passes=list(passes),
                             baseline=baseline)


def write_module(tmp_path, text, name='scratch.py'):
    p = tmp_path / name
    p.write_text(textwrap.dedent(text))
    return p


# ---------------------------------------------------------------------------
# the tree itself
# ---------------------------------------------------------------------------

class TestTreeCleanliness:
    def test_registry_has_the_eight_passes(self):
        assert set(core.registered_passes()) == set(ALL_PASSES)

    def test_full_tree_lints_clean_modulo_baseline(self):
        baseline = core.Baseline.load()
        result = core.run_analysis(baseline=baseline)
        assert result.files_scanned > 100
        msgs = [f.render() for f in result.findings]
        assert not msgs, 'unsuppressed findings:\n' + '\n'.join(msgs)
        assert not result.stale_baseline, (
            'baseline entries whose finding was fixed — delete them '
            f'(shrink-only): {result.stale_baseline}')
        assert result.clean

    def test_baseline_header_counts_entries_and_reasons(self):
        """The shrink-only contract: the header's entry_count must match
        the entries (growing the list is a two-place reviewable diff),
        and every grandfathered finding carries a reason."""
        raw = json.loads(core.DEFAULT_BASELINE_PATH.read_text())
        entries = raw['entries']
        assert raw['header']['entry_count'] == len(entries)
        keys = [e['key'] for e in entries]
        assert len(set(keys)) == len(keys), 'duplicate baseline keys'
        for e in entries:
            assert e['reason'].strip(), f'baseline entry without reason: {e}'

    def test_baseline_header_mismatch_is_rejected(self, tmp_path):
        p = tmp_path / 'baseline.json'
        p.write_text(json.dumps({
            'header': {'entry_count': 7},
            'entries': [{'key': 'k', 'reason': 'r'}]}))
        with pytest.raises(ValueError, match='entry_count'):
            core.Baseline.load(p)

    def test_baseline_entry_without_reason_is_rejected(self, tmp_path):
        p = tmp_path / 'baseline.json'
        p.write_text(json.dumps({
            'header': {'entry_count': 1},
            'entries': [{'key': 'k', 'reason': '  '}]}))
        with pytest.raises(ValueError, match='reason'):
            core.Baseline.load(p)


# ---------------------------------------------------------------------------
# fixture corpus: >=3 TP and >=3 TN snippets per pass
# ---------------------------------------------------------------------------

FIXTURE_SPECS = [
    ('trace-hazard', 'trace_hazard/bad_hazards.py',
     'trace_hazard/good_clean.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/engine.py',
     'host_sync/good/paddle_tpu/serving/engine.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/hotswap.py',
     'host_sync/good/paddle_tpu/serving/hotswap.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/autoscaler.py',
     'host_sync/good/paddle_tpu/serving/autoscaler.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/kv_pool.py',
     'host_sync/good/paddle_tpu/serving/kv_pool.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/remote.py',
     'host_sync/good/paddle_tpu/serving/remote.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/supervisor.py',
     'host_sync/good/paddle_tpu/serving/supervisor.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/serving/adapters/bank.py',
     'host_sync/good/paddle_tpu/serving/adapters/bank.py'),
    ('host-sync', 'host_sync/bad/paddle_tpu/observability/reqledger.py',
     'host_sync/good/paddle_tpu/observability/reqledger.py'),
    ('falsy-guard', 'falsy_guard/bad_falsy_or.py',
     'falsy_guard/good_is_none.py'),
    ('lock-order', 'lock_order/bad_locks.py', 'lock_order/good_locks.py'),
    ('lock-order', 'lock_order_interproc/bad_cross.py',
     'lock_order_interproc/good_cross.py'),
    ('raw-lock', 'raw_lock/bad_raw.py', 'raw_lock/good_wrapped.py'),
    ('lockset', 'lockset/bad_races.py', 'lockset/good_guarded.py'),
    ('swallowed-exception', 'swallowed_exception/bad_swallows.py',
     'swallowed_exception/good_handled.py'),
    ('obs-schema', 'obs_schema/bad_schema.py', 'obs_schema/good_schema.py'),
]


def run_lockset_fixture(path):
    """Execute a runtime-lockset fixture module's `run_scenarios()`
    under the report-mode sanitizer; returns the lockset violations."""
    import importlib.util

    from paddle_tpu.analysis import runtime as rt
    spec = importlib.util.spec_from_file_location(
        f'_lockset_fixture_{path.stem}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rt.reset()
    rt.enable('report')
    try:
        mod.run_scenarios()
        return rt.violations('lockset_race')
    finally:
        rt.disable()
        rt.reset()


class TestFixtureCorpus:
    @pytest.mark.parametrize('pass_name,bad,_good', FIXTURE_SPECS,
                             ids=[s[0] for s in FIXTURE_SPECS])
    def test_true_positives(self, pass_name, bad, _good):
        if pass_name in RUNTIME_FIXTURE_PASSES:
            violations = run_lockset_fixture(FIXTURES / bad)
            fields = {v['field'] for v in violations}
            assert len(fields) >= 3, (
                f'{pass_name} caught only {sorted(fields)} of >=3 '
                f'seeded races in {bad}')
            return
        result = run_on(FIXTURES / bad, [pass_name])
        assert len(result.findings) >= 3, (
            f'{pass_name} found only {len(result.findings)} of >=3 '
            f'planted defects in {bad}: '
            f'{[f.render() for f in result.findings]}')
        assert all(f.pass_name == pass_name for f in result.findings)

    @pytest.mark.parametrize('pass_name,_bad,good', FIXTURE_SPECS,
                             ids=[s[0] for s in FIXTURE_SPECS])
    def test_true_negatives(self, pass_name, _bad, good):
        if pass_name in RUNTIME_FIXTURE_PASSES:
            violations = run_lockset_fixture(FIXTURES / good)
            assert not violations, (
                f'{pass_name} false-positives: {violations}')
            return
        result = run_on(FIXTURES / good, [pass_name])
        msgs = [f.render() for f in result.findings]
        assert not msgs, f'{pass_name} false-positives:\n' + '\n'.join(msgs)

    def test_specific_bad_snippets_are_located(self):
        """Spot-check that findings land on the planted lines, not just
        anywhere in the file."""
        result = run_on(FIXTURES / 'lock_order/bad_locks.py',
                        ['lock-order'])
        msgs = ' | '.join(f.message for f in result.findings)
        assert 'lock-order cycle' in msgs
        assert 're-entry on non-reentrant' in msgs
        assert '_count' in msgs and 'without a lock' in msgs

    def test_interprocedural_cycles_name_both_classes(self):
        """The whole-program upgrade: cross-class, two-hop-transitive,
        and module-lock cycles plus a transitive re-entry — each names
        the exact lock nodes involved."""
        result = run_on(FIXTURES / 'lock_order_interproc/bad_cross.py',
                        ['lock-order'])
        msgs = ' | '.join(f.message for f in result.findings)
        assert 'Ledger._ledger_lock' in msgs and \
            'Journal._journal_lock' in msgs
        assert 'TwoHop._alock' in msgs and 'TwoHop._block' in msgs
        assert 'bad_cross._flush_lock' in msgs       # module-level node
        assert 're-entry on non-reentrant DeepReentry._lock' in msgs


# ---------------------------------------------------------------------------
# suppressions + baseline round trip
# ---------------------------------------------------------------------------

class TestSuppression:
    def test_same_line_and_next_line_and_file_suppressions(self, tmp_path):
        p = write_module(tmp_path, '''
            def a():
                try:
                    return 1
                except Exception:  # paddle-lint: disable=swallowed-exception -- fixture
                    return 0

            def b():
                try:
                    return 1
                # paddle-lint: disable-next=swallowed-exception -- fixture
                except Exception:
                    return 0

            def c():
                try:
                    return 1
                except Exception:
                    return 0
        ''')
        result = run_on(p, ['swallowed-exception'])
        assert len(result.findings) == 1        # only c() survives
        assert result.findings[0].scope == 'c'
        assert len(result.suppressed) == 2

        p2 = write_module(tmp_path, '''
            # paddle-lint: disable-file=swallowed-exception -- generated fixture
            def a():
                try:
                    return 1
                except Exception:
                    return 0
        ''', name='scratch2.py')
        result2 = run_on(p2, ['swallowed-exception'])
        assert not result2.findings and len(result2.suppressed) == 1

    def test_suppression_is_per_pass(self, tmp_path):
        p = write_module(tmp_path, '''
            def a():
                try:
                    return 1
                except Exception:  # paddle-lint: disable=falsy-guard -- wrong pass
                    return 0
        ''')
        result = run_on(p, ['swallowed-exception'])
        assert len(result.findings) == 1


class TestBaselineRoundTrip:
    def test_grandfather_then_shrink(self, tmp_path):
        bad = FIXTURES / 'swallowed_exception/bad_swallows.py'
        found = run_on(bad, ['swallowed-exception'])
        assert found.findings

        bl_path = tmp_path / 'baseline.json'
        bl = core.Baseline({f.key: 'fixture grandfather' for f
                            in found.findings}, path=bl_path)
        bl.save()
        reloaded = core.Baseline.load(bl_path)
        assert reloaded.entries == bl.entries

        # round trip: with the baseline the same file is clean
        again = run_on(bad, ['swallowed-exception'], baseline=reloaded)
        assert again.clean
        assert len(again.grandfathered) == len(found.findings)

        # shrink-only: fix one finding -> its entry goes STALE and the
        # run is no longer clean until the entry is deleted
        fixed = tmp_path / 'fixed.py'
        text = bad.read_text().replace(
            'except Exception:\n            pass',
            'except Exception:\n            raise', 1)
        # keep the repo-relative identity by scanning under tmp root
        fixed.write_text(text)
        files = [core.SourceFile(fixed, root=tmp_path)]
        # re-key the baseline onto the tmp file's rel path
        rekeyed = core.Baseline(
            {k.replace('tests/analysis_fixtures/swallowed_exception/'
                       'bad_swallows.py', 'fixed.py'): v
             for k, v in reloaded.entries.items()}, path=bl_path)
        res = core.run_analysis(files=files, passes=['swallowed-exception'],
                                baseline=rekeyed)
        assert res.stale_baseline, 'fixed finding must surface as stale'
        assert not res.clean

    def test_keys_are_line_number_free(self, tmp_path):
        p1 = write_module(tmp_path, '''
            def a():
                try:
                    return 1
                except Exception:
                    return 0
        ''', name='m.py')
        k1 = run_on(p1, ['swallowed-exception']).findings[0].key
        p1.write_text('# a comment\n# another\n\n' + p1.read_text())
        k2 = run_on(p1, ['swallowed-exception']).findings[0].key
        assert k1 == k2


# ---------------------------------------------------------------------------
# meta-tests: the historical bug classes must be caught if re-introduced
# ---------------------------------------------------------------------------

class TestHistoricalBugClasses:
    def test_pr1_closure_over_tracer_is_flagged(self, tmp_path):
        """The original _fused_softmax_ce break: custom_vjp fwd/bwd
        registered inside the op wrapper, closing over the wrapper's
        (tracer) arguments instead of passing residuals."""
        p = write_module(tmp_path, '''
            import jax
            import jax.numpy as jnp

            def fused_ce(logits2d, safe_labels, valid):
                @jax.custom_vjp
                def ce(x):
                    return ce_fwd(x)[0]

                def ce_fwd(x):
                    xf = x.astype(jnp.float32)
                    lse = jax.nn.logsumexp(xf, axis=-1)
                    tgt = jnp.take_along_axis(
                        xf, safe_labels[:, None], 1)[:, 0]
                    return jnp.where(valid, lse - tgt, 0.0), (x, lse)

                def ce_bwd(res, g):
                    x, lse = res
                    p = jnp.exp(x - lse[:, None])
                    onehot = jax.nn.one_hot(safe_labels, x.shape[-1])
                    return ((p - onehot) * jnp.where(valid, g, 0.0)[:, None],)

                ce.defvjp(ce_fwd, ce_bwd)
                return ce(logits2d)
        ''')
        result = run_on(p, ['trace-hazard'])
        msgs = [f.message for f in result.findings]
        assert any('closes over' in m and 'safe_labels' in m
                   for m in msgs), msgs

    def test_pr10_falsy_eventlog_or_is_flagged(self, tmp_path):
        p = write_module(tmp_path, '''
            from typing import Optional
            from paddle_tpu.observability.events import EventLog

            _default_log = EventLog()

            class Span:
                def __init__(self, name: str,
                             _log: Optional[EventLog] = None):
                    self._log = _log or _default_log
        ''')
        result = run_on(p, ['falsy-guard'])
        assert result.findings, 'PR 10 pattern not flagged'
        assert 'EventLog' in result.findings[0].message

    def test_fixed_tree_sites_stay_fixed(self):
        """The real files where these bugs lived lint clean now."""
        for rel, pas in (('paddle_tpu/nn/functional.py', 'trace-hazard'),
                         ('paddle_tpu/observability/events.py',
                          'falsy-guard')):
            result = run_on(ROOT / rel, [pas])
            assert not result.findings, [f.render()
                                         for f in result.findings]


# ---------------------------------------------------------------------------
# CLI exit-code contract: 0 clean / 1 findings / 2 internal error
# ---------------------------------------------------------------------------

def run_cli(*args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, '-m', 'paddle_tpu.analysis', *args],
        capture_output=True, text=True, cwd=str(cwd), timeout=300,
        env={'JAX_PLATFORMS': 'cpu', 'PATH': '/usr/bin:/bin',
             'PYTHONPATH': str(ROOT), 'HOME': '/tmp'})


class TestCliContract:
    def test_exit_0_clean_tree_and_json_shape(self):
        r = run_cli('--format=json')
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc['summary']['clean'] is True
        assert doc['summary']['finding_count'] == 0
        assert set(doc['summary']['passes_run']) == set(ALL_PASSES)

    def test_exit_1_on_findings(self):
        r = run_cli('--format=json', '--no-baseline',
                    'tests/analysis_fixtures/swallowed_exception/'
                    'bad_swallows.py')
        assert r.returncode == 1, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc['summary']['finding_count'] >= 3
        assert all(f['pass_name'] == 'swallowed-exception'
                   for f in doc['findings'])

    def test_exit_2_internal_error(self):
        assert run_cli('--passes=definitely-not-a-pass').returncode == 2
        assert run_cli('no/such/target.py').returncode == 2

    def test_list_passes(self):
        r = run_cli('--list-passes')
        assert r.returncode == 0
        for name in ALL_PASSES:
            assert name in r.stdout


# ---------------------------------------------------------------------------
# --stats subcommand: per-pass accounting + stale-suppression audit
# ---------------------------------------------------------------------------

class TestStatsAndStaleSuppressions:
    def test_stats_clean_on_the_real_tree(self):
        """The tree's own contract: every inline suppression still
        silences a live finding (the inline mirror of the shrink-only
        baseline rule) and the JSON carries per-pass counts."""
        r = run_cli('--stats', '--format=json')
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc['clean'] is True
        assert set(doc['passes']) == set(ALL_PASSES)
        for row in doc['passes'].values():
            assert set(row) == {'findings', 'grandfathered', 'suppressed',
                                'baseline_entries', 'stale_suppressions'}
        # the tree HAS live suppressions — the audit is not vacuous
        assert sum(row['suppressed'] for row in doc['passes'].values()) > 0

    def test_stale_suppression_fails_the_run(self, tmp_path):
        p = write_module(tmp_path, '''
            X = 1  # paddle-lint: disable=swallowed-exception -- nothing fires here
        ''')
        r = run_cli('--stats', '--no-baseline', str(p))
        assert r.returncode == 1, r.stdout + r.stderr
        assert 'STALE-SUPPRESSION' in r.stdout
        assert 'swallowed-exception' in r.stdout

    def test_unknown_pass_suppression_fails_the_run(self, tmp_path):
        p = write_module(tmp_path, '''
            X = 1  # paddle-lint: disable=swalloed-exceptoin -- typo
        ''')
        r = run_cli('--stats', '--no-baseline', str(p))
        assert r.returncode == 1
        assert 'unknown pass' in r.stdout

    def test_docstring_examples_are_not_suppressions_nor_stale(
            self, tmp_path):
        """A suppression EXAMPLE inside a docstring neither silences
        findings on its line nor trips the stale audit — comments are
        found by tokenizing, not line-scanning."""
        p = write_module(tmp_path, '''
            """Docs showing the syntax:

                x = y  # paddle-lint: disable=swallowed-exception -- example
            """

            def a():
                try:
                    return 1
                except Exception:
                    return 0
        ''')
        result = run_on(p, ['swallowed-exception'])
        assert len(result.findings) == 1          # not suppressed
        files = [core.SourceFile(p, root=p.parent)]
        res = core.run_analysis(files=files, passes=['swallowed-exception'])
        assert core.audit_suppressions(files, res) == []

    def test_live_suppression_is_not_stale(self, tmp_path):
        p = write_module(tmp_path, '''
            def a():
                try:
                    return 1
                except Exception:  # paddle-lint: disable=swallowed-exception -- fixture
                    return 0

            # paddle-lint: disable-file=falsy-guard -- no protected types here
        ''')
        files = [core.SourceFile(p, root=tmp_path)]
        res = core.run_analysis(
            files=files, passes=['swallowed-exception', 'falsy-guard'])
        stale = core.audit_suppressions(files, res)
        # the same-line one is live; the file-level falsy-guard one
        # suppresses nothing -> stale
        assert len(stale) == 1
        assert stale[0]['pass'] == 'falsy-guard'
        assert stale[0]['kind'] == 'disable-file'

    def test_audit_skips_passes_that_did_not_run(self, tmp_path):
        p = write_module(tmp_path, '''
            X = 1  # paddle-lint: disable=trace-hazard -- judged only when the pass runs
        ''')
        files = [core.SourceFile(p, root=tmp_path)]
        res = core.run_analysis(files=files, passes=['swallowed-exception'])
        assert core.audit_suppressions(files, res) == []


# ---------------------------------------------------------------------------
# framework mechanics
# ---------------------------------------------------------------------------

class TestFrameworkMechanics:
    def test_occurrence_numbering_disambiguates_identical_findings(
            self, tmp_path):
        p = write_module(tmp_path, '''
            def probe():
                try:
                    return 1
                except Exception:
                    return 0
                try:
                    return 2
                except Exception:
                    return 0
        ''')
        res = run_on(p, ['swallowed-exception'])
        keys = [f.key for f in res.findings]
        assert len(keys) == 2 and len(set(keys)) == 2
        assert keys[1].endswith('::#1')

    def test_unknown_pass_raises(self):
        with pytest.raises(KeyError):
            core.get_pass('nope')

    def test_obs_schema_scan_sees_known_families_and_events(self):
        """Anchors migrated from test_obs_schema_lint: the pass is only
        as good as its scanner."""
        files = core.discover_files()
        metrics = obs_schema.scan_metrics(files)
        for known in ('paddle_steps_total', 'paddle_span_seconds',
                      'paddle_goodput_seconds_total', 'paddle_mfu',
                      'paddle_suppressed_errors_total'):
            assert known in metrics, f'{known} not found by the scanner'
        emits = obs_schema.scan_emits(files)
        assert 'bad_step' in emits
        assert any('{}' in n for n in emits), \
            'no f-string emit found — scanner lost JoinedStr support'
        declared = obs_schema.scan_schema(files)
        assert 'program_cache_hit' in declared


# ---------------------------------------------------------------------------
# regression tests for findings fixed in this PR
# ---------------------------------------------------------------------------

class TestFusedCeRegression:
    """The top trace-hazard finding: _fused_softmax_ce_xla re-created its
    custom_vjp per call with the fwd rule closing over enclosing-scope
    tracers. Now module-level with labels/valid as explicit
    non-differentiated args."""

    def test_custom_vjp_is_module_level_and_closure_free(self):
        from paddle_tpu.nn import functional as F
        fn = F._ce_xla_bwd
        assert fn.__closure__ is None
        assert F._ce_xla_fwd.__closure__ is None

    def test_value_and_grad_parity_with_reference(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn import functional as F
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 9)), jnp.float32)
        labels = jnp.asarray([1, 8, 0, 3])
        valid = jnp.asarray([True, True, False, True])

        def ref(x):
            logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
            per = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
            return jnp.sum(jnp.where(valid, per, 0.0))

        def fused(x):
            return jnp.sum(F._fused_softmax_ce_xla(x, labels, valid))

        np.testing.assert_allclose(fused(x), ref(x), rtol=1e-5)
        np.testing.assert_allclose(jax.grad(fused)(x), jax.grad(ref)(x),
                                   rtol=1e-5, atol=1e-6)
        # and under jit + the split-vjp shape the dispatch cache uses
        out, pull = jax.vjp(fused, x)
        np.testing.assert_allclose(pull(jnp.float32(1.0))[0],
                                   jax.grad(ref)(x), rtol=1e-5, atol=1e-6)

    def test_dispatch_cache_zero_retrace_on_repeat_ce(self):
        """The dispatch-cache regression the satellite asks for: repeated
        same-shape cross_entropy calls through the eager path must not
        retrace."""
        import paddle_tpu as paddle
        from paddle_tpu import debug
        from paddle_tpu.nn import functional as F
        rng = np.random.default_rng(1)
        logits_np = rng.standard_normal((6, 11)).astype(np.float32)
        labels_np = rng.integers(0, 11, size=(6,))

        # warm once (first call may compile), then measure
        for _ in range(2):
            F.cross_entropy(paddle.to_tensor(logits_np),
                            paddle.to_tensor(labels_np))
        debug.reset_dispatch_stats()
        vals = []
        for _ in range(3):
            out = F.cross_entropy(paddle.to_tensor(logits_np),
                                  paddle.to_tensor(labels_np))
            vals.append(float(np.asarray(out.numpy())))
        s = debug.dispatch_stats()
        assert s['retraces'] == 0, s
        assert vals[0] == vals[1] == vals[2]


class TestFalsyGuardRegressions:
    """The falsy-guard sites converted to `is None`: an explicitly-passed
    (empty, hence potentially-falsy) framework object must be USED, not
    silently swapped for the global singleton."""

    def test_exporters_use_the_passed_empty_registry(self):
        from paddle_tpu.observability.exporters import (to_jsonl,
                                                        to_prometheus_text)
        from paddle_tpu.observability.metrics import MetricsRegistry
        fresh = MetricsRegistry(process_index=0)
        text = to_prometheus_text(registry=fresh)
        # the default registry has dozens of paddle_ families; a fresh
        # empty one must render none of them
        assert 'paddle_steps_total' not in text
        assert to_jsonl(registry=fresh).strip() == ''

    def test_store_and_mfu_window_use_passed_catalog(self):
        from paddle_tpu.observability.cost import MfuWindow, ProgramCatalog
        from paddle_tpu.programs.store import ProgramStore
        cat = ProgramCatalog()
        assert MfuWindow(catalog=cat)._catalog is cat
        assert ProgramStore(catalog=cat).catalog is cat

    def test_telemetry_uses_passed_registry(self):
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.telemetry import StepTelemetry
        fresh = MetricsRegistry(process_index=0)
        StepTelemetry(registry=fresh)
        assert fresh.get('paddle_steps_total') is not None


class TestSuppressedErrorsCounter:
    def test_count_suppressed_increments_site_label(self):
        from paddle_tpu import observability as obs
        reg = obs.get_registry()
        before = reg.value('paddle_suppressed_errors_total',
                           site='test.analysis.probe')
        obs.count_suppressed('test.analysis.probe')
        after = reg.value('paddle_suppressed_errors_total',
                          site='test.analysis.probe')
        assert after == before + 1

    def test_broken_event_listener_is_counted_not_silent(self):
        from paddle_tpu import observability as obs
        log = obs.EventLog(capacity=8)

        def bad_listener(event):
            raise RuntimeError('boom')

        log.add_listener(bad_listener)
        reg = obs.get_registry()
        before = reg.value('paddle_suppressed_errors_total',
                           site='event_listener')
        log.append({'name': 'probe'})
        after = reg.value('paddle_suppressed_errors_total',
                          site='event_listener')
        assert after == before + 1
