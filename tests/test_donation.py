"""ISSUE 13 — the donation gauntlet.

Covers the acceptance surface: the subprocess probe classifying
fault-injected corrupting runtimes (garbage outputs AND a segfaulting
child — the trainer must survive both) vs a safe one; verdicts
manifest-recorded per backend fingerprint and cached (no re-probe); a
safe verdict re-applying recorded donate_argnums to store-served
programs with bit-exact losses/greedy outputs vs the undonated path; a
corrupting verdict falling back undonated with `donation_probe_failed`
emitted; corruption sentinels guarding the first K donated invocations
and a mid-serving trip quarantining donation — recompile undonated,
every accepted request completed, never a garbage value surfaced, a
flight bundle written; quarantine outliving flag overrides; the pool
recovery path for a donated decode dying mid-call; and the bench
`donation_ab` tier-1 parity guard.

Tier-1 pins FLAGS_donation=off globally (conftest) because the
installed jaxlib is the known intermittently-corrupting runtime; every
test here opts back in explicitly and restores the pinned posture.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import flags as pflags
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import TrainStep
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.programs import donation
from paddle_tpu.serving import InferenceEngine, SamplingParams

NO_EOS = -1


@pytest.fixture(autouse=True)
def _donation_hygiene():
    """Every test here leaves the process exactly as tier-1 expects:
    donation pinned off, no persistent store, no cached verdicts, no
    probe-mode env leaking into later subprocesses."""
    yield
    os.environ.pop('PADDLE_DONATION_PROBE_MODE', None)
    pflags.set_flags({'FLAGS_donation': 'off'})
    donation.clear_cache()
    programs.configure(None)


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (s,)).tolist() for s in lens]


def _train_losses(steps=3):
    rng = np.random.RandomState(0)
    x = rng.standard_normal((16, 32)).astype('float32')
    y = rng.randint(0, 4, (16,))
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=m.parameters())
    step = TrainStep(m, lambda o, l: F.cross_entropy(o, l), opt)
    losses = [float(step(paddle.to_tensor(x),
                         paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    return losses, step


def _event_names():
    return [e['name'] for e in obs.get_event_log().events()]


# ---------------------------------------------------------------------------
# the subprocess probe
# ---------------------------------------------------------------------------

class TestProbe:
    def test_garbage_mode_classifies_corrupting(self):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'garbage'
        v = donation.run_probe(runs=3)
        assert v['verdict'] == 'corrupting'
        assert 'trial' in v['reason']

    def test_segfaulting_probe_never_kills_the_trainer(self):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'segv'
        v = donation.run_probe(runs=3)
        # we are alive to assert this — the subprocess took the SIGSEGV
        assert v['verdict'] == 'corrupting'
        assert 'signal' in v['reason']

    def test_ok_mode_is_safe(self):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'ok'
        v = donation.run_probe(runs=3)
        assert v['verdict'] == 'safe'

    def test_real_probe_returns_a_verdict_never_raises(self):
        # the REAL donated export chain on the installed jaxlib: the
        # verdict is the runtime's to give (this jaxlib corrupts
        # intermittently, so both answers are legitimate) — the
        # CONTRACT is a clean classification either way
        v = donation.run_probe(runs=2)
        assert v['verdict'] in ('safe', 'corrupting')
        assert v['runs'] == 2
        assert v['seconds'] > 0


# ---------------------------------------------------------------------------
# posture resolution + verdict manifests
# ---------------------------------------------------------------------------

class TestPostureResolution:
    def test_flag_off_never_probes(self, tmp_path):
        # a probe in 'garbage' mode would classify corrupting — but
        # 'off' must not even launch it
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'garbage'
        pflags.set_flags({'FLAGS_donation': 'off'})
        store = programs.configure(str(tmp_path / 'store'))
        st = store.donation_state()
        assert st['posture'] == 'off' and st['verdict'] is None
        assert not any(f.startswith('donation.')
                       for f in os.listdir(tmp_path / 'store'))

    def test_auto_without_directory_stays_off_without_probe(self):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'garbage'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        store = programs.configure(None)
        st = store.donation_state()
        assert st['posture'] == 'off'
        assert 'no persistent store' in st['reason']

    def test_auto_on_a_tpu_parent_never_spawns(self, tmp_path, monkeypatch):
        """A chip has one client: from the process that holds it the
        probe child could never attach (it used to time out after 180 s
        and record a false `corrupting`). No spawn, no verdict file,
        undonated — and loud about it."""
        monkeypatch.setattr(
            donation, 'run_probe',
            lambda *a, **k: pytest.fail('probe spawned from a TPU parent'))
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        d = str(tmp_path / 'store')
        fp = dict(programs.backend_fingerprint(), backend='tpu')
        with pytest.warns(RuntimeWarning, match='no verdict'):
            posture = donation.resolve_posture(d, fp)
        assert not posture['enabled'] and posture['verdict'] is None
        assert posture['source'] == 'no_verdict'
        assert f'python -m paddle_tpu.programs.donation {d}' \
            in posture['reason']
        assert not os.path.exists(d)
        assert 'donation_no_verdict' in _event_names()

    def test_auto_safe_probe_enables_and_records_manifest(self, tmp_path):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'ok'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        d = str(tmp_path / 'store')
        store = programs.configure(d)
        assert store.donation_enabled
        names = [f for f in os.listdir(d) if f.startswith('donation.')]
        assert len(names) == 1
        with open(os.path.join(d, names[0])) as f:
            manifest = json.load(f)
        assert manifest['verdict'] == 'safe'
        assert manifest['fingerprint'] == store._fingerprint
        evs = _event_names()
        assert 'donation_probe_ok' in evs and 'donation_enabled' in evs

    def test_auto_corrupting_probe_falls_back_undonated(self, tmp_path):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'garbage'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        d = str(tmp_path / 'store')
        store = programs.configure(d)
        assert not store.donation_enabled
        assert store.donation_state()['verdict'] == 'corrupting'
        assert 'donation_probe_failed' in _event_names()
        # the store still works — undonated, with nothing donated
        losses, _ = _train_losses(2)
        assert all(np.isfinite(losses))
        assert all(not e['donated'] for e in store.entries())

    def test_segv_probe_degrades_cleanly(self, tmp_path):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'segv'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        store = programs.configure(str(tmp_path / 'store'))
        st = store.donation_state()
        assert st['posture'] == 'off' and st['verdict'] == 'corrupting'
        assert 'signal' in st['reason']

    def test_recorded_verdict_skips_reprobe(self, tmp_path):
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'ok'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        d = str(tmp_path / 'store')
        store = programs.configure(d)
        assert store.donation_enabled
        # a re-init in a fresh process would read the manifest; here the
        # probe mode now SEGFAULTS, so any re-probe would flip the
        # verdict — staying enabled proves the recorded verdict served
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'segv'
        donation.clear_cache()          # drop the process cache too
        store = programs.configure(d)   # re-resolve from disk
        assert store.donation_enabled
        assert store.donation_state()['source'] == 'recorded'

    def test_verdicts_are_fingerprint_keyed(self, tmp_path):
        # a corrupting verdict recorded for ANOTHER runtime (the old
        # jaxlib) must not gate THIS one: a jaxlib upgrade re-probes and
        # flips donation on with zero code change
        d = str(tmp_path / 'store')
        os.makedirs(d)
        other_fp = dict(programs.backend_fingerprint(), jaxlib='0.0.0')
        donation.record_verdict(
            d, donation.fingerprint_token(other_fp),
            {'version': 1, 'verdict': 'corrupting', 'reason': 'old'})
        os.environ['PADDLE_DONATION_PROBE_MODE'] = 'ok'
        pflags.set_flags({'FLAGS_donation': 'auto'})
        donation.clear_cache()
        store = programs.configure(d)
        assert store.donation_enabled
        assert len([f for f in os.listdir(d)
                    if f.startswith('donation.')]) == 2


# ---------------------------------------------------------------------------
# donated train path (store-served)
# ---------------------------------------------------------------------------

class TestDonatedTrain:
    @pytest.fixture(autouse=True)
    def _strict_sanitizer(self, sanitizer_strict):
        """Donated train paths — incl. the sentinel-trip quarantine —
        run under the strict concurrency sanitizer (ISSUE 15)."""
        yield

    def test_store_served_donated_losses_bit_exact(self, tmp_path):
        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'don'))
        don, step = _train_losses(3)
        assert step.donation_live
        assert any(e['donated'] for e in store.entries()
                   if e['name'] == 'train_step')
        pflags.set_flags({'FLAGS_donation': 'off'})
        programs.configure(str(tmp_path / 'undon'))
        undon, step2 = _train_losses(3)
        assert don == undon
        # undonated STORE posture, but the direct path still donates —
        # donation_live reflects the store-served executable here
        assert not step2.donation_live

    def test_sentinel_trip_quarantines_recompiles_and_serves_good_values(
            self, tmp_path, monkeypatch):
        from paddle_tpu.observability import flight
        rec = flight.get_flight_recorder()
        monkeypatch.setattr(rec, 'min_interval_s', 0.0)
        dumps_before = len(rec.dumps)
        pflags.set_flags({'FLAGS_donation': 'off'})
        programs.configure(str(tmp_path / 'ref'))
        ref, _ = _train_losses(3)

        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'don'))
        q_before = obs.get_registry().value(
            'paddle_donation_quarantines_total')
        calls = {'n': 0}
        real = donation.outputs_ok

        def tripping(out):
            calls['n'] += 1
            return False if calls['n'] == 2 else real(out)

        monkeypatch.setattr(donation, 'outputs_ok', tripping)
        got, _ = _train_losses(3)
        # the tripped call itself returned the RIGHT value (undonated
        # re-run of the same invocation), and the run continued
        assert got == ref
        st = store.donation_state()
        assert st['posture'] == 'quarantined'
        assert st['donated_entries'] == 0
        assert 'donation_quarantined' in _event_names()
        assert obs.get_registry().value(
            'paddle_donation_quarantines_total') == q_before + 1
        assert len(rec.dumps) == dumps_before + 1   # flight bundle
        # manifest flipped: the quarantine is durable
        names = [f for f in os.listdir(tmp_path / 'don')
                 if f.startswith('donation.')]
        with open(tmp_path / 'don' / names[0]) as f:
            assert json.load(f)['verdict'] == 'quarantined'

    def test_quarantine_outlives_flag_on(self, tmp_path):
        d = str(tmp_path / 'store')
        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(d)
        assert store.donation_enabled
        store.quarantine_donation('test: simulated corruption')
        assert not store.donation_enabled
        # even a forced-on re-init honors the recorded quarantine: a
        # sentinel caught REAL corruption on this runtime
        donation.clear_cache()
        store = programs.configure(d)
        assert not store.donation_enabled
        assert store.donation_state()['posture'] == 'quarantined'


# ---------------------------------------------------------------------------
# donated serving path
# ---------------------------------------------------------------------------

class TestDonatedServing:
    @pytest.fixture(autouse=True)
    def _strict_sanitizer(self, sanitizer_strict):
        """Donated serving — incl. the mid-serving sentinel trip and
        pool recovery — runs under the strict concurrency sanitizer
        (ISSUE 15)."""
        yield

    def _run(self, gpt, donate_pool, prompts, max_new=6):
        eng = InferenceEngine(gpt, num_slots=4, max_length=64,
                              donate_pool=donate_pool)
        handles = eng.generate_many(
            prompts, SamplingParams(max_new_tokens=max_new,
                                    eos_token_id=NO_EOS))
        return eng, [list(h.tokens) for h in handles]

    def test_donated_pool_greedy_parity_store_served(self, gpt, tmp_path):
        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'store'))
        prompts = _prompts((5, 9, 13, 7))
        _, don = self._run(gpt, True, prompts)
        _, undon = self._run(gpt, False, prompts)
        assert don == undon
        decode = {(e['donated']) for e in store.entries()
                  if e['name'] == 'serving.decode_block'}
        # two distinct executables: the donated arm's and the
        # undonated arm's (donate_pool rides the statics)
        assert decode == {True, False}

    def test_sentinel_trip_mid_serving_completes_every_request(
            self, gpt, tmp_path, monkeypatch):
        pflags.set_flags({'FLAGS_donation': 'off'})
        prompts = _prompts((5, 9, 13, 7, 11))
        _, ref = self._run(gpt, False, prompts)

        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'store'))
        calls = {'n': 0}
        real = donation.outputs_ok

        def tripping(out):
            calls['n'] += 1
            return False if calls['n'] == 3 else real(out)

        monkeypatch.setattr(donation, 'outputs_ok', tripping)
        eng, got = self._run(gpt, True, prompts)
        # the trip mid-trace quarantined donation and recompiled
        # undonated — but every accepted request finished, bit-exact,
        # and no handle ever saw a garbage token
        assert got == ref
        assert eng.stats()['failed'] == 0
        assert store.donation_state()['posture'] == 'quarantined'
        assert 'donation_quarantined' in _event_names()

    def test_donated_decode_failure_recovers_the_pool(self, gpt):
        # direct-path donation (no store): a decode program dying
        # mid-call may have consumed its donated row inputs — the
        # engine must rebuild the pool and stay serviceable
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              donate_pool=True, prefix_cache=True)
        real_jit = eng._decode_jit
        state = {'raised': False}

        def dying(*args):
            state['raised'] = True
            raise RuntimeError('simulated device failure mid-decode')

        eng._decode_jit = dying
        h = eng.submit(_prompts((6,))[0], max_new_tokens=4,
                       eos_token_id=NO_EOS)
        with pytest.raises(RuntimeError, match='mid-decode'):
            eng.run()
        assert state['raised']
        assert 'serving_pool_recovered' in _event_names()
        for handle in eng.evict_all():
            assert handle is h            # orphan handed back, not lost
        # fresh rows: the engine serves the next request correctly
        eng._decode_jit = real_jit
        ref_eng, ref = self._run(gpt, False, _prompts((6,)), max_new=4)
        h2 = eng.submit(_prompts((6,))[0], max_new_tokens=4,
                        eos_token_id=NO_EOS)
        eng.run()
        assert list(h2.tokens) == ref[0]


# ---------------------------------------------------------------------------
# CLI runbook + bench guard
# ---------------------------------------------------------------------------

class TestCliAndBench:
    def test_module_cli_records_verdict(self, tmp_path):
        env = dict(os.environ, PADDLE_DONATION_PROBE_MODE='ok',
                   JAX_PLATFORMS='cpu')
        d = str(tmp_path / 'store')
        proc = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.programs.donation', d,
             '2'],
            capture_output=True, text=True, timeout=240, env=env)
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc['verdict'] == 'safe'
        assert [f for f in os.listdir(d) if f.startswith('donation.')]

    def test_bench_donation_ab_parity_guard(self):
        import bench
        r = bench.donation_ab(n_requests=4, max_new=4, train_steps=2)
        assert r['parity_tokens'], r
        assert r['parity_losses'], r
        assert r['donated_posture'] == 'on'
        assert r['pool_copy_bytes_saved'] > 0
        assert r['row_bytes'] * 4 == r['pool_bytes']   # 4 slots


# ---------------------------------------------------------------------------
# posture surfaces
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_store_stats_and_summary_carry_posture(self, tmp_path):
        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'store'))
        st = store.stats()
        assert st['donation']['posture'] == 'on'
        from paddle_tpu import debug
        text = debug.observability_summary()
        assert 'donation: on' in text

    def test_posture_gauge_tracks_quarantine(self, tmp_path):
        pflags.set_flags({'FLAGS_donation': 'on'})
        store = programs.configure(str(tmp_path / 'store'))
        reg = obs.get_registry()
        assert reg.value('paddle_donation_posture') == 1.0
        store.quarantine_donation('test')
        assert reg.value('paddle_donation_posture') == -1.0
