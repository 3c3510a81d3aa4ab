"""Online weight updates (ISSUE 12): versioned sha256-manifested
WeightStore with quarantine, trainer-side WeightPublisher, and the
rolling ReplicaUpdater hot-swap over a live Router.

The acceptance test at the center runs train→publish→swap on a live
ReplicaSet UNDER traffic and asserts the full contract: zero dropped
requests, zero real XLA compiles across the swap (compile-counter delta
== cache-hit delta, AND no new ProgramStore keys), every response
tagged with one consistent weight_version, post-swap greedy outputs
bit-exact versus a fresh engine loaded from the same version, and a
failed health gate (injected NaN checkpoint) rolling the replica back
to bit-exact previous-version outputs with the bad version quarantined.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (FINISHED, CanaryGate, InferenceEngine,
                                ReplicaSet, ReplicaUpdater, Router,
                                SamplingParams, WeightLoadError,
                                WeightPublisher, WeightStore,
                                finite_weights_gate)

NO_EOS = -1


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


@pytest.fixture(scope='module')
def trained_state():
    """A second, distinguishable set of weights for the same config
    (what 'the trainer moved on' looks like)."""
    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig.tiny()).eval()
    return {n: np.asarray(t.value) for n, t in m.state_dict().items()}


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (s,)).tolist() for s in lens]


def _sp(n=6):
    return SamplingParams(max_new_tokens=n, eos_token_id=NO_EOS)


def _state_of(model):
    return {n: np.asarray(t.value) for n, t in model.state_dict().items()}


def _fresh_reference(state, prompts, max_new):
    """Greedy outputs of a FRESH engine built from `state` (the
    bit-exactness oracle for swapped fleets)."""
    m = GPTForCausalLM(GPTConfig.tiny()).eval()
    m.set_state_dict(state)
    eng = InferenceEngine(m, num_slots=2, max_length=64, decode_block=2)
    return [h.result()
            for h in [eng.submit(p, _sp(max_new)) for p in prompts]]


def _events_since(log, n0, name):
    return [e for e in log.events()[n0:] if e['name'] == name]


# ---------------------------------------------------------------------------
# the versioned store
# ---------------------------------------------------------------------------

class TestWeightStore:
    def test_publish_load_round_trip_bit_exact(self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w')
        state = _state_of(gpt)
        v = store.publish(state, meta={'step': 17})
        assert v == 1 and store.latest_version() == 1
        loaded = store.load(v)
        assert set(loaded) == set(state)
        for n in state:
            np.testing.assert_array_equal(loaded[n], state[n])
        assert store.meta(v)['step'] == 17

    def test_versions_monotone_and_explicit_guard(self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w')
        state = _state_of(gpt)
        assert store.publish(state) == 1
        assert store.publish(state, version=5) == 5
        assert store.next_version() == 6
        with pytest.raises(ValueError):
            store.publish(state, version=3)   # monotone, always

    def test_corrupt_payload_fails_load_not_falls_back(self, tmp_path,
                                                       gpt):
        store = WeightStore(tmp_path / 'w')
        v = store.publish(_state_of(gpt))
        payload = tmp_path / 'w' / f'step_{v}' / 'tree.npz'
        raw = bytearray(payload.read_bytes())
        raw[len(raw) // 2] ^= 0xFF          # one flipped bit
        payload.write_bytes(bytes(raw))
        with pytest.raises(WeightLoadError):
            store.load(v)                    # sha256 manifest catches it

    def test_quarantine_filters_latest_and_load(self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w')
        state = _state_of(gpt)
        v1, v2 = store.publish(state), store.publish(state)
        store.quarantine(v2, 'failed gate (test)')
        assert store.latest_version() == v1
        assert store.quarantined() == [v2]
        with pytest.raises(WeightLoadError):
            store.load(v2)
        # numbering stays monotone PAST the quarantined version
        assert store.publish(state) == v2 + 1

    def test_retention_keeps_last_k(self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w', keep_versions=2)
        state = _state_of(gpt)
        for _ in range(4):
            store.publish(state)
        assert store.all_versions() == [3, 4]

    def test_rollback_needs_two_versions(self, tmp_path):
        with pytest.raises(ValueError):
            WeightStore(tmp_path / 'w', keep_versions=1)


# ---------------------------------------------------------------------------
# stale-writer detection (ISSUE 13 satellite: the PR-12 cross-process
# stretch — trainer and servers in SEPARATE processes over one store)
# ---------------------------------------------------------------------------

_STORE_CHILD = r'''
import json, os, sys
import numpy as np
from paddle_tpu.serving.hotswap import WeightStore

d, action = sys.argv[1], sys.argv[2]
store = WeightStore(d, stale_writer_s=3600.0)
fill = float(sys.argv[3]) if len(sys.argv) > 3 else 1.0
state = {'w': np.full((4, 4), fill, 'float32')}
if action == 'publish':
    print(json.dumps({'published': store.publish(state),
                      'writer_left': store.writer_marker() is not None}))
elif action == 'publish_killed_mid_commit':
    # die between the tmp dir completing and the atomic commit rename —
    # the exact torn state a SIGKILLed trainer leaves: a _WRITER marker
    # and an uncommitted step_*.tmp, but never a half-offered version
    real_replace = os.replace

    def dying(src, dst):
        if os.path.basename(dst).startswith('step_'):
            os._exit(17)
        return real_replace(src, dst)

    os.replace = dying
    store.publish(state)
elif action == 'serve':
    latest = store.latest_version()
    tree = store.load(latest) if latest is not None else None
    print(json.dumps({
        'latest': latest,
        'w0': None if tree is None else float(tree['w'].flat[0]),
        'writer_marker': store.writer_marker() is not None,
        'tmp_dirs': sorted(n for n in os.listdir(d)
                           if n.endswith('.tmp')),
    }))
'''


def _run_store_child(tmp_path, action, fill=None, timeout=240):
    import json as _json
    import os
    import subprocess
    import sys
    args = [sys.executable, '-c', _STORE_CHILD,
            str(tmp_path / 'wstore'), action]
    if fill is not None:
        args.append(str(fill))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(args, capture_output=True, text=True,
                          timeout=timeout, env=env)
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
        else ''
    return proc.returncode, (_json.loads(line) if line else None), \
        proc.stderr


class TestStaleWriterDetection:
    def test_trainer_server_smoke_with_mid_commit_kill(self, tmp_path):
        """Subprocess-driven trainer→server flow: publish, die
        mid-commit, serve the last committed version anyway, recover,
        publish again, serve the new version."""
        # trainer publishes v1 cleanly (and releases its marker)
        rc, out, err = _run_store_child(tmp_path, 'publish', fill=1.0)
        assert rc == 0, err
        assert out == {'published': 1, 'writer_left': False}
        # a second trainer dies BETWEEN tmp completion and commit
        rc, _, err = _run_store_child(tmp_path,
                                      'publish_killed_mid_commit',
                                      fill=2.0)
        assert rc == 17, err
        # the server still gets v1 — the torn v2 is invisible; only the
        # dead writer's marker and tmp dir remain
        rc, srv, err = _run_store_child(tmp_path, 'serve')
        assert rc == 0, err
        assert srv['latest'] == 1 and srv['w0'] == 1.0
        assert srv['writer_marker'] is True
        assert srv['tmp_dirs'] == ['step_2.tmp']
        # a RESTARTED trainer detects the stale marker (dead pid),
        # sweeps marker + tmp orphans, and publishes v2 for real
        rc, out, err = _run_store_child(tmp_path, 'publish', fill=3.0)
        assert rc == 0, err
        assert out['published'] == 2
        rc, srv, err = _run_store_child(tmp_path, 'serve')
        assert rc == 0, err
        assert srv['latest'] == 2 and srv['w0'] == 3.0
        assert srv['writer_marker'] is False
        assert srv['tmp_dirs'] == []

    def test_live_concurrent_publisher_is_a_loud_error(self, tmp_path):
        store = WeightStore(tmp_path / 'w')
        store._claim_writer(1)      # this live process holds the marker
        other = WeightStore(tmp_path / 'w')
        with pytest.raises(RuntimeError, match='live publisher'):
            other.publish({'w': np.ones((2, 2), 'float32')})
        store._release_writer()
        assert other.publish({'w': np.ones((2, 2), 'float32')}) == 1

    def test_dead_pid_marker_swept_in_process(self, tmp_path):
        import json as _json
        store = WeightStore(tmp_path / 'w')
        # a marker from a pid that cannot exist, same host
        import os as _os
        with open(store._writer_path(), 'w') as f:
            _json.dump({'pid': 2 ** 22 + 12345, 'started': 0,
                        'host': _os.uname().nodename}, f)
        (tmp_path / 'w' / 'step_9.tmp').mkdir()
        log0 = len(obs.get_event_log().events())
        v = store.publish({'w': np.ones((2, 2), 'float32')})
        assert v == 1
        assert not (tmp_path / 'w' / 'step_9.tmp').exists()
        names = [e['name'] for e in obs.get_event_log().events()[log0:]]
        assert 'weight_writer_stale' in names

    def test_foreign_host_marker_ages_out(self, tmp_path):
        import json as _json
        import time as _time
        store = WeightStore(tmp_path / 'w', stale_writer_s=5.0)
        with open(store._writer_path(), 'w') as f:
            _json.dump({'pid': 1, 'started': _time.time(),
                        'host': 'some-other-host'}, f)
        # young foreign marker: treated as live (pid probes don't
        # travel across hosts; age is the only signal)
        with pytest.raises(RuntimeError, match='live publisher'):
            store.publish({'w': np.ones((2, 2), 'float32')})
        with open(store._writer_path(), 'w') as f:
            _json.dump({'pid': 1, 'started': _time.time() - 60.0,
                        'host': 'some-other-host'}, f)
        assert store.publish({'w': np.ones((2, 2), 'float32')}) == 1

    def test_stats_surface_writer_marker(self, tmp_path):
        store = WeightStore(tmp_path / 'w')
        assert store.stats()['writer'] is None
        store._claim_writer(3)
        assert store.stats()['writer']['version'] == 3
        store._release_writer()


# ---------------------------------------------------------------------------
# the trainer side
# ---------------------------------------------------------------------------

class TestWeightPublisher:
    def test_interval_and_no_double_publish(self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w')
        pub = WeightPublisher(gpt, store, interval_steps=3)
        assert pub.maybe_publish(1) is None
        assert pub.maybe_publish(2) is None
        v = pub.maybe_publish(3)
        assert v == 1 and pub.last_published_step == 3
        assert pub.maybe_publish(3) is None    # same step, once
        assert pub.maybe_publish(6) == 2

    def test_callable_source_and_event(self, tmp_path, gpt):
        log = obs.get_event_log()
        n0 = len(log.events())
        store = WeightStore(tmp_path / 'w')
        state = _state_of(gpt)
        pub = WeightPublisher(lambda: state, store)
        v = pub.publish(step=4)
        loaded = store.load(v)
        np.testing.assert_array_equal(
            loaded[next(iter(state))], state[next(iter(state))])
        evs = _events_since(log, n0, 'weight_publish')
        assert evs and evs[-1]['attrs']['version'] == v
        assert evs[-1]['attrs']['step'] == 4


# ---------------------------------------------------------------------------
# the engine swap primitive
# ---------------------------------------------------------------------------

class TestEngineSwap:
    def test_swap_requires_drained_engine(self, gpt, trained_state):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2)
        eng.submit(_prompts([5])[0], _sp(4))
        with pytest.raises(RuntimeError, match='drained'):
            eng.swap_weights(trained_state, version=1)
        # draining it makes the swap legal
        eng.run()
        eng.swap_weights(trained_state, version=1)
        assert eng.weight_version == 1

    def test_aval_mismatch_and_missing_param_raise(self, gpt,
                                                   trained_state):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2)
        bad = dict(trained_state)
        name = next(iter(bad))
        bad[name] = np.zeros((3, 3), np.float32)
        with pytest.raises(ValueError, match='shape'):
            eng.swap_weights(bad, version=1)
        missing = dict(trained_state)
        missing.pop(name)
        with pytest.raises(KeyError, match='missing'):
            eng.swap_weights(missing, version=1)
        assert eng.weight_version == 0      # both refused atomically

    def test_swap_and_rollback_bit_exact_zero_compiles(
            self, gpt, trained_state):
        """The primitive's whole contract on one engine: post-swap
        outputs match a fresh engine on the new weights, rollback
        restores bit-exact old outputs, and neither direction compiles
        anything (same avals ⇒ same programs)."""
        reg = obs.get_registry()
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2)
        prompts = _prompts([5, 9], seed=3)
        before = [eng.submit(p, _sp(6)).result() for p in prompts]
        traces0 = dict(eng.stats()['traces'])
        c0 = reg.value('paddle_jit_compiles_total')
        h0 = reg.value('paddle_jit_cache_hits_total')

        prev = eng.swap_weights(trained_state, version=1)
        after = [eng.submit(p, _sp(6)).result() for p in prompts]
        assert after == _fresh_reference(trained_state, prompts, 6)
        assert after != before              # the weights actually moved

        eng.restore_weights(prev)
        assert eng.weight_version == 0
        rolled = [eng.submit(p, _sp(6)).result() for p in prompts]
        assert rolled == before             # bit-exact old behavior
        assert dict(eng.stats()['traces']) == traces0
        assert (reg.value('paddle_jit_compiles_total') - c0) \
            == (reg.value('paddle_jit_cache_hits_total') - h0)

    def test_handles_stamped_with_admission_version(self, gpt,
                                                    trained_state):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, weight_version=41)
        h1 = eng.submit(_prompts([5])[0], _sp(4))
        assert h1.weight_version is None     # queued: not admitted yet
        h1.result()
        assert h1.weight_version == 41
        eng.swap_weights(trained_state, version=42)
        h2 = eng.submit(_prompts([5])[0], _sp(4))
        h2.result()
        assert h2.weight_version == 42
        assert h1.weight_version == 41       # history does not rewrite


# ---------------------------------------------------------------------------
# acceptance: the rolling swap on a live fleet under traffic
# ---------------------------------------------------------------------------

class TestRollingSwap:
    @pytest.fixture(autouse=True)
    def _strict_sanitizer(self, sanitizer_strict):
        """Rolling swaps (incl. the kill-mid-swap chaos path in
        test_router.py) run under the strict concurrency sanitizer
        (ISSUE 15)."""
        yield

    def test_train_publish_swap_under_traffic_full_contract(
            self, tmp_path, gpt, trained_state):
        """The ISSUE-12 acceptance test. A 2-replica Router serves a
        mixed-length greedy trace; mid-flight the updater rolls a newly
        published version across the fleet while a traffic pump keeps
        submitting. Asserts: zero dropped requests, zero real XLA
        compiles across the swap (counter delta == cache-hit delta and
        no new ProgramStore keys), single consistent weight_version per
        response, post-swap outputs bit-exact vs a fresh engine on the
        same version, and rollback restoring bit-exact previous-version
        outputs after an injected NaN checkpoint (quarantined, with
        events)."""
        from paddle_tpu import programs as _programs
        reg = obs.get_registry()
        log = obs.get_event_log()
        store = WeightStore(tmp_path / 'w')
        v1 = store.publish(_state_of(gpt))

        router = Router(ReplicaSet(gpt, 2, num_slots=2, max_length=64,
                                   decode_block=2, weight_version=v1))
        # -- warm every program the trace needs (prefill buckets 4/8/16
        # + decode), so the swap window measures the swap alone
        warm_lens = [3, 6, 9, 14]
        warm = [router.submit(p, _sp(6))
                for p in _prompts(warm_lens, seed=5)]
        router.run()
        assert all(h.status == FINISHED for h in warm)

        # -- wave A in flight, then the rolling swap with a pump that
        # keeps NEW traffic arriving while replica 0 drains
        wave_a = [router.submit(p, _sp(6))
                  for p in _prompts(warm_lens, seed=6)]
        for _ in range(2):
            router.step()

        pumped = []

        def pump():
            if len(pumped) < 4:
                pumped.append(router.submit(
                    _prompts([warm_lens[len(pumped)]],
                             seed=7 + len(pumped))[0], _sp(6)))

        v2 = store.publish(trained_state)
        updater = ReplicaUpdater(router, store, traffic_pump=pump)

        keys0 = {e['key'] for e in _programs.get_store().entries()}
        traces0 = [dict(r.engine.stats()['traces'])
                   for r in router.replicas]
        c0 = reg.value('paddle_jit_compiles_total')
        h0 = reg.value('paddle_jit_cache_hits_total')
        ev0 = len(log.events())

        res = updater.update_to(v2)
        assert res['outcome'] == 'completed'
        assert all(r['outcome'] == 'completed' for r in res['replicas'])
        assert all(r['new_program_keys'] == 0 and r['real_compiles'] == 0
                   for r in res['replicas'])
        assert updater.fleet_version == v2

        # post-swap traffic, same shapes
        wave_b = [router.submit(p, _sp(6))
                  for p in _prompts(warm_lens, seed=20)]
        router.run()

        # 1. zero dropped requests — every accepted request FINISHED
        everyone = wave_a + pumped + wave_b
        assert pumped, 'the pump never ran: drain saw no traffic'
        for h in everyone:
            assert h.status == FINISHED, f'dropped/failed: {h!r}'
        st = router.stats()
        assert st['failed'] == 0 and st['in_flight'] == 0

        # 2. zero real XLA compiles across the swap + both waves:
        # compile-counter delta == cache-hit delta, no new store keys,
        # python trace counts flat on both replicas
        assert (reg.value('paddle_jit_compiles_total') - c0) \
            == (reg.value('paddle_jit_cache_hits_total') - h0)
        assert {e['key']
                for e in _programs.get_store().entries()} == keys0
        for r, t0 in zip(router.replicas, traces0):
            assert dict(r.engine.stats()['traces']) == t0, \
                f'replica {r.id} retraced across the swap'

        # 3. every response carries ONE consistent weight_version
        for h in everyone:
            assert h.weight_version in (v1, v2), h.weight_version
        for h in wave_b:
            assert h.weight_version == v2
        assert {p['weight_version'] for p in st['replicas']} == {v2}

        # 4. post-swap greedy outputs bit-exact vs a FRESH engine
        # loaded from the same version
        fresh = _fresh_reference(store.load(v2),
                                 _prompts(warm_lens, seed=20), 6)
        assert [h.tokens for h in wave_b] == fresh

        # 5. swap observability: begin/complete events per replica,
        # /healthz versions, router gauge values
        begins = _events_since(log, ev0, 'weight_swap_begin')
        completes = _events_since(log, ev0, 'weight_swap_complete')
        assert len(begins) == 2 and len(completes) == 2
        assert {e['attrs']['to_version'] for e in completes} == {v2}
        assert obs.health()['weight_versions']['replica:0'] == v2
        router._refresh_gauges()
        assert reg.value('paddle_router_weight_version',
                         replica='0') == v2

        # 6. rollback: an injected NaN checkpoint fails the gate, the
        # replica reverts, the version is quarantined with events, and
        # previous-version outputs stay bit-exact
        bad = dict(trained_state)
        name = next(n for n, a in bad.items()
                    if np.issubdtype(np.asarray(a).dtype, np.floating))
        bad[name] = np.full_like(np.asarray(bad[name]), np.nan)
        v3 = store.publish(bad)
        ev1 = len(log.events())
        res_bad = updater.update_to(v3)
        assert res_bad['outcome'] == 'aborted'
        assert res_bad['replicas'][0]['outcome'] == 'rolled_back'
        assert len(res_bad['replicas']) == 1   # rollout stopped there
        assert updater.fleet_version == v2     # fleet never mixed in v3
        assert store.quarantined() == [v3]
        assert _events_since(log, ev1, 'weight_swap_failed')
        assert _events_since(log, ev1, 'weight_rollback')
        assert _events_since(log, ev1, 'weight_version_quarantined')
        after_rollback = [router.submit(p, _sp(6))
                          for p in _prompts(warm_lens, seed=20)]
        router.run()
        assert [h.tokens for h in after_rollback] == fresh   # still v2
        assert all(h.weight_version == v2 for h in after_rollback)

        # 7. poll() never re-offers the quarantined version
        assert updater.poll() is None

    def test_load_failure_quarantines_without_touching_replicas(
            self, tmp_path, gpt):
        store = WeightStore(tmp_path / 'w')
        v1 = store.publish(_state_of(gpt))
        router = Router(ReplicaSet(gpt, 1, num_slots=2, max_length=64,
                                   decode_block=2, weight_version=v1))
        updater = ReplicaUpdater(router, store)
        v2 = store.publish(_state_of(gpt))
        payload = tmp_path / 'w' / f'step_{v2}' / 'tree.npz'
        payload.write_bytes(b'garbage')
        res = updater.update_to(v2)
        assert res['outcome'] == 'load_failed'
        assert res['replicas'] == []
        assert store.quarantined() == [v2]
        assert router.replicas[0].engine.weight_version == v1
        assert updater.poll() is None       # v1 is latest and current

    def test_canary_gate_probes_the_cordoned_replica(self, tmp_path,
                                                     gpt, trained_state):
        """The opt-in canary decodes ON the swapped replica while it is
        out of rotation; a mismatch rolls back, a match rejoins."""
        store = WeightStore(tmp_path / 'w')
        v1 = store.publish(_state_of(gpt))
        router = Router(ReplicaSet(gpt, 1, num_slots=2, max_length=64,
                                   decode_block=2, weight_version=v1))
        prompt = _prompts([5], seed=9)[0]
        baseline = router.submit(prompt, _sp(4))
        router.run()
        v2 = store.publish(trained_state)
        expected = _fresh_reference(trained_state, [prompt], 4)[0]

        # wrong expectation -> gate fails -> rollback + quarantine
        bad_gate = CanaryGate(prompt, 4, expect=[0, 0, 0, 0])
        updater = ReplicaUpdater(router, store,
                                 gates=[finite_weights_gate, bad_gate])
        res = updater.update_to(v2)
        assert res['replicas'][0]['outcome'] == 'rolled_back'
        assert 'canary mismatch' in res['replicas'][0]['reason']
        assert router.replicas[0].engine.weight_version == v1
        again = router.submit(prompt, _sp(4))
        router.run()
        assert again.tokens == baseline.tokens

        # right expectation -> swap completes (v2 was quarantined, so
        # republish the same weights as v3)
        v3 = store.publish(trained_state)
        good = ReplicaUpdater(router, store, gates=[
            finite_weights_gate, CanaryGate(prompt, 4, expect=expected)])
        res = good.update_to(v3)
        assert res['outcome'] == 'completed'
        assert router.replicas[0].engine.weight_version == v3


# ---------------------------------------------------------------------------
# the composed RLHF-shaped loop (tier-1-sized)
# ---------------------------------------------------------------------------

class TestRolloutLoop:
    def test_loop_trains_publishes_and_converges_fleet(self, tmp_path):
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.loop import RolloutLoop, response_lm_loss
        vocab = 32
        cfg = GPTConfig(vocab_size=vocab, hidden_size=32,
                        num_hidden_layers=1, num_attention_heads=2,
                        intermediate_size=64,
                        max_position_embeddings=32,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        paddle.seed(0)
        train_model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=train_model.parameters())
        step = TrainStep(train_model, response_lm_loss(vocab), opt)
        store = WeightStore(tmp_path / 'w')
        publisher = WeightPublisher(train_model, store, interval_steps=1)
        v1 = publisher.publish(step=0)
        serve_model = GPTForCausalLM(cfg).eval()
        serve_model.set_state_dict(store.load(v1))
        router = Router(ReplicaSet(serve_model, 2, num_slots=2,
                                   max_length=32, decode_block=2,
                                   weight_version=v1))
        updater = ReplicaUpdater(router, store)

        def prompt_fn(i):
            rng = np.random.RandomState(100 + i)
            return [rng.randint(1, vocab, (4,)).tolist()
                    for _ in range(4)]

        loop = RolloutLoop(
            train_step=step, router=router, publisher=publisher,
            updater=updater, prompt_fn=prompt_fn,
            reward_fn=lambda p, r: float(np.mean([t == 7 for t in r])),
            rollouts_per_iter=4, keep_best=2, max_new_tokens=4,
            train_passes=1)
        hist = loop.run(2)
        assert len(hist) == 2
        # every iteration published and the fleet swapped onto it: the
        # NEXT iteration's rollouts come from the new weights
        assert hist[0]['published_version'] == v1 + 1
        assert hist[0]['swap'] == {'version': v1 + 1,
                                   'outcome': 'completed'}
        assert hist[1]['fleet_version'] \
            == publisher.last_published_version
        assert updater.fleet_version == publisher.last_published_version
        assert all(np.isfinite(h['loss']) for h in hist)
        # rollouts carried the version they were generated under
        assert hist[1]['rollouts'] == 4
        st = router.stats()
        assert st['failed'] == 0
