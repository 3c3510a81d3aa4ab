"""ISSUE 45: a decode round RUNS AHEAD of the host whenever nothing could
be seated or retired between it and the next — the next block is
dispatched before the tokens of the one in flight are fetched, its slot
state advanced on the host at dispatch time, its pending tokens the
device's (`SlotState.carried`). Here on gpt: the rule step by step
(`ahead` on every `serving.decode_round`, one a block dispatched), the tokens against the same
engine held to the serial order, an EOS the host could not foresee, and
everything that assumes a quiet engine with a round in flight. One model
of each served family goes through `family_harness.
ahead_serves_the_serial_tokens` in its own `test_<family>_serving.py`."""
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import reqledger
from paddle_tpu.serving import (FINISHED, InferenceEngine, ReplicaSet,
                                Router, SamplingParams)
from paddle_tpu.serving import engine as engine_mod

from family_harness import SerialEngine

NO_EOS = -1
_leaves = jax.tree_util.tree_leaves


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (s,)).tolist() for s in lens]


def _ref_generate(model, prompt, max_new):
    out, _ = model.generate(
        paddle.to_tensor(np.array([prompt])), max_new_tokens=max_new,
        decode_strategy='greedy_search', eos_token_id=NO_EOS)
    return out.numpy()[0].tolist()


def _engine(gpt, cls=InferenceEngine, **kw):
    return cls(gpt, **dict(dict(num_slots=2, max_length=64,
                                decode_block=2), **kw))


def _greedy(n, eos=NO_EOS):
    return SamplingParams(max_new_tokens=n, eos_token_id=eos)


def _round_spans(log):
    """Per `serving.decode_round` (one a block dispatched) and
    `serving.settle` (a step that only fetches the block in flight),
    oldest first: (`ahead`, whether it dispatched a block, whether it
    fetched one, `discarded`)."""
    spans = [e for e in log.events() if e.get('ph') == 'X']
    out = []
    for e in spans:
        if e['name'] not in ('serving.decode_round', 'serving.settle'):
            continue
        kids = {k['name'] for k in spans if k['parent'] == e['id']}
        assert kids <= {'serving.decode_dispatch', 'serving.d2h'}
        dispatched = 'serving.decode_dispatch' in kids
        assert dispatched == (e['name'] == 'serving.decode_round')
        out.append((e['attrs'].get('ahead', 0), dispatched,
                    'serving.d2h' in kids, e['attrs'].get('discarded')))
    return out


@pytest.fixture
def log():
    lg = obs.get_event_log()
    lg.clear()
    return lg


@pytest.fixture
def draining():
    """A drain marks the whole process `draining` (/healthz): lifted
    again, so that no later test finds every replica degraded."""
    yield
    obs.clear_degraded('draining')


# ---------------------------------------------------------------------------
# the rule, step by step
# ---------------------------------------------------------------------------
def test_with_a_slot_free_every_round_is_settled_in_its_step(gpt, log):
    """One request in two slots: a request could be seated at any time,
    so no block is ever queued behind another — the serial order, and
    every step emits its block (the benchmark's traced rehearsals and
    serve-chat: never ahead)."""
    eng = _engine(gpt)
    h = eng.submit(_prompts([5])[0], _greedy(8))
    for k in range(1, 5):
        eng.step()
        assert len(h.tokens) == 2 * k and not eng._rounds
    assert h.status == FINISHED and not eng.has_work
    assert _round_spans(log) == [(0, True, True, 0)] * 4
    stats = eng.stats()
    assert (stats['rounds_ahead'], stats['blocks_discarded']) == (0, 0)


def test_with_every_slot_busy_the_next_block_goes_first(gpt, log):
    """Two requests of four blocks in two slots. Step 1 seats both and
    dispatches block 1; nobody can be seated and nobody ends inside it,
    so it stays in flight. Steps 2-4 dispatch the next block BEFORE they
    fetch the one in flight (`ahead` 1). Both reach `max_new_tokens`
    inside block 4, which the host knows when it has dispatched it: step
    5 only settles it."""
    eng = _engine(gpt)
    prompts = _prompts([5, 9])
    hs = [eng.submit(p, _greedy(8)) for p in prompts]
    before = obs.get_registry().value(
        'paddle_serving_decode_rounds_ahead_total')
    seen = []
    while eng.has_work:
        eng.step()
        seen.append(([len(h.tokens) for h in hs], len(eng._rounds)))
    assert seen == [([0, 0], 1), ([2, 2], 1), ([4, 4], 1), ([6, 6], 1),
                    ([8, 8], 0)]
    assert _round_spans(log) == [
        (0, True, False, None), (1, True, True, 0), (1, True, True, 0),
        (1, True, True, 0), (0, False, True, 0)]
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(gpt, p, 8)
    stats = eng.stats()
    assert stats['rounds_ahead'] == 3 and stats['decode_rounds'] == 4
    assert obs.get_registry().value(
        'paddle_serving_decode_rounds_ahead_total') - before == 3
    # in an ahead round the dispatch comes first, then the fetch
    spans = [e for e in log.events() if e.get('ph') == 'X']
    for e in spans:
        if e['name'] == 'serving.decode_round' and e['attrs']['ahead']:
            d, f = (next(k for k in spans if k['parent'] == e['id']
                         and k['name'] == n)
                    for n in ('serving.decode_dispatch', 'serving.d2h'))
            assert d['ts'] + d['dur'] <= f['ts'] + 1e-9


def test_an_end_by_length_inside_the_round_in_flight_is_settled_first(
        gpt, log):
    """The second request ends inside block 2: block 3 is not dispatched
    until block 2 is settled and its slot is free again — and with a
    slot free the rest is the serial order."""
    eng = _engine(gpt)
    prompts = _prompts([5, 9])
    a = eng.submit(prompts[0], _greedy(8))
    b = eng.submit(prompts[1], _greedy(4))
    seen = []
    while eng.has_work:
        eng.step()
        seen.append((len(a.tokens), len(b.tokens), len(eng._rounds)))
    assert seen == [(0, 0, 1), (2, 2, 1), (4, 4, 0), (6, 4, 0), (8, 4, 0)]
    assert _round_spans(log) == [
        (0, True, False, None), (1, True, True, 0), (0, False, True, 0),
        (0, True, True, 0), (0, True, True, 0)]
    assert a.tokens == _ref_generate(gpt, prompts[0], 8)
    assert b.tokens == _ref_generate(gpt, prompts[1], 4)


def test_a_slot_parked_mid_chunked_prefill_keeps_the_serial_order(gpt, log):
    """While the second slot prefills in chunks it is seated and not
    decoding: the first one's rounds are settled in their steps; once
    both decode, the engine runs ahead."""
    eng = _engine(gpt, prefill_chunk_tokens=4, buckets=[4, 8, 16])
    prompts = _prompts([3, 14])
    a = eng.submit(prompts[0], _greedy(16))
    b = eng.submit(prompts[1], _greedy(6))
    parked = []
    while eng.has_work:
        eng.step()
        parked.append(bool(eng._prefilling))    # still parked at its round
    rounds = _round_spans(log)
    assert a.tokens == _ref_generate(gpt, prompts[0], 16)
    assert b.tokens == _ref_generate(gpt, prompts[1], 6)
    n_parked = sum(parked)
    assert n_parked == 3 and parked[:3] == [True] * 3   # chunks of 4 of 14
    # every round of a step with a slot parked: dispatched and fetched
    assert rounds[:n_parked] == [(0, True, True, 0)] * n_parked
    assert sum(r[0] for r in rounds[n_parked:]) >= 1


# ---------------------------------------------------------------------------
# the tokens are the serial order's
# ---------------------------------------------------------------------------
_MODES = {'row': {}, 'paged': {'kv_page_size': 8},
          'paged_int8': {'kv_page_size': 8, 'kv_quant': 'int8'},
          'prefix_cache': {'prefix_cache': True},
          'chunked_prefill': {'prefill_chunk_tokens': 8}}


def _mixed_requests():
    """Six requests for two slots, greedy and sampled with parameters
    and seeds of their own: slots are reseated while the other decodes,
    and one answer crosses row 32 (both decode programs)."""
    sample = dict(strategy='sampling', eos_token_id=NO_EOS)
    return list(zip(_prompts([5, 9, 12, 7, 6, 20], seed=21), [
        _greedy(40),
        SamplingParams(max_new_tokens=9, temperature=1.3, top_k=20,
                       top_p=0.9, seed=11, **sample),
        SamplingParams(max_new_tokens=14, temperature=0.7, top_p=0.8,
                       seed=12, **sample),
        _greedy(11),
        SamplingParams(max_new_tokens=10, temperature=2.0, top_k=5,
                       seed=13, **sample),
        _greedy(7)]))


@pytest.mark.parametrize('mode', list(_MODES))
def test_a_mixed_batch_ahead_serves_the_serial_orders_tokens(gpt, mode):
    def serve(cls):
        eng = _engine(gpt, cls, decode_block=4, buckets=[8, 16, 32],
                      **_MODES[mode])
        hs = [eng.submit(p, sp) for p, sp in _mixed_requests()]
        eng.run()
        assert all(h.status == FINISHED for h in hs)
        return [list(h.tokens) for h in hs], eng.stats()
    toks, stats = serve(InferenceEngine)
    serial, serial_stats = serve(SerialEngine)
    assert toks == serial
    assert stats['rounds_ahead'] >= 5 and serial_stats['rounds_ahead'] == 0
    assert stats['decode_rounds'] == serial_stats['decode_rounds']
    assert stats['blocks_discarded'] == 0
    if 'int8' not in mode:                  # the greedy ones: generate()'s
        reqs = _mixed_requests()
        for i in (0, 3, 5):
            assert toks[i] == _ref_generate(gpt, reqs[i][0],
                                            reqs[i][1].max_new_tokens)


def test_through_the_router_a_backlog_runs_ahead_and_a_lone_request_not(
        gpt, log):
    router = Router(ReplicaSet(gpt, 1, num_slots=2, max_length=64,
                               decode_block=2))
    prompts = _prompts([5, 9, 7], seed=3)
    hs = [router.submit(p, _greedy(10)) for p in prompts]
    router.run()
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(gpt, p, 10)
    eng = router.replicas[0].engine
    assert eng.stats()['rounds_ahead'] >= 3
    ahead = eng.stats()['rounds_ahead']
    log.clear()
    h = router.submit(prompts[0], _greedy(10))
    router.run()
    assert h.tokens == _ref_generate(gpt, prompts[0], 10)
    assert eng.stats()['rounds_ahead'] == ahead
    assert all(r == (0, True, True, 0) for r in _round_spans(log))


# ---------------------------------------------------------------------------
# an end the host could not foresee
# ---------------------------------------------------------------------------
def _eos_at(tokens, lo, hi):
    """An index in [lo, hi) whose token does not occur before it."""
    for k in range(lo, hi):
        if tokens[k] not in tokens[:k]:
            return k
    raise AssertionError(f'no fresh token in {tokens[lo:hi]}')


def test_an_eos_inside_a_round_costs_that_slot_one_discarded_block(gpt, log):
    """The first request's EOS falls inside block 2, which the host
    learns after it has dispatched block 3: nothing past the EOS is
    emitted, the slot's share of block 3 is dropped (`discarded` 1 on
    the span that fetched it, `blocks_discarded` 1), the other slot
    loses nothing, and the request seated in the freed slot gets its own
    tokens and none of the dropped block's."""
    prompts = _prompts([5, 9, 7], seed=5)
    refs = [_ref_generate(gpt, p, 12) for p in prompts]
    k = _eos_at(refs[0], 2, 4)
    eng = _engine(gpt)
    a = eng.submit(prompts[0], _greedy(12, eos=refs[0][k]))
    b = eng.submit(prompts[1], _greedy(12))
    c = eng.submit(prompts[2], _greedy(12))        # waits for a slot
    for _ in range(3):
        eng.step()
    assert a.status == FINISHED and a.tokens == refs[0][:k + 1]
    assert len(b.tokens) == 4 and len(eng._rounds) == 1     # block 3
    assert eng._counts['blocks_discarded'] == 0             # not yet
    slot = next(s for s in range(2) if s not in eng._slot_req)
    eng.step()                              # settles block 3, seats nobody
    assert len(b.tokens) == 6 and not eng._rounds and not c.tokens
    assert eng._counts['blocks_discarded'] == 1
    eng.step()                              # c takes the freed slot
    assert eng._slot_req[slot] is c
    eng.run()
    assert a.tokens == refs[0][:k + 1]      # nothing past its EOS, ever
    assert b.tokens == refs[1] and c.tokens == refs[2]
    rounds = _round_spans(log)
    assert [r[3] for r in rounds[:4]] == [None, 0, 0, 1]
    assert rounds[3] == (0, False, True, 1)
    assert sum(r[3] or 0 for r in rounds) == 1
    assert eng.stats()['blocks_discarded'] == 1


# ---------------------------------------------------------------------------
# what assumes a quiet engine settles the round in flight first
# ---------------------------------------------------------------------------
def _in_flight(gpt, n_new=12, **kw):
    """An engine two steps in: both slots decoding, one block fetched,
    one in flight."""
    eng = _engine(gpt, **kw)
    prompts = _prompts([5, 9], seed=8)
    hs = [eng.submit(p, _greedy(n_new)) for p in prompts]
    eng.step()
    eng.step()
    assert len(eng._rounds) == 1 and [len(h.tokens) for h in hs] == [2, 2]
    return eng, hs, prompts


def _serves_anew(gpt, eng):
    """The pool is usable and no round is left behind: a fresh request
    is served the reference's tokens."""
    assert not eng._rounds
    prompt = _prompts([6], seed=9)[0]
    h = eng.submit(prompt, _greedy(6))
    eng.run()
    assert h.tokens == _ref_generate(gpt, prompt, 6)
    assert not eng._rounds and not eng.has_work


def test_stats_settles_the_round_in_flight(gpt):
    eng, hs, _ = _in_flight(gpt)
    assert eng._counts['decode_rounds'] == 1
    stats = eng.stats()
    assert not eng._rounds and [len(h.tokens) for h in hs] == [4, 4]
    assert stats['decode_rounds'] == 2 and stats['tokens'] == 8


def test_drain_finishes_the_round_in_flight_and_everything_else(
        gpt, draining):
    eng, hs, prompts = _in_flight(gpt)
    assert eng.drain(deadline_s=60.0)
    assert eng.draining and not eng._rounds and not eng.has_work
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(gpt, p, 12)


def test_begin_drain_counts_what_was_in_flight_as_it_ended(gpt, draining):
    eng, hs, _ = _in_flight(gpt, n_new=4)   # both end inside the block
    eng.begin_drain()
    assert not eng._rounds and all(h.status == FINISHED for h in hs)
    begun = [e for e in obs.get_event_log().events()
             if e['name'] == 'serving_drain_begin'][-1]
    assert begun['attrs']['in_flight'] == 0


def test_evict_all_drops_the_round_in_flight(gpt):
    eng, hs, _ = _in_flight(gpt)
    assert sorted(h.request_id for h in eng.evict_all()) == \
        sorted(h.request_id for h in hs)
    assert not eng._rounds and not eng.has_work
    assert eng.pool.free_count == 2
    assert [len(h.tokens) for h in hs] == [2, 2]    # handed off as they were
    _serves_anew(gpt, eng)


def test_a_drain_deadline_fails_what_is_left_and_leaves_no_round(gpt):
    eng, hs, _ = _in_flight(gpt)
    eng._fail_remaining(TimeoutError('deadline'))   # what `drain` calls
    assert not eng._rounds and not eng.has_work
    assert all(isinstance(h.error, TimeoutError) for h in hs)


def test_swap_weights_settles_a_block_nobody_is_left_to_take(gpt):
    """One slot, one request whose EOS falls inside block 2: when block
    2 is fetched block 3 is already in flight and nobody is seated. The
    engine has work until that block is settled, and `swap_weights`
    settles it itself before it asks whether the engine is drained."""
    prompt = _prompts([5], seed=5)[0]
    ref = _ref_generate(gpt, prompt, 12)
    k = _eos_at(ref, 2, 4)
    eng = _engine(gpt, num_slots=1)
    h = eng.submit(prompt, _greedy(12, eos=ref[k]))
    for _ in range(3):
        eng.step()
    assert h.status == FINISHED and h.tokens == ref[:k + 1]
    assert not eng._slot_req and len(eng._rounds) == 1 and eng.has_work
    state = {name: np.asarray(v) for name, v in gpt.state_dict().items()}
    prev = eng.swap_weights(state, version=3)
    assert not eng._rounds and eng.weight_version == 3
    assert eng._counts['blocks_discarded'] == 1
    _serves_anew(gpt, eng)
    eng.restore_weights(prev)
    assert eng.weight_version == 0
    _serves_anew(gpt, eng)


def test_a_failed_dispatch_with_a_round_in_flight_rebuilds_the_pool_once(
        gpt):
    """Block 2's program dies with block 1 in flight on the pool block 1
    was to return: both go, the pool is rebuilt once, the handles are
    still there for the router to fail over, and the engine serves the
    next request correctly."""
    eng = _engine(gpt)
    prompts = _prompts([5, 9], seed=8)
    hs = [eng.submit(p, _greedy(12)) for p in prompts]
    eng.step()
    assert len(eng._rounds) == 1
    real = eng._decode_program

    def dying(*args):
        for leaf in _leaves(args[3]):
            leaf.delete()                   # what a donated call may do
        raise RuntimeError('simulated device failure mid-decode')
    eng._decode_program = lambda rows, args: dying
    obs.get_event_log().clear()
    with pytest.raises(RuntimeError, match='mid-decode'):
        eng.step()
    assert [e['name'] for e in obs.get_event_log().events()].count(
        'serving_pool_recovered') == 1
    assert not eng._rounds
    assert not any(leaf.is_deleted() for leaf in _leaves(eng.pool.rows))
    assert sorted(h.request_id for h in eng.evict_all()) == \
        sorted(h.request_id for h in hs)
    eng._decode_program = real
    _serves_anew(gpt, eng)


def test_a_failed_fetch_leaves_the_round_in_flight_for_the_next_step(
        gpt, monkeypatch):
    """The tokens of a dispatched block are still the device's when a
    fetch fails: the step raises, the round stays in flight (both, when
    the failed fetch followed an ahead dispatch), and the next steps
    fetch them in order — nothing is lost, nothing emitted twice."""
    eng, hs, prompts = _in_flight(gpt)
    real = engine_mod._from_device
    left = [1]

    def failing(x):
        if left[0]:
            left[0] -= 1
            raise ValueError('simulated fetch failure')
        return real(x)
    monkeypatch.setattr(engine_mod, '_from_device', failing)
    with pytest.raises(ValueError, match='fetch failure'):
        eng.step()                          # dispatched block 3, then failed
    assert len(eng._rounds) == 2 and [len(h.tokens) for h in hs] == [2, 2]
    eng.step()                              # the oldest alone, no dispatch
    assert len(eng._rounds) == 1 and [len(h.tokens) for h in hs] == [4, 4]
    eng.run()
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(gpt, p, 12)


# ---------------------------------------------------------------------------
# the buffer is the next block's as soon as the call returns
# ---------------------------------------------------------------------------
def test_writing_the_buffer_after_a_dispatch_does_not_reach_that_block(gpt):
    """The engine advances the ONE buffer while the block it has just
    dispatched may not have started (jax's CPU client reads a numpy
    argument where it lies whenever it starts on a 64-byte boundary): the
    call is handed a copy, so whatever is written afterwards — here junk
    over every word, put back before the next step — the block's tokens
    are the reference's."""
    eng = _engine(gpt)
    buf = eng._slot_state.buffer
    handed = eng._decode_args()[4]
    assert not np.shares_memory(handed, buf)
    assert handed.tobytes() == buf.tobytes()
    prompts = _prompts([5, 9], seed=8)
    hs = [eng.submit(p, _greedy(12)) for p in prompts]
    while eng.has_work:
        eng.step()
        kept = buf.copy()
        buf[:] = 0x7f7f7f7f                 # under a block in flight
        time.sleep(0.002)
        buf[:] = kept
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(gpt, p, 12)


def test_a_reseated_slot_takes_the_hosts_token_not_the_devices(gpt):
    """`carried` is set by a dispatch and cleared by a seat: the first
    block of a request reads `tok` (its last prompt token), every later
    one the last token of the block before, whatever `tok` holds."""
    eng = _engine(gpt)
    prompts = _prompts([5, 9, 7], seed=5)
    a, b = (eng.submit(p, _greedy(n)) for p, n in zip(prompts, (4, 12)))
    c = eng.submit(prompts[2], _greedy(6))
    assert not eng._carried.any()
    eng.step()
    assert eng._carried.all() and eng._tok.tolist() == [prompts[0][-1],
                                                        prompts[1][-1]]
    eng._tok[:] = 99                        # stale from here on: unread
    while not a.done:
        eng.step()
    slot = next(s for s in range(2) if s not in eng._slot_req)
    eng.step()                              # c seated where a was
    assert eng._slot_req[slot] is c and eng._tok[slot] == prompts[2][-1]
    eng._tok[1 - slot] = 98
    eng.run()
    for h, p, n in zip((a, b, c), prompts, (4, 12, 6)):
        assert h.tokens == _ref_generate(gpt, p, n)


# ---------------------------------------------------------------------------
# the request ledger books overlapped rounds once
# ---------------------------------------------------------------------------
def test_overlapped_rounds_do_not_book_the_same_seconds_twice(gpt):
    """A round dispatched ahead overlaps the one before it; its wall is
    booked from the LATER of its dispatch and the emission before it, so
    the engine's decode wall stays inside the wall clock of the run."""
    ledger = reqledger.get_ledger()
    assert reqledger.enabled()
    eng = _engine(gpt, decode_block=8)
    prompts = _prompts([5, 9], seed=8)
    for p in prompts:                       # the programs are compiled
        eng.submit(p, _greedy(16))
    eng.run()
    wall0 = ledger.engine_decode_wall_s()
    t0 = time.perf_counter()
    hs = [eng.submit(p, _greedy(48)) for p in prompts]
    eng.run()
    elapsed = time.perf_counter() - t0
    booked = ledger.engine_decode_wall_s() - wall0
    assert eng.stats()['rounds_ahead'] >= 4
    assert 0 < booked <= elapsed
    for h in hs:
        assert 0 < h._ledger_rec.phases['decode'] <= elapsed + 1e-3
