"""The program names its own work (ISSUE 24): one span primitive on the
host clock AND the profiler's timeline, spans at each boundary inside
`engine.step` / `Router.step` / `TrainStep`, and named scopes of one
pinned vocabulary on what is compiled, with the table from an HLO
instruction back to its scope."""
import gc
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import observability as obs
from paddle_tpu import profiler, programs
from paddle_tpu.jit import TrainStep
from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import goodput as goodput_mod
from paddle_tpu.observability.events import EventLog
from paddle_tpu.programs import scopes as scopes_mod
from paddle_tpu.serving import (InferenceEngine, ReplicaSet, Router,
                                SamplingParams)

PKG = os.path.dirname(os.path.abspath(paddle.__file__))


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


@pytest.fixture
def log():
    lg = obs.get_event_log()
    lg.clear()
    return lg


def _spans(log, prefix=''):
    return [e for e in log.events()
            if e.get('ph') == 'X' and e['name'].startswith(prefix)]


def _serve(gpt, n_requests=3, decode_block=2, new_tokens=4, max_length=64):
    router = Router(ReplicaSet(gpt, 1, num_slots=2, max_length=max_length,
                               decode_block=decode_block))
    hs = [router.submit([1, 2, 3, 4, 5 + i], SamplingParams(
        max_new_tokens=new_tokens, eos_token_id=-1))
        for i in range(n_requests)]
    router.run()
    assert all(len(h.tokens) == new_tokens for h in hs)
    return hs


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_records_id_parent_and_request_id(log):
    with obs.span('outer') as outer:
        with obs.span('inner', request_id=41) as inner:
            pass
        with obs.span('second') as second:
            pass
    ev = {e['name']: e for e in log.events()}
    assert ev['outer']['parent'] == 0 and ev['outer']['id'] == outer.id > 0
    assert ev['inner']['parent'] == ev['second']['parent'] == outer.id
    assert len({outer.id, inner.id, second.id}) == 3
    assert ev['inner']['attrs'] == {'request_id': 41}
    assert (ev['outer']['depth'], ev['inner']['depth']) == (1, 2)


def test_span_set_adds_counts_and_a_disabled_span_is_a_no_op(log):
    with obs.span('work', slots=2) as sp:
        sp.set(admitted=3)
    assert log.events()[-1]['attrs'] == {'slots': 2, 'admitted': 3}
    obs.disable()
    try:
        n = len(log)
        with obs.span('off') as sp:
            sp.set(x=1)
        assert len(log) == n and sp.id == 0
    finally:
        obs.enable()


def test_record_span_nests_in_nothing(log):
    t0 = time.perf_counter()
    with obs.span('step') as step:
        obs.record_span('serving.queue', t0, request_id=9)
        with obs.span('child') as child:
            pass
    ev = {e['name']: e for e in log.events()}
    assert ev['serving.queue']['parent'] == 0
    assert ev['serving.queue']['depth'] == 0
    assert ev['serving.queue']['dur'] >= 0
    assert ev['child']['parent'] == step.id     # the queue span is not open


def test_one_span_system():
    """The only call of jax.profiler.TraceAnnotation in the package is
    the span primitive's; RecordEvent is that span plus the profiler's
    host table."""
    hits = []
    for path in glob.glob(os.path.join(PKG, '**', '*.py'), recursive=True):
        with open(path) as f:
            if re.search(r'TraceAnnotation\(', f.read()):
                hits.append(os.path.relpath(path, PKG))
    assert hits == [os.path.join('observability', 'events.py')]


def test_record_event_is_one_span(log):
    with profiler.RecordEvent('user.region'):
        pass
    assert [e['name'] for e in _spans(log)] == ['user.region']
    assert not hasattr(profiler.RecordEvent('x'), '_jax_ctx')


def test_goodput_bookkeeping_clears_under_an_uncategorised_top_span():
    lg = EventLog()
    led = goodput_mod.GoodputLedger(log=lg)
    led.start(reset=True)
    try:
        for _ in range(5):
            with obs.Span('serving.router_step', _log=lg):
                with obs.Span('serving.step', _log=lg):
                    with obs.Span('serving.decode_round', _log=lg):
                        pass
        assert all(not v for v in led._intervals.values())
        assert led.report()['categories']['serving_decode'] > 0
    finally:
        led.stop()


# ---------------------------------------------------------------------------
# the serving step
# ---------------------------------------------------------------------------

def test_serving_spans_nest_and_carry_scalar_counts(gpt, log):
    _serve(gpt)
    spans = _spans(log, 'serving.')
    by_id = {e['id']: e for e in spans}
    names = {e['name'] for e in spans}
    assert {'serving.router_step', 'serving.reap', 'serving.step',
            'serving.admit', 'serving.prefill', 'serving.decode_round',
            'serving.decode_dispatch', 'serving.d2h', 'serving.emit',
            'serving.queue'} <= names

    def parent(e):
        return by_id[e['parent']]['name'] if e['parent'] else None
    want = {'serving.step': 'serving.router_step',
            'serving.reap': 'serving.router_step',
            'serving.admit': 'serving.step',
            'serving.prefill': 'serving.admit',
            'serving.decode_round': 'serving.step',
            'serving.settle': 'serving.step',
            'serving.decode_dispatch': 'serving.decode_round',
            'serving.emit': 'serving.step'}
    for e in spans:
        if e['name'] in want:
            assert parent(e) == want[e['name']], e
        if e['name'] == 'serving.d2h':
            assert parent(e) in ('serving.decode_round', 'serving.settle')
    for e in spans:     # scalars only: no list or dict rides a span
        assert all(isinstance(v, (int, float, str)) for v in
                   (e.get('attrs') or {}).values()), e
    # ISSUE 45: ONE `serving.decode_round` a block dispatched, with the
    # block's counts and whether it was dispatched AHEAD of the fetch of
    # the one in flight; it holds one dispatch and at most one fetch,
    # and says `discarded` of the block it fetched. A step that only
    # fetches the block in flight does so under `serving.settle`
    every = [e for e in spans if e['name'] == 'serving.decode_round']
    dispatched = {'ahead', 'active', 'slots', 'real_rows', 'needed_rows',
                  'read_rows', 'rows'}
    for e in every:
        kids = sorted(k['name'] for k in spans if k['parent'] == e['id'])
        assert kids in (['serving.decode_dispatch'],
                        ['serving.d2h', 'serving.decode_dispatch']), kids
        assert set(e['attrs']) == dispatched | (
            {'discarded'} if 'serving.d2h' in kids else set()), e
        assert e['attrs']['ahead'] in (0, 1)
        if e['attrs']['ahead']:
            d, f = (next(k for k in spans if k['parent'] == e['id']
                         and k['name'] == n)
                    for n in ('serving.decode_dispatch', 'serving.d2h'))
            assert d['ts'] + d['dur'] <= f['ts'] + 1e-9     # dispatch FIRST
        assert e['attrs'].get('discarded', 0) == 0          # no EOS here
    # three requests of two blocks in two slots: both slots busy and
    # nobody ending inside the first block, so its successor ran ahead
    assert sum(e['attrs']['ahead'] for e in every) >= 1
    settles = [e for e in spans if e['name'] == 'serving.settle']
    assert settles and all(
        set(e['attrs']) == {'discarded'} and [
            k['name'] for k in spans if k['parent'] == e['id']]
        == ['serving.d2h'] for e in settles)
    # every block dispatched was fetched, under one or the other
    assert len(every) == len(settles) + sum(
        'discarded' in e['attrs'] for e in every)
    rounds = [{k: v for k, v in e['attrs'].items()
               if k not in ('ahead', 'discarded')} for e in every]
    # ten rows of 64 at most: the half-length program, on both layers
    assert all(a['rows'] == 32 and a['read_rows'] == 2 * 32 * 2
               for a in rounds)
    assert all(a['slots'] == 2 and 1 <= a['active'] <= 2 for a in rounds)
    # by the pool's own book: a seated request holds its prompt's rows
    assert all(a['real_rows'] >= 5 * a['active'] for a in rounds)
    assert sum(e['attrs']['admitted'] for e in spans
               if e['name'] == 'serving.admit') == 3
    # a gpt prefill attends against the row it writes: no pairs of an
    # own-tokens attention to say (a latent engine's carry both:
    # `tests/test_deepseek_v3.py`)
    assert all(set(e['attrs']) == {'request_id', 'bucket', 'slot',
                                   'prompt_len'}
               for e in spans if e['name'] == 'serving.prefill')
    # a count rides a span only where a metric reads it
    for name in ('serving.emit', 'serving.reap', 'serving.router_step',
                 'serving.step'):
        assert all(not e.get('attrs') for e in spans
                   if e['name'] == name), name
    # one request's spans share its identifier
    rid = [e['attrs']['request_id'] for e in spans
           if e['name'] == 'serving.queue']
    assert sorted(rid) == sorted(e['attrs']['request_id'] for e in spans
                                 if e['name'] == 'serving.prefill')


def test_a_program_call_is_two_spans_under_dispatch_and_prefill(gpt, log):
    """ISSUE 35: `StoredJit.__call__` opens `serving.program_resolve`
    (the signature and the lookup, with the leaves it flattened and
    those that are host arrays) and `serving.program_call` (the
    executable's call), under whatever span the engine had open."""
    _serve(gpt)
    spans = _spans(log, 'serving.')
    by_id = {e['id']: e for e in spans}
    resolves = [e for e in spans if e['name'] == 'serving.program_resolve']
    calls = [e for e in spans if e['name'] == 'serving.program_call']
    assert len(resolves) == len(calls) > 0
    parents = {by_id[e['parent']]['name'] for e in resolves + calls}
    assert parents == {'serving.decode_dispatch', 'serving.prefill'}
    assert all(not e.get('attrs') for e in calls)
    assert all(set(e['attrs']) == {'leaves', 'host_leaves'}
               for e in resolves)
    # by hand, on the toy GPT: 28 parameters, K and V of two layers, and
    # the slot state, ONE numpy buffer since ISSUE 36 (nine arrays
    # before: tok, pos, steps, active, temp, topk, topp, greedy, keys),
    # and since ISSUE 45 the tokens of the block before, the device's;
    # nothing frozen, no buffer, no adapter
    decode = [e['attrs'] for e in resolves
              if by_id[e['parent']]['name'] == 'serving.decode_dispatch']
    assert decode and all(a == {'leaves': 28 + 4 + 1 + 1, 'host_leaves': 1}
                          for a in decode)
    # a prefill calls two programs: the prefill itself (the parameters
    # and the ids, on the device) and the seat of its row (the pool's
    # four leaves, the row's four, and the slot, a Python int)
    prefill = [e['attrs'] for e in resolves
               if by_id[e['parent']]['name'] == 'serving.prefill']
    assert {(a['leaves'], a['host_leaves']) for a in prefill} \
        == {(28 + 1, 0), (4 + 4 + 1, 1)}
    # the dispatch's two children leave it a self time, not a hole: they
    # lie inside it, resolve before call
    for d in (e for e in spans if e['name'] == 'serving.decode_dispatch'):
        kids = sorted((e for e in resolves + calls if e['parent'] == d['id']),
                      key=lambda e: e['ts'])
        assert [e['name'] for e in kids] == ['serving.program_resolve',
                                             'serving.program_call']
        assert d['ts'] <= kids[0]['ts']
        assert kids[0]['ts'] + kids[0]['dur'] <= kids[1]['ts'] + 1e-9
        assert kids[1]['ts'] + kids[1]['dur'] <= d['ts'] + d['dur'] + 1e-9


def test_children_of_serving_step_cover_its_wall(gpt, log):
    # a step long enough for the bound: thirty-two sub-steps a block (a
    # block of eight on the toy model is 2 ms, where the step's own
    # 0.1 ms of bookkeeping read 0.944-0.955 from run to run; since
    # ISSUE 45 two blocks in two busy slots are THREE steps — a dispatch,
    # a dispatch ahead with a fetch, a fetch — so sixteen read 0.944-0.951
    # and thirty-two 0.967-0.971). A step that runs ahead no longer sits
    # in a fetch for most of its wall, so the thread losing its core for
    # 3 ms between two spans — beside five other workers it read 0.79
    # once — shows: the bound is the program's, so the best of three
    # serves is held to it
    kw = dict(n_requests=4, decode_block=32, new_tokens=64, max_length=128)
    _serve(gpt, **kw)               # warm: the programs are compiled
    shares = []
    for _ in range(3):
        log.clear()
        _serve(gpt, **kw)
        spans = _spans(log, 'serving.')
        steps = [e for e in spans if e['name'] == 'serving.step']
        covered = sum(e['dur'] for e in spans
                      if e['parent'] in {s['id'] for s in steps})
        shares.append(covered / sum(s['dur'] for s in steps))
        if shares[-1] >= 0.95:
            break
    assert max(shares) >= 0.95, shares


def _spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def _collect(seconds):
    junk = []
    for _ in range(200_000):        # cycles only the collector frees
        a, b = [], []
        a.append(b)
        b.append(a)
        junk.append(a)
    del junk, a, b
    gc.collect()
    time.sleep(seconds)


@pytest.mark.parametrize('stall', [time.sleep, _spin, _collect, None],
                         ids=['asleep', 'spinning', 'collecting', 'fast'])
def test_a_slow_router_step_keeps_a_record_of_what_it_fell_under(
        gpt, log, stall, monkeypatch, caplog):
    """ISSUE 35: a router step over `SLOW_STEP_S` emits ONE
    `serving_slow_step` with the span of the largest self time inside
    it, the thread's CPU time, the collector's time and the programs
    built; a fast step emits nothing. The stall is patched into the emit
    path, inside `serving.emit`, on the last step."""
    from paddle_tpu.serving import router as router_mod
    _serve(gpt)                     # the programs are compiled
    log.clear()
    caplog.clear()
    family = obs.get_registry().get('paddle_serving_slow_steps_total')
    before = family.total()
    emit = InferenceEngine._emit_round
    left = [3]                      # the rounds of one request of 6 tokens

    def stalled(self, *args):
        left[0] -= 1
        if stall is not None and not left[0]:
            stall(router_mod.SLOW_STEP_S + 0.1)
        return emit(self, *args)
    monkeypatch.setattr(InferenceEngine, '_emit_round', stalled)
    with caplog.at_level('WARNING', logger=router_mod.__name__):
        _serve(gpt, n_requests=1, new_tokens=6)
    assert left[0] == 0
    records = [e for e in log.events() if e['name'] == 'serving_slow_step']
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith('serving_slow_step: ')]
    if stall is None:
        assert not records and not lines and family.total() == before
        return
    assert len(records) == len(lines) == 1 and family.total() == before + 1
    rec = records[0]['attrs']
    assert set(rec) == {'dur_s', 'under', 'under_s', 'cpu_s', 'gc_s', 'live',
                        'admitted', 'built'}
    assert all(isinstance(v, (int, float, str)) for v in rec.values())
    assert rec['under'] == 'serving.emit'
    assert router_mod.SLOW_STEP_S < rec['under_s'] <= rec['dur_s']
    assert (rec['live'], rec['admitted'], rec['built']) == (0, 0, 0)
    assert all(f'{k}={v}' in lines[0] for k, v in rec.items())
    assert obs.get_registry().value('paddle_serving_slow_steps_total',
                                    under='serving.emit') >= 1
    # (No CPU time is held against the wall clock: beside five other
    # workers a spin of 0.61 s got 0.43 s of a core, and the collector's
    # pause, timed on the wall, read 7.2 s against 4.3 s of CPU.)
    if stall is time.sleep:         # the thread was not running
        assert rec['cpu_s'] < 0.1 and rec['gc_s'] < 0.1
    elif stall is _spin:            # Python held it: many times a sleep's
        assert rec['cpu_s'] > 0.2
    else:                           # the collector's part is told apart
        assert rec['gc_s'] > 0
        snap = {m['name']: m for m in obs.get_registry().snapshot()['metrics']}
        assert snap['paddle_gc_pause_seconds_total']['samples'][0]['value'] \
            >= rec['gc_s']


def test_a_slow_step_that_seats_several_requests_falls_under_prefill(
        gpt, log, monkeypatch):
    """`under` is the NAME with the largest self time, summed over its
    spans: a step that seats two requests, each prefill a little over
    half the constant, is under `serving.prefill` with both."""
    from paddle_tpu.serving import router as router_mod
    _serve(gpt)
    log.clear()
    seat = InferenceEngine._prefill_row

    def slow_seat(self, *args):
        time.sleep(0.5 * router_mod.SLOW_STEP_S + 0.05)
        return seat(self, *args)
    monkeypatch.setattr(InferenceEngine, '_prefill_row', slow_seat)
    _serve(gpt, n_requests=2)
    rec, = [e['attrs'] for e in log.events()
            if e['name'] == 'serving_slow_step']
    assert (rec['under'], rec['admitted']) == ('serving.prefill', 2)
    longest = max(e['dur'] for e in _spans(log, 'serving.prefill'))
    assert longest < router_mod.SLOW_STEP_S < rec['under_s'] <= rec['dur_s']


def test_spec_round_carries_the_same_children(gpt, log):
    paddle.seed(11)
    draft = GPTForCausalLM(GPTConfig.tiny(num_hidden_layers=1)).eval()
    eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                          draft_model=draft, num_draft_tokens=2)
    eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=4,
                                            eos_token_id=-1))
    eng.run()
    spans = _spans(log, 'serving.')
    rounds = {e['id']: e for e in spans if e['name'] == 'serving.spec_round'}
    assert rounds and all(
        {'active', 'slots', 'real_rows', 'k'} == set(e['attrs'])
        for e in rounds.values())
    kids = {e['name'] for e in spans if e['parent'] in rounds}
    assert kids == {'serving.decode_dispatch', 'serving.d2h'}


# ---------------------------------------------------------------------------
# the train step and the scopes
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def toy_step():
    paddle.seed(3)
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits[:, :-1].reshape([-1, cfg.vocab_size]),
            labels[:, 1:].reshape([-1]))
    step = TrainStep(model, loss_fn, opt)
    ids = np.random.RandomState(0).randint(0, 128, (2, 16)).astype('int32')
    step(ids, ids)
    return step, ids


def test_train_step_spans(toy_step, log):
    step, ids = toy_step
    step(ids, ids)
    ev = {e['name']: e for e in _spans(log, 'train.')}
    assert set(ev) == {'train.step', 'train.dispatch', 'train.writeback',
                       'train.program_resolve', 'train.program_call'}
    assert ev['train.dispatch']['parent'] == ev['train.step']['id']
    # the jitted step's call is the two spans of every stored program,
    # named by the wrapper's kind; by hand: 28 parameters, AdamW's two
    # moments of each and its step count, the key, the rate, ids and
    # labels, every one a device array
    for name in ('train.program_resolve', 'train.program_call'):
        assert ev[name]['parent'] == ev['train.dispatch']['id']
    assert len(jax.tree_util.tree_leaves(step._opt_state)) == 2 * 28 + 1
    assert ev['train.program_resolve']['attrs'] == {
        'leaves': 28 + (2 * 28 + 1) + 4, 'host_leaves': 0}
    assert ev['train.writeback']['parent'] == ev['train.step']['id']
    assert (ev['train.dispatch']['dur'] + ev['train.writeback']['dur']
            >= 0.95 * ev['train.step']['dur'])


def _construct_seconds():
    return obs.get_registry().value('paddle_setup_seconds_total',
                                    phase='construct')


def test_an_engines_construction_is_one_span_booked_as_set_up(gpt, log):
    """`serving.engine_init` around `InferenceEngine.__init__` — the
    pool's allocation, the slot state, the wrappers — with what it made
    as scalars, and its wall under `phase="construct"`."""
    before = _construct_seconds()
    eng = InferenceEngine(gpt, num_slots=3, max_length=64, decode_block=2)
    (ev,) = _spans(log, 'serving.engine_init')
    assert ev['attrs'] == {'slots': 3, 'max_length': 64,
                           'pool_bytes': eng.pool.pool_bytes,
                           'programs_resolved': 0}
    assert eng.pool.pool_bytes > 0 and ev['parent'] == 0
    assert _construct_seconds() - before == pytest.approx(ev['dur'])
    # nothing is built by constructing: the programs come with the
    # first step, inside `serving.router_step`
    assert not _spans(log, 'serving.program_')
    # every replica of a set constructs its own engine
    Router(ReplicaSet(gpt, 2, num_slots=2, max_length=64, decode_block=2))
    assert len(_spans(log, 'serving.engine_init')) == 3
    assert _construct_seconds() - before == pytest.approx(
        sum(e['dur'] for e in _spans(log, 'serving.engine_init')))


def test_a_train_steps_construction_is_one_span_booked_as_set_up(log):
    before = _construct_seconds()
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig.tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    TrainStep(model, lambda out, lab: out.mean(), opt)
    (ev,) = _spans(log, 'train.step_init')
    assert ev['parent'] == 0 and 'attrs' not in ev
    assert _construct_seconds() - before == pytest.approx(ev['dur'])
    assert _spans(log, 'train.') == [ev]        # constructing runs nothing


def test_a_disabled_constructor_span_books_nothing(gpt, log):
    before = _construct_seconds()
    obs.disable()
    try:
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2)
    finally:
        obs.enable()
    assert eng.pool.num_slots == 2
    assert not _spans(log, 'serving.engine_init')
    assert _construct_seconds() == before


def test_the_import_is_booked_once_as_set_up():
    """`paddle_tpu/__init__.py` reads the clock at its first and last
    line: the package's own body and whatever it was first to import."""
    fam = obs.get_registry().get('paddle_setup_seconds_total')
    assert fam is not None and fam.labelnames == ('phase',)
    phases = {key[0]: child.value for key, child in fam.children()}
    assert set(phases) <= {'import', 'construct'}
    assert 0 < phases['import'] < 600
    src = open(os.path.join(PKG, '__init__.py')).read().strip().splitlines()
    assert '_T_IMPORT' in src[-1] and "'import'" in src[-2]


def test_scope_vocabulary_is_pinned(toy_step, gpt):
    """Every name of the vocabulary is on the toy train step's or the
    decode program's HLO, and no scope of the models' is outside it."""
    assert scopes_mod.SCOPES == (
        'embed', 'attention', 'mlp', 'norm', 'lm_head', 'loss', 'sample',
        'kv_write', 'optimizer', 'moe/router', 'moe/experts', 'moe/shared',
        'conv', 'state_write', 'latent_absorb', 'mhc', 'kda', 'ssm')
    # the expert layer's three are on an expert model's decode program
    # (tests/test_afmoe.py::test_expert_scopes_are_on_the_decode_program),
    # the short convolution's two on a hybrid's (below), the absorbed
    # products' on a latent-attention model's (tests/test_deepseek_v3.py),
    # the hyper-connections' on a model with a residual path of several
    # streams (tests/test_xing4.py), the KDA layers' on a model with a
    # matrix state (tests/test_ling3_serving.py), the state-space layers'
    # on a model with a diagonal one (tests/test_jamba_serving.py)
    moe = {s for s in scopes_mod.SCOPES if s.startswith('moe/')} \
        | {'conv', 'state_write', 'latent_absorb', 'mhc', 'kda', 'ssm'}
    _serve(gpt, n_requests=1)
    table = programs.scope_table()
    assert 'train_step' in table and 'serving.decode_block' in table
    found = {}
    for prog in ('train_step', 'serving.decode_block'):
        found[prog] = {scope for op, *_ in table[prog].values()
                       for scope in programs.scope_path(op)[-1:]}
    assert found['train_step'] == {'embed', 'attention', 'mlp', 'norm',
                                   'lm_head', 'loss', 'optimizer'}
    assert found['serving.decode_block'] >= {'sample', 'kv_write',
                                             'attention', 'lm_head'}
    # (minus `moe`: the store's table is by program NAME, and an expert
    # model's decode block may have been served in this process)
    assert (found['train_step'] | found['serving.decode_block']) - moe \
        == set(scopes_mod.SCOPES) - moe
    ops = [op for op, *_ in table['train_step'].values()]
    assert any('transpose(jvp(mlp))' in op for op in ops)      # backward
    assert any('jvp(mlp)' in op and 'transpose' not in op for op in ops)
    # every instruction with an op_name joins by its name; shapes ride
    assert any(shape.startswith('f32_') for _, shape, *_ in
               table['train_step'].values())
    # ... and each says how it came by its name
    assert {how for *_, how in table['train_step'].values()} \
        <= {'own', 'callee', 'user', 'operand', 'caller', ''}


def test_conv_scopes_are_placed_on_a_hybrids_decode_program():
    """`conv` and `state_write`: on the decode program of a model with
    conv layers, every instruction under them placed by a name of its
    own or of what it holds; README documents the counter and the three
    counts that came with them, and the expert layer's counts and
    counters, the kernel's among them."""
    from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
    assert {'conv', 'state_write'} <= set(scopes_mod.SCOPES)
    paddle.seed(11)
    _serve(Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny()).eval(), n_requests=2)
    rows = programs.scope_table()['serving.decode_block'].values()
    how = {}
    for op, _, *more in rows:
        path = programs.scope_path(op)
        if 'conv' in path:
            how.setdefault(path[-1], set()).add((tuple(more) + ((), 'own'))[1])
    assert {'conv', 'state_write'} <= set(how)
    assert how['state_write'] <= {'own', 'callee'}
    with open(os.path.join(os.path.dirname(PKG), 'README.md')) as f:
        readme = f.read()
    for word in ('slot_state_bytes_total', '`attn_layers`', '`state_layers`',
                 '`state_bytes`', 'moe_expert_kernel_substeps_total',
                 '`expert_kernel_substeps`', 'moe_experts_touched_total',
                 '`experts_touched`', '`expert_layer_substeps`'):
        assert word in readme, word


def test_scope_path_lists_the_vocabulary_scopes_outermost_first():
    assert programs.scope_path('jit(step_fn)/jvp(mlp)/dot_general') \
        == ('mlp',)
    assert programs.scope_path(
        'jit(f)/transpose(jvp(attention))/kv_write/scatter') \
        == ('attention', 'kv_write')
    assert programs.scope_path(
        'jit(f)/jvp(attention)/jit(flash_attention)/pallas_call') \
        == ('attention',)
    assert programs.scope_path('jit(f)/mul') == ()
    assert programs.scope_path('') == ()


_HLO = '''HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %multiply.3 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(f)/jvp(mlp)/mul" source_file="x.py" source_line=3}
  ROOT %add.4 = f32[4]{0} add(%multiply.3, %param_0.1), metadata={op_name="jit(f)/optimizer/add"}
}

%body.7 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.8 = f32[4]{0} get-tuple-element(%p), index=1
  %fusion.9 = f32[4]{0} fusion(%get-tuple-element.8), kind=kLoop, calls=%fused_computation.nameless
  ROOT %tuple.10 = (s32[], f32[4]{0}) tuple(%get-tuple-element.8, %fusion.9)
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %copy-start.6 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.1)
  %copy-done.6 = f32[4]{0} copy-done(%copy-start.6)
  %while.11 = (s32[], f32[4]{0}) while(%copy-done.6), condition=%cond.12, body=%body.7, metadata={op_name="jit(f)/attention/kv_write/scatter"}
  ROOT %fusion.2 = (bf16[8,128]{1,0:T(8,128)(2,1)}, f32[]) fusion(%copy-done.6), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/transpose(jvp(mlp))/dot_general"}
}
'''


def test_parse_hlo_scopes_says_how_each_instruction_came_by_its_name():
    t = scopes_mod.parse_hlo_scopes(_HLO)
    # its own metadata; a fusion without any counts under its root's
    # ... and holds, inside, instructions of two scopes
    assert t['fusion.2'] == ('jit(f)/transpose(jvp(mlp))/dot_general',
                             'bf16_8_128', ('mlp', 'optimizer'), 'own')
    assert t['fusion.1'] == ('jit(f)/optimizer/add', 'f32_4',
                             ('mlp', 'optimizer'), 'callee')
    assert t['multiply.3'][::3] == ('jit(f)/jvp(mlp)/mul', 'own')
    # borrowed, and marked so — a copy the compiler made: what consumes
    # its result names it
    assert t['copy-start.6'][::3] == t['copy-done.6'][::3] \
        == ('jit(f)/attention/kv_write/scatter', 'user')
    # the body of the loop a scatter became: the loop's name
    assert t['fusion.9'] == ('jit(f)/attention/kv_write/scatter', 'f32_4',
                             (), 'caller')
    # an argument's name is not an op_name; its user's is
    assert t['Arg_0.1'] == ('jit(f)/optimizer/add', 'f32_4', (), 'user')
    # nothing within reach: no name, and no how
    alone = scopes_mod.parse_hlo_scopes(
        'ENTRY %m (a: f32[4]) -> f32[4] {\n'
        '  ROOT %copy.1 = f32[4]{0} copy(%a)\n}\n')
    assert alone['copy.1'] == ('', 'f32_4', (), '')


def test_every_pallas_kernel_states_its_name():
    with open(os.path.join(PKG, 'ops', 'pallas_kernels.py')) as f:
        src = f.read()
    calls = src.split('pl.pallas_call(')[1:]
    names = [re.search(r"\bname='(\w+)'", c.split(')(', 1)[0]) for c in calls]
    assert all(names), 'a pallas_call without a stated name'
    assert [m.group(1) for m in names] == [
        'flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv', 'rms_norm_fwd',
        'ce_fwd', 'ce_bwd', 'paged_attention', 'adapter_matmul',
        'moe_decode_experts', 'mla_decode_attention',
        'kv_decode_attention', 'kda_decode_step', 'ssm_prefill_scan',
        'moe_grouped_experts']


def test_named_kernel_reaches_the_lowered_program():
    """The stated name is on the lowered custom call (interpret mode on
    a CPU lowers no custom call, so this reads the jaxpr's params)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as K
    x = jnp.ones((16, 256), jnp.float32)
    lab = jnp.zeros((16,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: K.softmax_cross_entropy_fwd(a, b, interpret=True))(x, lab)
    eqns = [e for e in jaxpr.jaxpr.eqns if 'pallas' in e.primitive.name]
    assert eqns and 'ce_fwd' in str(eqns[0].params)
