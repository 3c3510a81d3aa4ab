"""ISSUE 36 (a): the per-slot decode state is ONE host buffer and one
argument of every decode and speculation program (ISSUE 45: with one
more flag a slot, `carried`, and a decode block takes the tokens of the
block before beside it). The engine's names
are views into it; the programs unpack it on the device into the values
they always took; the served tokens are the parent's, token for token,
greedy and sampled."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import InferenceEngine, SamplingParams
from paddle_tpu.serving.adapters import AdapterBank, make_adapter_factors
from paddle_tpu.serving.api import FINISHED
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.serving.slot_state import SlotState

NO_EOS = -1
_DTYPES = {'tok': np.int32, 'pos': np.int32, 'steps': np.int32,
           'active': np.bool_, 'temp': np.float32, 'topk': np.int32,
           'topp': np.float32, 'greedy': np.bool_, 'keys': np.uint32,
           'eos': np.int32, 'adapter_rows': np.int32, 'carried': np.bool_}


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _draft():
    paddle.seed(99)
    return GPTForCausalLM(GPTConfig.tiny(num_hidden_layers=1)).eval()


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (s,)).tolist() for s in lens]


def _ref_generate(model, prompt, max_new):
    out, _ = model.generate(
        paddle.to_tensor(np.array([prompt])), max_new_tokens=max_new,
        decode_strategy='greedy_search', eos_token_id=NO_EOS)
    return out.numpy()[0].tolist()


def nine(eng):
    """The nine values the scan takes, as the engine's names hold them."""
    return (eng._tok, eng._pos, eng._steps, eng._active, eng._temp,
            eng._topk, eng._topp, eng._greedy, eng._keys)


def loose_decode_fns(eng):
    """The decode programs as the parent of ISSUE 36 spelled them, the
    slot state nine loose values after the pool: -> (the whole-length
    block, the half-length one), each `(params, frozen, buffers, pool,
    *nine)`."""
    def whole(params, frozen, buffers, pool, tok, *state):
        fwd = cached_forward(eng.model, params, frozen, buffers)
        return eng._decode_scan(fwd, pool, tok, *state)

    def half(params, frozen, buffers, pool, tok, *state):
        fwd = cached_forward(eng.model, params, frozen, buffers)
        return eng._decode_scan(fwd, pool, tok, *state,
                                rows=eng._half_rows)
    return whole, half


def _scramble(state, seed):
    """Every field of `state` set to values of its own."""
    n = state.num_slots
    rng = np.random.default_rng(seed)
    state.tok[:] = rng.integers(0, 128, n)
    state.pos[:] = rng.integers(0, 20, n)
    state.steps[:] = rng.integers(0, 100, n)
    state.temp[:] = rng.random(n) + 0.5
    state.topk[:] = rng.integers(0, 50, n)
    state.topp[:] = rng.random(n) * 0.5 + 0.5
    state.keys[:] = rng.integers(0, 2 ** 32, (n, 2),
                                 dtype=np.uint64).astype(np.uint32)
    state.eos[:] = rng.integers(-1, 128, n)
    state.adapter_rows[:] = rng.integers(0, 4, n)
    state.active[:] = rng.random(n) > 0.4
    state.greedy[:] = rng.random(n) > 0.5
    state.carried[:] = rng.random(n) > 0.5


# ---------------------------------------------------------------------------
# the buffer and its views
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('n', [1, 3, 12, 32])
def test_fields_are_views_and_unpack_returns_them(n):
    """Any slot count (the flags pad to whole words): every field a view
    of the one buffer with the dtype it always had, and `unpack`, under
    jit, what the views hold — dtype, shape and bits."""
    state = SlotState(n)
    assert state.buffer.dtype == np.int32 and state.buffer.ndim == 1
    # what a free slot holds: what the loose arrays were built with
    assert state.temp.tolist() == [1.0] * n == state.topp.tolist()
    assert state.greedy.all() and not state.active.any()
    assert not state.carried.any()
    assert state.eos.tolist() == [-1] * n and not state.keys.any()
    _scramble(state, n)
    views = {name: getattr(state, name) for name in _DTYPES}
    for name, view in views.items():
        assert view.dtype == _DTYPES[name], name
        assert view.shape == ((n, 2) if name == 'keys' else (n,)), name
        assert np.shares_memory(view, state.buffer), name
    # no two fields overlap: together they fill the buffer (less the
    # flags' padding)
    assert sum(v.nbytes for v in views.values()) \
        == state.buffer.nbytes - 3 * (-n % 4)
    assert state.pos[state.active].tolist() == [
        p for p, a in zip(state.pos.tolist(), state.active.tolist()) if a]
    out = jax.jit(state.unpack)(state.buffer)
    assert out._fields[:9] == ('tok', 'pos', 'steps', 'active', 'temp',
                               'topk', 'topp', 'greedy', 'keys')
    for name, view in views.items():
        got = np.asarray(getattr(out, name))
        assert got.dtype == view.dtype and got.shape == view.shape, name
        assert got.tobytes() == view.tobytes(), name


def test_the_engines_names_are_the_buffers_views(gpt):
    """A write through a name is in the buffer, and in what the next
    round's program reads on the device."""
    eng = InferenceEngine(gpt, num_slots=3, max_length=32, decode_block=2)
    buf = eng._slot_state.buffer
    names = dict(zip(('tok', 'pos', 'steps', 'active', 'temp', 'topk',
                      'topp', 'greedy', 'keys'), nine(eng)),
                 eos=eng._eos_arr, adapter_rows=eng._adapter_rows,
                 carried=eng._carried)
    for name, view in names.items():
        assert view.dtype == _DTYPES[name], name
        assert np.shares_memory(view, buf), name
    # the call gets a COPY (ISSUE 45: the engine writes the buffer for
    # the next block while this one may not have started)
    handed = eng._decode_args()[4]
    assert handed is not buf and handed.tobytes() == buf.tobytes()
    before = buf.copy()
    eng._pos[1] = 17
    eng._active[2] = True
    eng._greedy[0] = False
    eng._temp[2] = 0.25
    eng._keys[1] = (7, 2 ** 32 - 1)
    assert (buf != before).sum() == 6
    got = jax.jit(eng._slot_state.unpack)(eng._decode_args()[4])
    assert np.asarray(got.pos).tolist() == [0, 17, 0]
    assert np.asarray(got.active).tolist() == [False, False, True]
    assert np.asarray(got.greedy).tolist() == [False, True, True]
    assert np.asarray(got.temp).tolist() == [1.0, 1.0, 0.25]
    assert np.asarray(got.keys).tolist() == [[0, 0], [7, 2 ** 32 - 1],
                                            [0, 0]]
    assert eng._round_rows() == 16          # `_pos[_active]`, by mask
    assert eng._needed_rows()[0] == 1 * gpt.config.num_hidden_layers


@pytest.mark.parametrize('program', ['decode', 'decode_half'])
def test_the_packed_program_returns_what_the_loose_one_returns(gpt, program):
    """One call of the engine's program on the buffer against the same
    scan on the nine loose arrays (the parent's program, spelled): the
    tokens of every slot, greedy and sampled, and every pool leaf. A
    `carried` slot's pending token is the last of the block before's
    (ISSUE 45), every other slot's the buffer's."""
    eng = InferenceEngine(gpt, num_slots=4, max_length=64, decode_block=4)
    _scramble(eng._slot_state, 5)
    eng._active[:] = [True, True, False, True]
    eng._greedy[:] = [True, False, False, False]
    eng._carried[:] = [True, False, True, False]
    eng._prev_toks = jax.numpy.asarray(
        np.arange(100, 116, dtype=np.int32).reshape(4, 4))
    pending = np.where(eng._carried, [103, 107, 111, 115], eng._tok)
    packed = {'decode': eng._decode_block_fn,
              'decode_half': eng._decode_block_half_fn}[program]
    loose = dict(zip(('decode', 'decode_half'),
                     loose_decode_fns(eng)))[program]
    args = eng._decode_args()
    toks, pool = jax.jit(packed)(*args)
    assert args[5] is eng._prev_toks and len(args) == 6
    want, want_pool = jax.jit(loose)(*args[:4], pending.astype(np.int32),
                                     *nine(eng)[1:])
    assert np.asarray(toks).tolist() == np.asarray(want).tolist()
    assert np.asarray(toks)[2].tolist() == [0] * 4      # inactive
    for a, b in zip(jax.tree_util.tree_leaves(pool),
                    jax.tree_util.tree_leaves(want_pool)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# the served tokens are the parent's
# ---------------------------------------------------------------------------
_MODES = {'row': {}, 'paged': {'kv_page_size': 8}, 'spec': {'spec': True},
          'paged_spec': {'kv_page_size': 8, 'spec': True}}

# what the PARENT of ISSUE 36 (commit f0de272, nine loose arrays) serves
# for `_requests()` below, taken there by the very code of `_serve` in
# each of the four modes: all four emit the same tokens, because a
# sampled slot draws from the pending position's logits with its own key
# and sample index whatever the round's program. jax 0.9.0, CPU, float32
_PARENT_TOKENS = [
    [57, 57, 115, 115, 115, 76, 76, 76, 76, 76, 76, 76, 80, 80, 80, 80, 80,
     80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80,
     80, 80, 76, 76, 76],
    [120, 9, 22, 37, 36, 36, 60, 94],
    [46, 68, 30, 83, 82, 57, 122, 65, 105, 107, 11, 31, 68, 81],
    [67, 67, 76, 57, 57, 36, 36, 15, 76, 90],
    [57, 15, 15, 15, 15, 15]]


def _requests():
    """Five requests for three slots: a greedy answer that crosses row
    32 (both decode programs), three sampled ones with parameters and
    seeds of their own, the shortest of which retires while the others
    decode, and a greedy one admitted into a slot that came free."""
    sample = dict(strategy='sampling', eos_token_id=NO_EOS)
    return list(zip(_prompts([5, 9, 12, 7, 6], seed=21), [
        SamplingParams(max_new_tokens=40, eos_token_id=NO_EOS),
        SamplingParams(max_new_tokens=8, temperature=1.3, top_k=20,
                       top_p=0.9, seed=11, **sample),
        SamplingParams(max_new_tokens=14, temperature=0.7, top_p=0.8,
                       seed=12, **sample),
        SamplingParams(max_new_tokens=10, temperature=2.0, top_k=5,
                       seed=13, **sample),
        SamplingParams(max_new_tokens=6, eos_token_id=NO_EOS)]))


def _serve(gpt, mode, **more):
    kw = dict(_MODES[mode], **more)
    if kw.pop('spec', False):
        kw.update(draft_model=_draft(), num_draft_tokens=3)
    eng = InferenceEngine(gpt, num_slots=3, max_length=64, decode_block=4,
                          buckets=[16, 32, 48], **kw)
    hs = [eng.submit(p, sp) for p, sp in _requests()]
    eng.run()
    assert all(h.status == FINISHED for h in hs)
    return eng, [list(h.tokens) for h in hs]


def _decode_resolves(log):
    spans = [e for e in log.events() if e.get('ph') == 'X']
    dispatch = {e['id'] for e in spans
                if e['name'] == 'serving.decode_dispatch'}
    assert dispatch
    return [e['attrs'] for e in spans
            if e['name'] == 'serving.program_resolve'
            and e['parent'] in dispatch]


@pytest.mark.parametrize('mode', list(_MODES))
def test_a_mixed_batch_serves_the_parents_tokens(gpt, mode, fresh_programs):
    # `fresh_programs`: the store keys a program by the model's class,
    # configuration and avals, and `traces` below counts THIS engine's —
    # an engine of the same shape served earlier in the process (one of
    # tests/test_serving.py's) would hand its programs over untraced
    log = obs.get_event_log()
    log.clear()
    eng, toks = _serve(gpt, mode)
    assert toks == _PARENT_TOKENS
    reqs = _requests()
    for i in (0, 4):                        # the greedy ones: generate()'s
        assert toks[i] == _ref_generate(gpt, reqs[i][0],
                                        reqs[i][1].max_new_tokens)
    stats = eng.stats()
    assert stats['completed'] == 5 and eng.pool.num_slots == 3
    # every round handed its program ONE host array: the buffer
    resolves = _decode_resolves(log)
    assert len(resolves) >= 10
    assert all(a['host_leaves'] == 1 for a in resolves)
    if 'spec' not in mode:                  # the whole and the half program
        rows = {e['attrs']['rows'] for e in log.events()
                if e['name'] == 'serving.decode_round'}
        assert rows == {32, 64}
        traces = stats['traces']
        pre = 'paged_' if mode == 'paged' else ''
        assert traces[pre + 'decode_step'] == 1
        assert traces[pre + 'decode_step_half'] == 1


def test_a_banked_engine_hands_over_one_host_array_too(gpt):
    """The per-slot bank rows ride the buffer; the bank's arrays are on
    the device. Base requests on a banked engine: the parent's tokens."""
    bank = AdapterBank(gpt, capacity=2, rank=4)
    bank.load('ad0', make_adapter_factors(bank, seed=1, scale=0.2),
              version=1)
    log = obs.get_event_log()
    log.clear()
    eng, toks = _serve(gpt, 'row', adapter_bank=bank)
    assert toks == _PARENT_TOKENS
    assert all(a['host_leaves'] == 1 for a in _decode_resolves(log))
    h = eng.submit(_prompts([6], seed=3)[0], SamplingParams(
        max_new_tokens=6, eos_token_id=NO_EOS), adapter_id='ad0')
    eng.step()
    assert eng._adapter_rows.tolist().count(0) == 2     # one slot pinned
    assert int(np.asarray(jax.jit(eng._slot_state.unpack)(
        eng._slot_state.buffer).adapter_rows).max()) == max(
            eng._adapter_rows)
    eng.run()
    assert h.status == FINISHED
