"""paddle_tpu.serving — continuous-batching engine over the slot pool.

Covers the ISSUE-4 acceptance surface: mixed-length greedy parity vs
per-request generate() (token for token), mid-flight admission into
freed slots with ZERO recompiles (python trace counters + the
jax.monitoring compile counter), eos retirement freeing slots,
per-request sampling params, request-level fault isolation, streaming,
scheduler FCFS/budget behavior, the kv-pool primitives, metrics, and
the two generation.py satellites (lax.top_k logits parity, max_length
clamp semantics).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import debug, observability as obs
from paddle_tpu.nlp import (GPTConfig, GPTForCausalLM, LlamaConfig,
                            LlamaForCausalLM)
from paddle_tpu.nlp import generation
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.resilience import FatalError, RetryPolicy, TransientError
from paddle_tpu.serving import (FAILED, FINISHED, FCFSScheduler,
                                InferenceEngine, RequestHandle,
                                SamplingParams, SlotPool, default_buckets)
from paddle_tpu.serving import engine as engine_mod

from fault_injection import FaultInjector

NO_EOS = -1
_NO_SLEEP = RetryPolicy(base_delay=0.0, sleep=lambda d: None)


@pytest.fixture(scope='module')
def gpt():
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (s,)).tolist() for s in lens]


def _ref_generate(model, prompt, max_new, eos=NO_EOS):
    out, _ = model.generate(
        paddle.to_tensor(np.array([prompt])), max_new_tokens=max_new,
        decode_strategy='greedy_search', eos_token_id=eos)
    return out.numpy()[0].tolist()


def _trim_at_eos(tokens, eos):
    if eos in tokens:
        return tokens[:tokens.index(eos) + 1]
    return tokens


# ---------------------------------------------------------------------------
# satellite: _process_logits via lax.top_k — parity with the old sort path
# ---------------------------------------------------------------------------

def _old_process_logits(logits, temperature, top_k, top_p):
    """The pre-lax.top_k implementation (full jnp.sort), verbatim."""
    neg = float(jnp.finfo(jnp.float32).min)
    logits = logits.astype(jnp.float32)
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    v = logits.shape[-1]
    if top_k and 0 < top_k < v:
        kth = jnp.sort(logits, axis=-1)[:, v - top_k][:, None]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p and top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum((cum - probs) < top_p, axis=-1) - 1
        cutoff = jnp.take_along_axis(srt, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


@pytest.mark.parametrize('temp,top_k,top_p', [
    (1.0, 5, 1.0), (0.7, 12, 1.0), (1.0, 0, 0.9), (1.3, 8, 0.75),
    (1.0, 1, 1.0), (1.0, 64, 0.5), (2.0, 63, 0.99),
])
def test_process_logits_topk_lax_parity(temp, top_k, top_p):
    rng = np.random.RandomState(3)
    logits = rng.standard_normal((4, 64)).astype(np.float32)
    logits[0, :8] = logits[0, 8]          # duplicated values (sort ties)
    new = generation._process_logits(jnp.asarray(logits), temp, top_k,
                                     top_p)
    old = _old_process_logits(jnp.asarray(logits), temp, top_k, top_p)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# ---------------------------------------------------------------------------
# satellite: generate(max_length=) no longer decodes past the prompt
# ---------------------------------------------------------------------------

def test_max_length_met_warns_once_and_returns_empty(gpt):
    generation._warned_max_length[0] = False
    ids = paddle.to_tensor(np.array([[3, 5, 7, 9, 11]]))
    with pytest.warns(UserWarning, match='already meets max_length'):
        out, scores = gpt.generate(ids, max_length=4)
    assert tuple(out.shape) == (1, 0)
    assert tuple(scores.shape) == (1,)
    with warnings.catch_warnings():
        warnings.simplefilter('error')    # second call: warn ONCE only
        out, _ = gpt.generate(ids, max_length=5)
    assert tuple(out.shape) == (1, 0)


def test_max_length_budget_still_decodes_to_total_length(gpt):
    ids = paddle.to_tensor(np.array([[3, 5, 7, 9, 11]]))
    out, _ = gpt.generate(ids, max_length=9, eos_token_id=NO_EOS)
    assert tuple(out.shape) == (1, 4)     # 9 total - 5 prompt
    ref = _ref_generate(gpt, [3, 5, 7, 9, 11], 4)
    assert out.numpy()[0].tolist() == ref


# ---------------------------------------------------------------------------
# kv_pool
# ---------------------------------------------------------------------------

def test_default_buckets_cover_max_length():
    assert default_buckets(64) == (8, 16, 32, 64)
    assert default_buckets(48) == (8, 16, 32, 48)


def test_slot_pool_alloc_free_cycle(gpt):
    pool = SlotPool(gpt, num_slots=3, max_length=32)
    slots = [pool.alloc() for _ in range(3)]
    assert slots == [0, 1, 2] and pool.free_count == 0
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.free(1)
    assert pool.alloc() == 1              # lowest free slot reused
    with pytest.raises(ValueError):
        pool.free(99)
    pool.free(0)
    with pytest.raises(ValueError):
        pool.free(0)                      # double free


def test_slot_pool_bucket_for(gpt):
    pool = SlotPool(gpt, num_slots=2, max_length=64)
    assert pool.bucket_for(3) == 8
    assert pool.bucket_for(8) == 8
    assert pool.bucket_for(9) == 16
    assert pool.bucket_for(64) == 64
    with pytest.raises(ValueError):
        pool.bucket_for(65)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _ones_row(model, max_length, value=1.0, dtype=None):
    return jax.tree_util.tree_map(
        lambda c: jnp.full((1,) + c.shape[1:], value, dtype or c.dtype),
        model.init_cache(1, max_length))


def test_slot_pool_set_row_writes_one_slot(gpt):
    """Seating a row changes that slot bit-exactly and no other: the
    pool is one stacked array per leaf and the seat program writes one
    row of it."""
    pool = SlotPool(gpt, num_slots=3, max_length=16)
    rng = np.random.RandomState(0)

    def random_row():
        return jax.tree_util.tree_map(
            lambda c: jnp.asarray(rng.randn(1, *c.shape[1:]), c.dtype),
            gpt.init_cache(1, 16))

    for i in range(3):                      # distinct, non-zero rows
        pool.set_row(i, random_row())
    before = [np.asarray(leaf) for leaf in _leaves(pool.cache)]
    slab = random_row()
    pool.set_row(1, slab)
    assert pool.stats()['row_writes'] == 4
    for was, now, row in zip(before, _leaves(pool.cache), _leaves(slab)):
        now = np.asarray(now)
        assert now.shape == was.shape            # still [slots, ...]
        assert np.array_equal(now[1:2], np.asarray(row))
        assert np.array_equal(now[0], was[0])
        assert np.array_equal(now[2], was[2])
    for got, row in zip(_leaves(pool.row(1)), _leaves(slab)):
        assert got.shape == row.shape
        assert np.array_equal(np.asarray(got), np.asarray(row))


def test_slot_pool_set_row_casts_to_the_pool_dtype(gpt):
    """A float32 slab lands in a bfloat16 pool (the cast is part of the
    seat program) without disturbing the other slots."""
    pool = SlotPool(gpt, num_slots=2, max_length=16, dtype='bfloat16')
    slab = _ones_row(gpt, 16, 1.5, jnp.float32)
    pool.set_row(1, slab)
    for leaf in _leaves(pool.cache):
        assert leaf.dtype == jnp.bfloat16
        got = np.asarray(leaf.astype(jnp.float32))
        assert (got[1] == 1.5).all() and (got[0] == 0).all()


def test_slot_pool_copy_slot_is_an_independent_copy(gpt):
    pool = SlotPool(gpt, num_slots=3, max_length=16)
    pool.set_row(0, _ones_row(gpt, 16))
    pool.copy_slot(0, 2)
    k = np.asarray(_leaves(pool.cache)[0])
    assert (k[2] == 1).all() and (k[0] == 1).all() and (k[1] == 0).all()
    # a REAL copy: rewriting the source leaves the destination alone
    pool.set_row(0, _ones_row(gpt, 16, 3.0))
    k = np.asarray(_leaves(pool.cache)[0])
    assert (k[0] == 3).all() and (k[2] == 1).all()
    st = pool.stats()
    assert st['row_copies'] == 1 and st['row_writes'] == 2
    assert st['copied_bytes'] == st['row_bytes']
    assert st['pool_bytes'] == 3 * st['row_bytes']


def test_slot_pool_programs_compile_once_for_all_slots(gpt):
    """The slot index is a traced scalar: seat, copy and slice each
    trace and compile ONCE, whatever slots they are called with."""
    pool = SlotPool(gpt, num_slots=4, max_length=24)   # shapes of its own
    slab = _ones_row(gpt, 24)
    reg = obs.get_registry()
    pool.set_row(0, slab)
    pool.copy_slot(0, 1)
    pool.row(0)
    assert dict(pool.traces) == {'prefill_seat_row': 1,
                                 'prefill_copy_row': 1,
                                 'prefill_slice_row': 1}
    compiles = reg.value('paddle_jit_compiles_total')
    for slot in (1, 2, 3, 0):
        pool.set_row(slot, slab)
        pool.copy_slot(slot, (slot + 1) % 4)
        assert (np.asarray(_leaves(pool.row(slot))[0]) == 1).all()
    assert reg.value('paddle_jit_compiles_total') == compiles
    assert sum(pool.traces.values()) == 3


def test_slot_pool_rows_are_the_device_buffers(gpt):
    """`pool.rows` is the stacked pytree itself — 2 x layers leaves,
    however many slots — so deleting its leaves frees the pool (what the
    benchmark does before its reference pass)."""
    layers = gpt.config.num_hidden_layers
    pool = SlotPool(gpt, num_slots=5, max_length=16)
    pool.set_row(3, _ones_row(gpt, 16))
    leaves = _leaves(pool.rows)
    assert len(leaves) == 2 * layers
    assert sum(leaf.nbytes for leaf in leaves) == pool.pool_bytes
    assert all(leaf.shape[0] == 5 for leaf in leaves)
    for a, b in zip(leaves, _leaves(pool.cache)):
        assert a is b
    for leaf in leaves:
        leaf.delete()
    assert all(leaf.is_deleted() for leaf in _leaves(pool.cache))
    pool.reset_rows()                       # and it can be rebuilt
    assert not any(np.asarray(leaf).any() for leaf in _leaves(pool.rows))


def _decode_args(eng):
    """The decode programs' arguments, spelled: the slot state is ONE
    buffer where a run of nine arrays stood (ISSUE 36), then the tokens
    of the block before, the device's (ISSUE 45)."""
    return (eng._params, eng._frozen, eng._buffers, eng.pool.cache,
            eng._slot_state.buffer, eng._prev_toks)


def test_decode_program_takes_the_stacked_pool_donated(gpt):
    """The decode call passes 2 x layers pool arrays, all donated, builds
    no pool leaf by concatenating slot rows, and the compiled program
    aliases the whole pool from its inputs to its outputs."""
    layers = gpt.config.num_hidden_layers
    eng = InferenceEngine(gpt, num_slots=3, max_length=32, decode_block=2)
    lowered = eng._decode_jit.lower(*_decode_args(eng))
    pool_args = _leaves(lowered.args_info[0][3])
    assert len(pool_args) == 2 * layers
    assert all(a.donated for a in pool_args)
    leaf = _leaves(eng.pool.cache)[0]
    shape = 'x'.join(str(d) for d in leaf.shape)
    for line in lowered.as_text().splitlines():
        if 'concatenate' in line:
            assert f'-> tensor<{shape}x' not in line, line
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes == eng.pool.pool_bytes


def test_failed_donated_seat_recovers_the_pool(gpt):
    """A seat program dying with the pool donated to it is the STEP's
    failure (every seated request lost its KV), not one request's: the
    pool is rebuilt, the error leaves step() typed, the handles are
    still there for the router to fail over, and the engine serves the
    next request correctly."""
    from paddle_tpu.serving import PoolLostError
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2)
    prompts = _prompts((6, 9))
    ref = _ref_generate(gpt, prompts[0], 4)
    real = eng.pool._seat_jit

    def dying(pool, row, slot):
        for leaf in _leaves(pool):
            leaf.delete()                   # what a donated call may do
        raise RuntimeError('simulated device failure mid-seat')

    eng.pool._seat_jit = dying
    hs = [eng.submit(p, max_new_tokens=4, eos_token_id=NO_EOS)
          for p in prompts]
    with pytest.raises(PoolLostError, match='mid-seat') as err:
        eng.step()
    assert isinstance(err.value.__cause__, RuntimeError)
    assert 'serving_pool_recovered' in [e['name'] for e in
                                        obs.get_event_log().events()]
    assert not any(h.done for h in hs)      # nobody was failed in place
    assert not any(leaf.is_deleted() for leaf in _leaves(eng.pool.rows))
    assert sorted(h.request_id for h in eng.evict_all()) == \
        sorted(h.request_id for h in hs)    # seated AND popped-behind
    assert eng.pool.free_count == 2
    eng.pool._seat_jit = real
    h = eng.submit(prompts[0], max_new_tokens=4, eos_token_id=NO_EOS)
    eng.run()
    assert h.status == FINISHED and h.tokens == ref


def test_donated_decode_failure_recovers_the_pool(gpt, sanitizer_strict):
    """A decode program dying mid-call may have consumed the pool it
    was given: the engine rebuilds the pool, hands the orphaned request
    back, and serves the next one correctly."""
    eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                          prefix_cache=True)
    prompt = _prompts((6,))[0]
    ref = _ref_generate(gpt, prompt, 4)
    real_program = eng._decode_program

    def dying(*args):
        for leaf in _leaves(args[3]):
            leaf.delete()                   # what a donated call may do
        raise RuntimeError('simulated device failure mid-decode')

    eng._decode_program = lambda rows, args: dying
    h = eng.submit(prompt, max_new_tokens=4, eos_token_id=NO_EOS)
    with pytest.raises(RuntimeError, match='mid-decode'):
        eng.run()
    assert 'serving_pool_recovered' in [e['name'] for e in
                                        obs.get_event_log().events()]
    assert not any(leaf.is_deleted() for leaf in _leaves(eng.pool.rows))
    for handle in eng.evict_all():
        assert handle is h                  # orphan handed back, not lost
    eng._decode_program = real_program
    h2 = eng.submit(prompt, max_new_tokens=4, eos_token_id=NO_EOS)
    eng.run()
    assert h2.tokens == ref


def test_one_prefill_row_in_flight(gpt, monkeypatch):
    """The runtime reserves a program's outputs when it is enqueued, so
    admissions dispatched back to back would each hold a row until its
    seat ran. Every prefill dispatch therefore waits for what is queued
    on the pool — the seat of the admission before it."""
    eng = InferenceEngine(gpt, num_slots=3, max_length=64, decode_block=2)
    order = []
    real_wait, real_prefill = jax.block_until_ready, eng._prefill_jit

    def wait(tree):
        order.append('wait' if tree is eng.pool.rows else 'wait-other')
        return real_wait(tree)

    def prefill(*args):
        order.append('prefill')
        return real_prefill(*args)

    monkeypatch.setattr(jax, 'block_until_ready', wait)
    eng._prefill_jit = prefill
    for p in _prompts((5, 9, 13)):
        eng.submit(p, max_new_tokens=2, eos_token_id=NO_EOS)
    eng.step()                              # three admissions, one step
    assert [o for o in order if o != 'wait-other'] == \
        ['wait', 'prefill'] * 3


# ---------------------------------------------------------------------------
# the KV write: generation.update_kv_cache against a plain numpy loop
# ---------------------------------------------------------------------------

def _np_kv_write(cache, new, off):
    """The write as `dynamic_update_slice` defines it, slot by slot: a
    block that would pass the slot's end starts at L - S instead."""
    out = np.array(cache)
    b, s = new.shape[:2]
    for i in range(b):
        o = int(off) if np.ndim(off) == 0 else int(off[i])
        o = min(max(o, 0), cache.shape[1] - s)
        out[i, o:o + s] = np.asarray(new[i]).astype(cache.dtype)
    return out


_L = 16     # rows of a slot in these cases
_KV_WRITE_CASES = {
    # decode: one row a slot, each at its own position
    's1_heads16': dict(heads=16, s=1, off=[0, 7, 3, 15, 9]),
    's1_heads8': dict(heads=8, s=1, off=[5, 0, 14, 2, 2]),
    's1_heads4': dict(heads=4, s=1, off=[1, 1, 8, 13, 6]),
    # speculation's k+1 rows: 14 and 15 would pass the end of a 16-row
    # slot and are clamped to 12, as dynamic_update_slice clamps them
    's4_clamped_at_the_end': dict(heads=4, s=4, off=[0, 14, 15, 12, 5]),
    # a chunk as long as the slot: every offset clamps to 0
    's16_whole_slot': dict(heads=4, s=_L, off=[0, 3, 15, 1, 9]),
    # bf16 rows (the model's activations) into the f32 leaf of the cells
    'bf16_rows_into_f32': dict(heads=8, s=1, off=[2, 11, 4, 0, 15],
                               new_dtype='bfloat16'),
    # inactive slots are parked at the last row: the junk they write
    # lands there, before anything attends it
    'parked_at_the_last_row': dict(heads=4, s=1, off=[15, 15, 6, 15, 15]),
    # scalar offset (generate(), beam search, t5, whole and chunked
    # prefill): one dynamic_update_slice, as before
    'scalar_offset': dict(heads=4, s=3, off=6),
    'scalar_offset_clamped': dict(heads=4, s=3, off=15),
}


@pytest.mark.parametrize('case', list(_KV_WRITE_CASES))
def test_update_kv_cache_matches_a_plain_loop(case):
    spec = _KV_WRITE_CASES[case]
    h, s, d, b = spec['heads'], spec['s'], 8, 5
    rng = np.random.RandomState(len(case))
    new_dt = spec.get('new_dtype', 'float32')
    caches = [rng.randn(b, _L, h, d).astype('float32') for _ in range(2)]
    news = [jnp.asarray(rng.randn(b, s, h, d), new_dt) for _ in range(2)]
    off = spec['off']
    off_dev = jnp.asarray(off, jnp.int32)

    @jax.jit
    def write(kc, vc, k, v, o):
        out = generation.update_kv_cache(
            paddle.Tensor(kc), paddle.Tensor(vc), paddle.Tensor(k),
            paddle.Tensor(v), o)
        return tuple(t.value for t in out)

    got = write(jnp.asarray(caches[0]), jnp.asarray(caches[1]), *news,
                off_dev)
    for g, c, n in zip(got, caches, news):
        assert g.dtype == jnp.float32 and g.shape == c.shape
        np.testing.assert_array_equal(np.asarray(g),
                                      _np_kv_write(c, np.asarray(n), off))
    # bit-equal to the one dynamic_update_slice a scalar offset has always
    # been, and per row to what the vmapped form gave
    kc, k = jnp.asarray(caches[0]), news[0].astype(jnp.float32)
    if np.ndim(off) == 0:
        want = jax.lax.dynamic_update_slice(kc, k, (0, off, 0, 0))
    else:
        want = jax.vmap(lambda cr, nr, o: jax.lax.dynamic_update_slice(
            cr, nr, (o, 0, 0)))(kc, k, off_dev)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _handle(prompt_len, max_new=4):
    return RequestHandle(list(range(1, prompt_len + 1)),
                         SamplingParams(max_new_tokens=max_new))


def test_scheduler_fcfs_order_and_slot_limit():
    sched = FCFSScheduler()
    hs = [_handle(4) for _ in range(5)]
    for h in hs:
        sched.submit(h)
    got = sched.admissible(3, bucket_for=lambda n: n)
    assert got == hs[:3]                  # strict FCFS prefix
    assert sched.queue_depth == 2
    assert sched.admissible(0, bucket_for=lambda n: n) == []
    assert sched.admissible(5, bucket_for=lambda n: n) == hs[3:]


def test_scheduler_prefill_token_budget():
    sched = FCFSScheduler(max_prefill_tokens=10)
    hs = [_handle(8), _handle(8), _handle(8)]
    for h in hs:
        sched.submit(h)
    # first admission always proceeds (progress guarantee); the second
    # would blow the 10-token budget and waits
    assert sched.admissible(3, bucket_for=lambda n: n) == hs[:1]
    assert sched.admissible(3, bucket_for=lambda n: n) == hs[1:2]


def test_scheduler_cancel_and_queue_gauge():
    sched = FCFSScheduler()
    h1, h2 = _handle(4), _handle(4)
    sched.submit(h1)
    sched.submit(h2)
    assert obs.get_registry().value('paddle_serving_queue_depth') == 2
    assert sched.cancel(h1)
    assert not sched.cancel(h1)
    assert sched.admissible(2, bucket_for=lambda n: n) == [h2]
    assert obs.get_registry().value('paddle_serving_queue_depth') == 0


# ---------------------------------------------------------------------------
# engine: greedy parity, slot reuse, recompiles
# ---------------------------------------------------------------------------

def test_engine_mixed_length_greedy_matches_generate(gpt):
    prompts = _prompts([3, 9, 5, 14, 7, 11])
    news = [6, 9, 4, 12, 8, 5]
    eng = InferenceEngine(gpt, num_slots=3, max_length=64, decode_block=4)
    handles = eng.generate_many(
        prompts, [SamplingParams(max_new_tokens=n, eos_token_id=NO_EOS)
                  for n in news])
    for h, p, n in zip(handles, prompts, news):
        assert h.status == FINISHED
        assert h.tokens == _ref_generate(gpt, p, n), \
            f'request {h.request_id} diverged from generate()'
    st = eng.stats()
    assert st['completed'] == 6 and st['failed'] == 0
    assert eng.pool.free_count == 3       # every slot returned


def test_engine_llama_per_row_cache_offsets():
    # the llama family shares update_kv_cache: per-row slots must work
    # for RoPE models too (rope offsets already support [B])
    paddle.seed(11)
    model = LlamaForCausalLM(LlamaConfig.tiny()).eval()
    prompts = _prompts([4, 9])
    eng = InferenceEngine(model, num_slots=2, max_length=32,
                          decode_block=2)
    hs = eng.generate_many(
        prompts, [SamplingParams(max_new_tokens=5, eos_token_id=NO_EOS)
                  for _ in prompts])
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref_generate(model, p, 5)


def test_midflight_admission_reuses_slot_with_zero_recompiles(gpt):
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2)
    # warmup wave: compiles the decode block + the touched buckets
    eng.generate_many(
        _prompts([3, 9, 6], seed=1),
        [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 3)
    traces = dict(eng.stats()['traces'])
    # 1 trace when this engine compiled the decode block itself; 0 when
    # the program store handed it a sibling engine's executable (same
    # model/geometry key) — either way it must never grow below
    assert traces.get('decode_step', 0) <= 1
    compiles_before = obs.get_registry().value('paddle_jit_compiles_total')

    # second wave, same buckets, more requests than slots: every
    # admission lands in a freed slot and NOTHING recompiles
    hs = eng.generate_many(
        _prompts([4, 8, 5, 16, 7], seed=2),
        [SamplingParams(max_new_tokens=6, eos_token_id=NO_EOS)] * 5)
    assert all(h.status == FINISHED for h in hs)
    assert eng.stats()['traces'] == traces, 'admission retraced a program'
    assert obs.get_registry().value('paddle_jit_compiles_total') \
        == compiles_before, 'admission triggered an XLA compile'
    # with 2 slots and 5 requests, slots were necessarily reused
    assert eng.stats()['prefills'] == 8
    assert eng.pool.free_count == 2


def test_eos_retirement_frees_slot_and_matches_generate(gpt):
    prompt = _prompts([6], seed=5)[0]
    ref = _ref_generate(gpt, prompt, 10)
    eos = ref[2]                          # force an early eos hit
    expected = _trim_at_eos(ref, eos)
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=4)
    h = eng.submit(prompt, SamplingParams(max_new_tokens=10,
                                          eos_token_id=eos))
    eng.run()
    assert h.status == FINISHED
    assert h.tokens == expected
    assert h.tokens[-1] == eos
    assert eng.pool.free_count == 2       # retirement freed the slot


# ---------------------------------------------------------------------------
# engine: per-request sampling params
# ---------------------------------------------------------------------------

def test_per_request_sampling_params_honored(gpt):
    eng = InferenceEngine(gpt, num_slots=4, max_length=64, decode_block=4)
    prompt = _prompts([5], seed=9)[0]
    sp = dict(max_new_tokens=8, strategy='sampling', temperature=1.5,
              top_k=30, top_p=0.9, eos_token_id=NO_EOS)
    h1 = eng.submit(prompt, SamplingParams(seed=123, **sp))
    h2 = eng.submit(prompt, SamplingParams(seed=123, **sp))
    h3 = eng.submit(prompt, SamplingParams(
        max_new_tokens=8, strategy='sampling', top_k=1,
        eos_token_id=NO_EOS, seed=5))
    h4 = eng.submit(prompt, SamplingParams(max_new_tokens=8,
                                           eos_token_id=NO_EOS))
    eng.run()
    assert h1.tokens == h2.tokens         # same seed => same tokens
    assert h3.tokens == h4.tokens         # top_k=1 degenerates to greedy
    assert h4.tokens == _ref_generate(gpt, prompt, 8)


def test_greedy_request_unaffected_by_sampling_neighbours(gpt):
    prompt = _prompts([7], seed=13)[0]
    ref = _ref_generate(gpt, prompt, 8)
    eng = InferenceEngine(gpt, num_slots=4, max_length=64, decode_block=4)
    hs = eng.generate_many(
        [prompt, prompt, prompt],
        [SamplingParams(max_new_tokens=8, eos_token_id=NO_EOS),
         SamplingParams(max_new_tokens=8, strategy='sampling',
                        temperature=2.0, seed=1, eos_token_id=NO_EOS),
         SamplingParams(max_new_tokens=8, strategy='sampling',
                        temperature=2.0, seed=2, eos_token_id=NO_EOS)])
    assert hs[0].tokens == ref            # bit-identical despite neighbours


# ---------------------------------------------------------------------------
# engine: streaming + convenience API
# ---------------------------------------------------------------------------

def test_stream_yields_tokens_incrementally(gpt):
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2)
    prompt = _prompts([4], seed=3)[0]
    h = eng.submit(prompt, SamplingParams(max_new_tokens=7,
                                          eos_token_id=NO_EOS))
    seen = []
    for tok in h.stream():
        seen.append(tok)
    assert seen == h.tokens == _ref_generate(gpt, prompt, 7)
    assert h.done and h.ttft is not None and h.ttft >= 0


def test_result_blocks_until_done(gpt):
    eng = InferenceEngine(gpt, num_slots=1, max_length=64, decode_block=4)
    hs = [eng.submit(p, SamplingParams(max_new_tokens=4,
                                       eos_token_id=NO_EOS))
          for p in _prompts([3, 5], seed=4)]
    assert hs[1].result() == _ref_generate(gpt, hs[1].prompt_tokens, 4)
    assert hs[0].done                     # draining served everyone


def test_submit_validation_errors(gpt):
    eng = InferenceEngine(gpt, num_slots=2, max_length=32)
    with pytest.raises(ValueError):
        eng.submit([])                    # empty prompt
    with pytest.raises(ValueError):
        eng.submit(list(range(40)))       # no bucket fits
    with pytest.raises(ValueError):      # prompt + budget > slot length
        eng.submit(list(range(20)), SamplingParams(max_new_tokens=20))
    with pytest.raises(ValueError):
        SamplingParams(strategy='beam_search')
    with pytest.raises(ValueError):
        InferenceEngine(gpt, max_length=4096)   # > max_position_embeddings
    with pytest.raises(ValueError):
        eng.generate_many([[1, 2]], [SamplingParams(), SamplingParams()])


# ---------------------------------------------------------------------------
# engine: resilience — request-level failure, engine survives
# ---------------------------------------------------------------------------

def test_fatal_transfer_failure_fails_only_that_request(gpt):
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2,
                          retry_policy=_NO_SLEEP)
    prompts = _prompts([4, 6, 5], seed=6)
    sp = SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)
    inj = FaultInjector(nth=2, exc=FatalError('injected device loss'))
    with inj.patch(engine_mod, '_to_device'):
        hs = [eng.submit(p, sp) for p in prompts]
        eng.run()
    assert [h.status for h in hs] == [FINISHED, FAILED, FINISHED]
    assert isinstance(hs[1].error, FatalError)
    assert hs[0].tokens == _ref_generate(gpt, prompts[0], 4)
    assert eng.pool.free_count == 2       # the failed slot was freed
    with pytest.raises(FatalError):
        list(hs[1].stream())              # stream surfaces the error
    # the engine keeps serving new requests afterwards
    h = eng.submit(prompts[1], sp)
    eng.run()
    assert h.status == FINISHED
    assert h.tokens == _ref_generate(gpt, prompts[1], 4)


def test_transient_transfer_failure_is_retried(gpt):
    eng = InferenceEngine(gpt, num_slots=1, max_length=64,
                          retry_policy=_NO_SLEEP)
    reg = obs.get_registry()
    retries_before = reg.value('paddle_resilience_retries_total',
                               site='serving.h2d')
    inj = FaultInjector(nth=1, exc=TransientError('blip'), repeat=2)
    with inj.patch(engine_mod, '_to_device'):
        h = eng.submit(_prompts([5], seed=8)[0],
                       SamplingParams(max_new_tokens=3,
                                      eos_token_id=NO_EOS))
        eng.run()
    assert h.status == FINISHED           # retried through the blips
    assert inj.calls == 3
    assert reg.value('paddle_resilience_retries_total',
                     site='serving.h2d') == retries_before + 2


# ---------------------------------------------------------------------------
# observability wiring
# ---------------------------------------------------------------------------

def test_serving_metrics_and_summary(gpt):
    reg = obs.get_registry()
    before_sub = reg.value('paddle_serving_requests_total',
                           status='submitted')
    before_done = reg.value('paddle_serving_requests_total',
                            status='completed')
    ttft_fam = reg.get('paddle_serving_ttft_seconds')
    before_ttft = ttft_fam._children[()].count if ttft_fam else 0
    occ_fam = reg.get('paddle_serving_slot_occupancy')
    before_occ = occ_fam._children[()].count if occ_fam else 0
    eng = InferenceEngine(gpt, num_slots=2, max_length=64)
    hs = eng.generate_many(
        _prompts([3, 11, 6], seed=10),
        [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 3)
    assert reg.value('paddle_serving_requests_total',
                     status='submitted') == before_sub + 3
    assert reg.value('paddle_serving_requests_total',
                     status='completed') == before_done + 3
    ttft = reg.get('paddle_serving_ttft_seconds')._children[()]
    assert ttft.count == before_ttft + 3
    assert reg.value('paddle_serving_active_slots') == 0
    assert reg.value('paddle_serving_tokens_total') >= 12
    occ = reg.get('paddle_serving_slot_occupancy')._children[()]
    assert occ.count - before_occ == eng.stats()['decode_rounds'] > 0
    text = debug.observability_summary()
    assert 'serving:' in text and 'ttft avg' in text
    assert sum(len(h.tokens) for h in hs) == 12


# ---------------------------------------------------------------------------
# ISSUE-9: chunked prefill
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_long_prompt_chunks_and_matches_generate(self, gpt):
        prompts = _prompts([26, 4, 17, 9], seed=31)
        refs = [_ref_generate(gpt, p, 5) for p in prompts]
        eng = InferenceEngine(gpt, num_slots=4, max_length=64,
                              decode_block=2, prefill_chunk_tokens=8)
        hs = eng.generate_many(
            prompts, [SamplingParams(max_new_tokens=5,
                                     eos_token_id=NO_EOS)] * 4)
        assert [h.tokens for h in hs] == refs
        st = eng.stats()
        assert st['chunked_prefills'] == 3      # the 26/17/9-token ones
        assert st['chunk_rounds'] >= 4
        assert st['prefill_tokens'] == sum(len(p) for p in prompts)

    def test_short_requests_stream_while_long_prefills(self, gpt):
        """The TTFT story: with chunking, a short request admitted with
        a long one gets its first token BEFORE the long prompt finishes
        prefilling."""
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, prefill_chunk_tokens=8)
        long_h = eng.submit(_prompts([30], seed=33)[0],
                            SamplingParams(max_new_tokens=4,
                                           eos_token_id=NO_EOS))
        short_h = eng.submit(_prompts([3], seed=34)[0],
                             SamplingParams(max_new_tokens=4,
                                            eos_token_id=NO_EOS))
        eng.step()
        eng.step()
        assert short_h.tokens                  # already streaming
        assert not long_h.tokens               # still chunking
        assert long_h.status == 'RUNNING'
        eng.run()
        assert long_h.tokens == _ref_generate(gpt,
                                              long_h.prompt_tokens, 4)

    def test_chunked_zero_recompiles_across_waves(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, prefill_chunk_tokens=8)
        sp = [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 3
        eng.generate_many(_prompts([25, 6, 14], seed=35), sp)
        traces = dict(eng.stats()['traces'])
        compiles = obs.get_registry().value('paddle_jit_compiles_total')
        hs = eng.generate_many(_prompts([22, 5, 12], seed=36), sp)
        assert all(h.status == FINISHED for h in hs)
        assert eng.stats()['traces'] == traces
        assert obs.get_registry().value('paddle_jit_compiles_total') \
            == compiles

    def test_chunked_drain_finishes_mid_prefill_requests(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, prefill_chunk_tokens=8)
        h = eng.submit(_prompts([28], seed=37)[0],
                       SamplingParams(max_new_tokens=3,
                                      eos_token_id=NO_EOS))
        eng.step()                     # mid-chunked-prefill
        assert not h.tokens
        try:
            assert eng.drain(deadline_s=120.0)
            assert h.status == FINISHED
            assert h.tokens == _ref_generate(gpt, h.prompt_tokens, 3)
        finally:
            obs.clear_degraded('draining')


# ---------------------------------------------------------------------------
# ISSUE-9: per-slot speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeEngine:
    def _draft(self):
        paddle.seed(99)
        return GPTForCausalLM(
            GPTConfig.tiny(num_hidden_layers=1)).eval()

    def test_independent_draft_bit_identical_greedy(self, gpt):
        """The exactness guarantee, in-engine: even a draft that almost
        never agrees leaves greedy outputs token-identical."""
        prompts = _prompts([4, 9, 6], seed=41)
        refs = [_ref_generate(gpt, p, 7) for p in prompts]
        eng = InferenceEngine(gpt, num_slots=3, max_length=64,
                              decode_block=2, draft_model=self._draft(),
                              num_draft_tokens=3)
        hs = eng.generate_many(
            prompts, [SamplingParams(max_new_tokens=7,
                                     eos_token_id=NO_EOS)] * 3)
        assert [h.tokens for h in hs] == refs
        sp = eng.stats()['spec']
        assert sp['rounds'] > 0 and sp['proposed'] > 0

    def test_self_draft_accepts_and_advances_multiple(self, gpt):
        """Draft == target: near-total acceptance, so requests finish in
        far fewer rounds than tokens."""
        prompts = _prompts([5, 8], seed=43)
        refs = [_ref_generate(gpt, p, 12) for p in prompts]
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              draft_model=gpt, num_draft_tokens=4)
        hs = eng.generate_many(
            prompts, [SamplingParams(max_new_tokens=12,
                                     eos_token_id=NO_EOS)] * 2)
        assert [h.tokens for h in hs] == refs
        sp = eng.stats()['spec']
        assert sp['rounds'] <= 8               # vs 12+ single-token rounds
        assert sp['acceptance_rate'] > 0.5
        assert obs.get_registry().value(
            'paddle_serving_spec_accepted_total') > 0
        assert obs.get_registry().value(
            'paddle_spec_rounds_total', source='engine') > 0

    def test_sampling_rows_unaffected_by_speculation(self, gpt):
        """Sampling requests in a speculating engine take the plain
        per-round sampling path: same seed => same tokens, and greedy
        neighbours still match generate()."""
        prompt = _prompts([6], seed=45)[0]
        sp = dict(max_new_tokens=8, strategy='sampling', temperature=1.4,
                  top_k=24, eos_token_id=NO_EOS)
        eng = InferenceEngine(gpt, num_slots=3, max_length=64,
                              draft_model=gpt, num_draft_tokens=3)
        h1 = eng.submit(prompt, SamplingParams(seed=7, **sp))
        h2 = eng.submit(prompt, SamplingParams(seed=7, **sp))
        h3 = eng.submit(prompt, SamplingParams(max_new_tokens=8,
                                               eos_token_id=NO_EOS))
        eng.run()
        assert h1.tokens == h2.tokens
        assert h3.tokens == _ref_generate(gpt, prompt, 8)

    def test_eos_retires_mid_round(self, gpt):
        prompt = _prompts([6], seed=47)[0]
        ref = _ref_generate(gpt, prompt, 10)
        eos = ref[3]
        expected = _trim_at_eos(ref, eos)
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              draft_model=gpt, num_draft_tokens=4)
        h = eng.submit(prompt, SamplingParams(max_new_tokens=10,
                                              eos_token_id=eos))
        eng.run()
        assert h.status == FINISHED and h.tokens == expected
        assert eng.pool.free_count == 2

    def test_spec_headroom_validated_at_submit(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=32,
                              draft_model=gpt, num_draft_tokens=4)
        with pytest.raises(ValueError, match='speculation headroom'):
            eng.submit(list(range(1, 21)),
                       SamplingParams(max_new_tokens=10))
        # the same request fits a non-speculating engine
        eng2 = InferenceEngine(gpt, num_slots=2, max_length=32)
        eng2.submit(list(range(1, 21)), SamplingParams(max_new_tokens=10))

    def test_spec_zero_recompiles_across_waves(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              draft_model=self._draft(),
                              num_draft_tokens=3)
        sp = [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 3
        eng.generate_many(_prompts([3, 9, 6], seed=49), sp)
        traces = dict(eng.stats()['traces'])
        compiles = obs.get_registry().value('paddle_jit_compiles_total')
        hs = eng.generate_many(_prompts([4, 8, 5], seed=50), sp)
        assert all(h.status == FINISHED for h in hs)
        assert eng.stats()['traces'] == traces
        assert obs.get_registry().value('paddle_jit_compiles_total') \
            == compiles


# ---------------------------------------------------------------------------
# tier-1 bench guard: bit-identical outputs + zero recompiles + speedup
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_serving_guard():
    # Full-gate tier: parity + zero-recompile are asserted fast-tier by
    # test_engine_mixed_length_greedy_matches_generate and the
    # zero-recompile wave tests; this re-proves them through bench.py.
    import bench
    res = bench.serving_ab(num_requests=8, num_slots=4, trials=1)
    assert res['parity'], 'engine greedy outputs diverged from generate()'
    assert res['recompiles_after_warmup'] == 0, \
        'continuous batching recompiled after warmup'
    # the >= 1.5x bar is asserted on the full bench trace; here just
    # sanity-check both arms actually ran
    assert res['engine_tokens_per_sec'] > 0
    assert res['sequential_tokens_per_sec'] > 0


@pytest.mark.slow
def test_bench_prefix_guard():
    # Full-gate tier: prefix parity/hit behavior is asserted fast-tier
    # by test_prefix_cache.py TestEngineIntegration; the bench A/B adds
    # the prefill-reduction headline at ~24 s.
    import bench
    res = bench.prefix_ab(num_requests=8, num_slots=10, trials=1)
    assert res['parity'], 'prefix-cache outputs diverged from generate()'
    assert res['recompiles_after_warmup'] == 0, \
        'prefix-cache trace recompiled after warmup'
    assert res['cache_hits'] > 0
    # the shared-system-prompt trace must collapse prefill to suffixes
    # (the >= 30% acceptance bar, with margin even at guard scale)
    assert res['prefill_token_reduction'] >= 0.3


@pytest.mark.slow
def test_bench_chunked_guard():
    # Full-gate tier: chunked parity/rounds/TTFT streaming are asserted
    # fast-tier by TestChunkedPrefill; this re-proves them through the
    # bench A/B arms.
    import bench
    res = bench.chunked_ab(num_short=4, long_len=48, max_length=64,
                           num_slots=6, chunk=16, trials=1)
    assert res['parity'], 'chunked outputs diverged from generate()'
    assert res['recompiles_after_warmup'] == 0, \
        'chunked trace recompiled after warmup'
    assert res['chunk_rounds'] >= 2
    # the p50-TTFT ratio is asserted on the full bench run where the
    # structural gap dwarfs CI noise; here both arms must report
    assert res['p50_short_ttft_ms_chunked'] > 0
    assert res['p50_short_ttft_ms_unchunked'] > 0


def test_bench_spec_guard():
    import bench
    res = bench.spec_ab(num_requests=4, num_slots=4, max_new=16,
                        distill_steps=60, trials=1)
    assert res['parity'], 'speculative outputs diverged from generate()'
    assert res['recompiles_after_warmup'] == 0, \
        'speculative trace recompiled after warmup'
    assert res['acceptance_rate'] > 0
    assert res['tokens_per_sec_spec'] > 0
    assert res['tokens_per_sec_plain'] > 0


def test_bench_stack_guard():
    """The ISSUE-9 composed-stack acceptance bar: prefix cache +
    chunked prefill + speculative decoding ALL enabled, greedy outputs
    bit-identical to generate(), zero compiles after warmup by both
    the python trace counters AND paddle_jit_compiles_total."""
    import bench
    res = bench.stack_ab(num_requests=8, num_slots=6)
    assert res['parity'], 'composed latency stack diverged from ' \
                          'generate()'
    assert res['recompiles_after_warmup'] == 0
    assert res['jit_compiles_delta'] == 0
    assert res['completed'] == 8
    assert res['prefix_hits'] > 0
    assert res['chunk_rounds'] > 0


# ---------------------------------------------------------------------------
# ISSUE-6 satellite: graceful drain wired to PreemptionHandler
# ---------------------------------------------------------------------------

class TestGracefulDrain:
    def _engine(self, gpt, **kw):
        kw.setdefault('num_slots', 2)
        kw.setdefault('max_length', 64)
        kw.setdefault('decode_block', 2)
        return InferenceEngine(gpt, **kw)

    def test_no_accepted_request_dropped_on_sigterm(self, gpt):
        """Fault-injection: SIGTERM lands with requests queued AND
        in-flight; every accepted request still finishes, new ones are
        rejected, /healthz flips to draining/503."""
        from paddle_tpu.resilience import PreemptionHandler
        eng = self._engine(gpt)
        handler = PreemptionHandler()   # not installed: test delivers
        eng.enable_graceful_drain(handler=handler, deadline_s=120.0)
        # 2 slots, 4 requests: two decode in-flight, two still queued
        prompts = _prompts([3, 9, 5, 7], seed=2)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=6,
                                           eos_token_id=NO_EOS))
              for p in prompts]
        eng.step()                      # two running, two queued
        assert eng.scheduler.queue_depth == 2
        handler.request()               # the eviction signal
        log = obs.get_event_log()
        ev0 = len(log.events())
        try:
            ok = eng.drain()
            assert ok
            # accepted requests: ALL finished, none dropped/failed
            for h, p in zip(hs, prompts):
                assert h.status == FINISHED
                assert h.tokens == _ref_generate(gpt, p, 6)
            # new submissions rejected while draining
            with pytest.raises(RuntimeError, match='draining'):
                eng.submit(_prompts([4], seed=9)[0])
            assert eng.stats()['submitted'] == 4   # reject not counted
            # healthz: 503 draining until the process exits
            health = obs.health()
            assert health['status'] == 'draining'
            assert 'draining' in health['degraded']
            names = [e['name'] for e in log.events()[ev0:]]
            assert 'serving_drain_begin' in names
            assert 'serving_drain_complete' in names
        finally:
            obs.clear_degraded('draining')

    def test_drain_deadline_fails_stragglers_not_silently(self, gpt):
        eng = self._engine(gpt)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=30,
                                           eos_token_id=NO_EOS))
              for p in _prompts([3, 5, 7], seed=4)]
        eng.step()
        try:
            ok = eng.drain(deadline_s=0.0)   # expires immediately
            assert not ok
            assert not eng.has_work          # nothing left dangling
            for h in hs:
                assert h.status == FAILED
                assert isinstance(h.error, TimeoutError)
            assert eng.pool.free_count == eng.pool.num_slots
        finally:
            obs.clear_degraded('draining')

    def test_step_picks_up_preemption_flag(self, gpt):
        from paddle_tpu.resilience import PreemptionHandler
        eng = self._engine(gpt)
        handler = PreemptionHandler()
        eng.enable_graceful_drain(handler=handler, deadline_s=60.0)
        h = eng.submit(_prompts([3], seed=6)[0],
                       SamplingParams(max_new_tokens=4,
                                      eos_token_id=NO_EOS))
        handler.request()
        try:
            eng.run()                        # step() notices the flag
            assert eng.draining
            assert h.status == FINISHED
        finally:
            obs.clear_degraded('draining')

    def test_drain_without_handler_is_explicit(self, gpt):
        eng = self._engine(gpt)
        h = eng.submit(_prompts([4], seed=7)[0],
                       SamplingParams(max_new_tokens=3,
                                      eos_token_id=NO_EOS))
        try:
            assert eng.drain(deadline_s=60.0)
            assert h.status == FINISHED
        finally:
            obs.clear_degraded('draining')


# ---------------------------------------------------------------------------
# two decode programs: attention over every row of a slot, or over the
# first half while no active slot comes near it
# ---------------------------------------------------------------------------

_FAMILIES = {
    'gpt': lambda: GPTForCausalLM(GPTConfig.tiny()),
    'llama_grouped_heads': lambda: LlamaForCausalLM(LlamaConfig.tiny()),
    'afmoe_window_and_full': lambda: AfmoeForCausalLM(AfmoeConfig.tiny()),
}
_MODES = {'row': {}, 'paged': {'kv_page_size': 8},
          'chunked_prefill': {'prefill_chunk_tokens': 16}}


@pytest.fixture(scope='module')
def families():
    built = {}

    def get(name):
        if name not in built:
            paddle.seed(11)
            built[name] = _FAMILIES[name]().eval()
        return built[name]
    return get


def _serve_by_rows(model, whole_only, **kw):
    """One request set through a 3 x 64 engine: one answer crosses row
    32, two stay short, one prompt of 40 arrives behind them. -> (tokens,
    [(rows, slots prefilling in chunks), ...] a decode round)."""
    eng = InferenceEngine(model, num_slots=3, max_length=64, decode_block=4,
                          buckets=[16, 32, 48], eos_token_id=NO_EOS, **kw)
    if whole_only:
        eng._half_rows = 0          # what an engine of max_length 7 has
    rounds, pick = [], eng._round_rows

    def noting():
        rounds.append((pick(), len(eng._prefilling)))
        return rounds[-1][0]
    eng._round_rows = noting
    reqs = zip(_prompts([5, 9, 12, 40], seed=3), (40, 8, 14, 6))
    hs = [eng.submit(p, SamplingParams(max_new_tokens=n, eos_token_id=NO_EOS))
          for p, n in reqs]
    eng.run()
    assert all(h.status == FINISHED for h in hs)
    return [list(h.tokens) for h in hs], rounds


@pytest.mark.parametrize('mode', list(_MODES))
@pytest.mark.parametrize('family', list(_FAMILIES))
def test_half_length_decode_serves_the_whole_programs_tokens(
        families, family, mode):
    """The program changes between two rounds of one answer, and the
    tokens are those of the same set held to the whole program."""
    model = families(family)
    toks, rounds = _serve_by_rows(model, False, **_MODES[mode])
    want, whole = _serve_by_rows(model, True, **_MODES[mode])
    assert toks == want
    assert {r for r, _ in whole} == {64}
    assert {r for r, _ in rounds} == {32, 64}
    # the first answer starts under the half program and ends past it
    assert rounds[0][0] == 32 and rounds[-1][0] == 64
    if mode == 'chunked_prefill':
        # a slot parked at row 63 while it prefills does not count
        assert any(r == 32 and n for r, n in rounds)


@pytest.mark.parametrize('pos,active,rows', [
    ((27, 3, 0), (1, 1, 0), 32),        # 27 + 4 + 1 rows: the last fit
    ((28, 3, 0), (1, 1, 0), 64),
    ((3, 63, 9), (1, 0, 1), 32),        # parked mid-prefill: not counted
    ((3, 63, 9), (1, 1, 1), 64),
    ((0, 0, 0), (0, 0, 1), 32)])
def test_round_rows_follows_the_longest_active_position(gpt, pos, active,
                                                        rows):
    eng = InferenceEngine(gpt, num_slots=3, max_length=64, decode_block=4)
    eng._pos[:] = pos
    eng._active[:] = active
    assert eng._round_rows() == rows


def test_no_half_program_where_a_block_cannot_fit_in_it(gpt):
    eng = InferenceEngine(gpt, num_slots=2, max_length=8, decode_block=4)
    assert eng._decode_half_jit is None
    h = eng.submit([1, 2, 3], max_new_tokens=4, eos_token_id=NO_EOS)
    eng.run()
    assert h.tokens == _ref_generate(gpt, [1, 2, 3], 4)


@pytest.mark.parametrize('mode', ['row', 'paged'])
def test_first_decode_dispatch_builds_both_programs(gpt, mode):
    """No compile after the first decode dispatch while the longest
    position sweeps across `max_length // 2`."""
    log = obs.get_event_log()
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2,
                          **_MODES[mode])
    h = eng.submit(_prompts([5], seed=9)[0], max_new_tokens=50,
                   eos_token_id=NO_EOS)
    eng.step()                  # admission, prefill, one decode round
    assert len(h.tokens) == 2
    reg = obs.get_registry()
    compiles = reg.value('paddle_jit_compiles_total')
    traces = dict(eng.stats()['traces'])
    log.clear()
    eng.run()
    assert reg.value('paddle_jit_compiles_total') == compiles
    assert eng.stats()['traces'] == traces
    rows = [e['attrs']['rows'] for e in log.events()
            if e['name'] == 'serving.decode_round']
    assert set(rows) == {32, 64} and rows == sorted(rows)
    assert h.tokens == _ref_generate(gpt, _prompts([5], seed=9)[0], 50)


def test_decode_round_span_and_counter_say_what_was_read(gpt):
    log = obs.get_event_log()
    reg = obs.get_registry()
    eng = InferenceEngine(gpt, num_slots=2, max_length=64, decode_block=2)
    read = reg.value('paddle_serving_decode_rows_read_total')
    log.clear()
    eng.generate_many(_prompts([4, 25]), [SamplingParams(
        max_new_tokens=12, eos_token_id=NO_EOS)] * 2)
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    layers = gpt.config.num_hidden_layers
    assert {a['rows'] for a in rounds} == {32, 64}
    for a in rounds:
        assert a['read_rows'] == 2 * a['rows'] * layers
        assert a['needed_rows'] <= a['read_rows']
    # slots x rows a sub-step, two sub-steps a round
    assert reg.value('paddle_serving_decode_rows_read_total') - read \
        == sum(2 * a['rows'] * 2 for a in rounds)


def test_whole_decode_program_has_no_slice_and_the_half_one_does(gpt):
    """`rows=max_length` spelled out lowers to the text of the program
    the engine has always had; the half program differs from it by a
    slice of each leaf before attention, and still writes, carries and
    returns whole leaves."""
    eng = InferenceEngine(gpt, num_slots=3, max_length=32, decode_block=2)
    args = _decode_args(eng)
    # the engine's own are these, the buffer a copy of itself
    assert all(a is b or (a is args[4] and a.tobytes() == b.tobytes())
               for a, b in zip(_leaves(args), _leaves(eng._decode_args())))

    def _decode_block_fn(params, frozen, buffers, pool, state, prev):
        fwd = generation.cached_forward(eng.model, params, frozen, buffers)
        slots = eng._slot_state.unpack(state)
        tok = jnp.where(slots.carried, prev[:, -1], slots.tok)
        return eng._decode_scan(fwd, pool, tok, *slots[1:9], rows=32)
    spelled = jax.jit(_decode_block_fn, donate_argnums=(3,)).lower(*args)
    whole = eng._decode_jit.lower(*args)
    assert whole.as_text() == spelled.as_text()
    leaf = _leaves(eng.pool.cache)[0]
    cut = f'-> tensor<{leaf.shape[0]}x16x'
    assert not [ln for ln in whole.as_text().splitlines()
                if 'stablehlo.slice' in ln and cut in ln]
    half = eng._decode_half_jit.lower(*args)
    assert len([ln for ln in half.as_text().splitlines()
                if 'stablehlo.slice' in ln and cut in ln]) \
        == 2 * gpt.config.num_hidden_layers
    assert half.out_info[1] == whole.out_info[1]        # the pool, whole
    assert all(a.donated for a in _leaves(half.args_info[0][3]))
    assert eng._decode_jit._name == 'serving.decode_block'
    assert eng._decode_half_jit._name == 'serving.decode_block_r16'
    assert "'rows'" not in eng._decode_jit._statics_token
    assert "'rows':16" in eng._decode_half_jit._statics_token
