"""Paged, quantized KV cache (ISSUE 16): page lifecycle under churn,
copy-on-write splits, prefix-cache page sharing with ref-count pinning,
eviction preferring zero-ref pages, failover evict_all returning every
page, paged-engine greedy parity vs the row engine AND generate(),
zero recompiles after warmup, int8 quantization error bounds, the
absmax per-channel observer parity with the traced per-page scales,
and the bucket_for / stranded-capacity satellites."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.quantization import (AbsmaxChannelObserver,
                                     kv_dequantize_page, kv_page_scales,
                                     kv_quantize_page)
from paddle_tpu.serving import (FINISHED, InferenceEngine, PagedSlotPool,
                                PagePoolExhausted, PagedPrefixCache,
                                PromptTooLongError, SamplingParams,
                                SlotPool)

NO_EOS = -1


class _KVOnly:
    """Minimal init_cache-contract model for pool-only tests (no
    forward needed): one layer of (K, V) leaves [B, L, H, D]."""

    def __init__(self, heads=2, dim=4):
        self.heads, self.dim = heads, dim

    def init_cache(self, batch, length, dtype=None):
        shape = (batch, length, self.heads, self.dim)
        dt = dtype or jnp.float32
        return ((jnp.zeros(shape, dt), jnp.zeros(shape, dt)),)


def _pool(num_slots=4, max_length=64, page_size=16, num_pages=None,
          quant=None):
    return PagedSlotPool(_KVOnly(), num_slots, max_length,
                         page_size=page_size, num_pages=num_pages,
                         quant=quant)


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


def _all_pages_free(pool):
    return (pool.free_page_count == pool.num_pages - 1
            and pool.used_page_count == 0)


# ---------------------------------------------------------------------------
# pool primitives: reserve / free / COW under churn
# ---------------------------------------------------------------------------

class TestPageLifecycle:

    def test_reserve_free_roundtrip(self):
        pool = _pool()
        slot = pool.alloc()
        pool.reserve(slot, 40)            # 3 pages of 16
        assert pool.allocated_rows(slot) == 48
        assert pool.used_page_count == 3
        assert all(pool.page_table[slot][:3] > 0)
        assert all(pool.page_table[slot][3:] == 0)
        pool.free(slot)
        assert _all_pages_free(pool)

    def test_reserve_is_idempotent_over_mapped_pages(self):
        pool = _pool()
        slot = pool.alloc()
        pool.reserve(slot, 20)
        first = list(pool.page_table[slot])
        pool.reserve(slot, 60)            # extends, keeps existing pages
        assert list(pool.page_table[slot][:2]) == first[:2]
        assert pool.used_page_count == 4

    def test_reserve_all_or_nothing_on_exhaustion(self):
        pool = _pool(num_pages=6)         # 5 usable
        a, b = pool.alloc(), pool.alloc()
        pool.reserve(a, 64)               # 4 pages
        free_before = pool.free_page_count
        with pytest.raises(PagePoolExhausted):
            pool.reserve(b, 33)           # needs 3, only 1 free
        assert pool.free_page_count == free_before, \
            'failed reservation must not leak partial allocations'
        assert all(pool.page_table[b] == 0)

    def test_reserve_past_max_length_raises(self):
        pool = _pool()
        slot = pool.alloc()
        with pytest.raises(ValueError, match='max_length'):
            pool.reserve(slot, 65)

    def test_null_page_is_never_allocated(self):
        pool = _pool()
        slots = [pool.alloc() for _ in range(4)]
        for s in slots:
            pool.reserve(s, 64)
        assert pool.free_page_count == 0
        for s in slots:
            assert (pool.page_table[s] > 0).all()   # page 0 never dealt

    def test_ctor_validation(self):
        with pytest.raises(ValueError, match='multiple'):
            _pool(max_length=60, page_size=16)
        with pytest.raises(ValueError, match='seat'):
            _pool(num_pages=3)            # < pages_per_slot + 1
        with pytest.raises(ValueError, match='quant'):
            _pool(quant='fp8')

    def test_cow_split_on_shared_page(self):
        pool = _pool()
        a = pool.alloc()
        pool.reserve(a, 32)
        hold = pool.hold_pages(a, 32)     # pin both pages
        pool.free(a)                      # pages survive at refs=1
        assert pool.used_page_count == 2
        b = pool.alloc()
        pool.attach_prefix(b, hold, 2)    # shared: refs=2
        assert pool.stats()['shared_pages'] == 2
        split = pool.ensure_exclusive(b, 31)   # row 31 -> page 1
        assert split
        assert pool.stats()['cow_splits'] == 1
        assert int(pool.page_table[b][1]) != hold.pages[1]
        assert int(pool.page_table[b][0]) == hold.pages[0]  # untouched
        # second call: already exclusive, no-op
        assert not pool.ensure_exclusive(b, 31)
        pool.free(b)
        pool.release_hold(hold)
        assert _all_pages_free(pool)

    def test_cow_copies_device_page_contents(self):
        pool = _pool()
        a = pool.alloc()
        pool.reserve(a, 16)
        pid = int(pool.page_table[a][0])
        pool.pages = jax.tree_util.tree_map(
            lambda c: c.at[pid].set(7.0), pool.pages)
        hold = pool.hold_pages(a, 16)
        pool.free(a)
        b = pool.alloc()
        pool.attach_prefix(b, hold, 1)
        pool.ensure_exclusive(b, 0)
        npid = int(pool.page_table[b][0])
        leaf = jax.tree_util.tree_leaves(pool.pages)[0]
        assert npid != pid
        np.testing.assert_array_equal(np.asarray(leaf[npid]),
                                      np.asarray(leaf[pid]))

    def test_hold_survives_slot_free_and_releases_clean(self):
        pool = _pool()
        slot = pool.alloc()
        pool.reserve(slot, 40)
        hold = pool.hold_pages(slot, 40)  # only the 2 FULL pages
        assert hold is not None and len(hold.pages) == 2
        assert hold.kv_len == 32          # trailing partial page excluded
        pool.free(slot)
        assert pool.used_page_count == 2  # partial page freed, full held
        pool.release_hold(hold)
        assert _all_pages_free(pool)
        with pytest.raises(RuntimeError, match='twice'):
            pool.release_hold(hold)

    def test_hold_below_one_page_is_none(self):
        pool = _pool()
        slot = pool.alloc()
        pool.reserve(slot, 8)
        assert pool.hold_pages(slot, 8) is None

    def test_churn_never_leaks_pages(self):
        """Random alloc/reserve/hold/attach/free churn: refcount
        conservation — every page is exactly free, mapped, or held."""
        rng = np.random.RandomState(3)
        pool = _pool(num_slots=6, num_pages=30)
        holds, seated = [], {}
        for _ in range(300):
            op = rng.randint(4)
            if op == 0 and pool.free_count:
                s = pool.alloc()
                try:
                    pool.reserve(s, int(rng.randint(1, 65)))
                    seated[s] = True
                except PagePoolExhausted:
                    pool.free(s)
            elif op == 1 and seated:
                s = list(seated)[rng.randint(len(seated))]
                h = pool.hold_pages(s, pool.allocated_rows(s))
                if h is not None:
                    holds.append(h)
            elif op == 2 and seated:
                s = list(seated)[rng.randint(len(seated))]
                del seated[s]
                pool.free(s)
            elif op == 3 and holds:
                pool.release_hold(holds.pop(rng.randint(len(holds))))
            refs = pool._page_refs[1:]
            assert (refs >= 0).all()
            assert int((refs == 0).sum()) == pool.free_page_count
        for h in holds:
            pool.release_hold(h)
        for s in seated:
            pool.free(s)
        assert _all_pages_free(pool)


# ---------------------------------------------------------------------------
# satellite: bucket_for typed error + stranded-capacity stats
# ---------------------------------------------------------------------------

class TestBucketAndStrandedStats:

    @pytest.mark.parametrize('make', [
        lambda: SlotPool(_KVOnly(), 2, 32),
        lambda: _pool(num_slots=2, max_length=32, page_size=16),
    ])
    def test_bucket_for_typed_error(self, make):
        pool = make()
        assert pool.bucket_for(7) == 8
        with pytest.raises(PromptTooLongError) as ei:
            pool.bucket_for(33)
        assert isinstance(ei.value, ValueError)   # typed, still a VE
        assert 'largest prefill bucket' in str(ei.value)

    def test_row_pool_stranded_capacity(self):
        pool = SlotPool(_KVOnly(), 3, 64)
        s = pool.alloc()
        pool.note_written(s, 10)
        st = pool.stats()
        assert st['allocated_rows'] == 64          # whole row, always
        assert st['written_rows'] == 10
        assert st['stranded_rows'] == 54
        assert st['slot_written_rows'] == {s: 10}
        assert 0 < st['row_utilization'] < 1
        pool.free(s)
        assert pool.stats()['stranded_rows'] == 0
        assert pool.stats()['row_utilization'] == 1.0

    def test_paged_pool_strands_less_than_a_page_per_slot(self):
        pool = _pool(page_size=16)
        s = pool.alloc()
        pool.reserve(s, 10)
        pool.note_written(s, 10)
        st = pool.stats()
        assert st['allocated_rows'] == 16          # one page, not 64
        assert st['stranded_rows'] == 6
        assert st['stranded_rows'] < pool.page_size

    def test_note_written_is_high_water_and_clamped(self):
        pool = SlotPool(_KVOnly(), 1, 32)
        s = pool.alloc()
        pool.note_written(s, 5)
        pool.note_written(s, 3)                    # no regression
        assert pool.stats()['written_rows'] == 5
        pool.note_written(s, 999)
        assert pool.stats()['written_rows'] == 32  # clamped


# ---------------------------------------------------------------------------
# satellite: absmax per-channel observer == traced per-page KV scales
# ---------------------------------------------------------------------------

class TestObserverParity:

    def test_channel_observer_matches_kv_page_scales(self):
        rng = np.random.RandomState(0)
        page = rng.standard_normal((16, 4, 8)).astype(np.float32) * 3
        ob = AbsmaxChannelObserver(channel_axis=1)
        ob(paddle.to_tensor(page))
        want = np.asarray(kv_page_scales(jnp.asarray(page)))
        np.testing.assert_allclose(ob.scales(), want, rtol=1e-6)

    def test_channel_observer_running_max_and_zero_channel(self):
        ob = AbsmaxChannelObserver(channel_axis=1)
        a = np.zeros((4, 3, 2), np.float32)
        a[:, 0] = 2.0
        b = np.zeros((4, 3, 2), np.float32)
        b[:, 1] = 5.08
        ob(paddle.to_tensor(a))
        ob(paddle.to_tensor(b))
        s = ob.scales()
        assert s.shape == (3,)
        np.testing.assert_allclose(s[0], 2.0 / 127)
        np.testing.assert_allclose(s[1], 0.04)
        assert s[2] == 1.0                # all-zero channel: safe scale


# ---------------------------------------------------------------------------
# int8 page quantization: deterministic error bounds
# ---------------------------------------------------------------------------

class TestInt8Bounds:

    def test_roundtrip_error_within_half_step(self):
        """Per-(page, head) absmax int8: |x - dq(q(x))| <= scale/2 =
        amax/254 per head — the bound the README documents."""
        rng = np.random.RandomState(1)
        page = jnp.asarray(rng.standard_normal((16, 4, 8)) * 5,
                           jnp.float32)
        scales = kv_page_scales(page)
        q = kv_quantize_page(page, scales)
        assert q.dtype == jnp.int8
        back = kv_dequantize_page(q, scales, jnp.float32)
        err = np.abs(np.asarray(back) - np.asarray(page))
        bound = np.asarray(scales)[None, :, None] / 2 + 1e-7
        assert (err <= bound).all()

    def test_quantized_pool_stores_int8_with_scales(self):
        pool = _pool(quant='int8')
        pages, scales = pool.device_state()
        for leaf in jax.tree_util.tree_leaves(pages):
            assert leaf.dtype == jnp.int8
        for leaf in jax.tree_util.tree_leaves(scales):
            assert leaf.dtype == jnp.float32
            assert leaf.shape == (pool.num_pages, 2)
        assert pool.stats()['kv_quant'] == 'int8'

    def test_unquantized_scales_are_empty_pytree(self):
        pool = _pool()
        _, scales = pool.device_state()
        assert jax.tree_util.tree_leaves(scales) == []


# ---------------------------------------------------------------------------
# prefix-cache page sharing: ref-count pinning + zero-ref-first eviction
# ---------------------------------------------------------------------------

class TestPagedPrefixCache:

    @staticmethod
    def _seed_entry(pool, cache, tokens):
        s = pool.alloc()
        pool.reserve(s, len(tokens))
        cache.insert(tokens, s)
        pool.free(s)

    def test_insert_retains_pages_not_slots(self):
        pool = _pool(num_slots=4, num_pages=33)
        cache = PagedPrefixCache(pool, fraction=0.5)
        self._seed_entry(pool, cache, list(range(32)))   # 2 full pages
        assert cache.held_pages == 2
        assert pool.free_count == 4        # ALL slots back — pages held
        assert pool.used_page_count == 2
        node, matched = cache.lookup(list(range(32)) + [99])
        assert node is not None and matched == 32
        assert len(node.slot.pages) == 2   # the resource is a PageHold

    def test_pinned_entry_survives_eviction_pressure(self):
        """Eviction prefers zero-ref pages: a pinned (acquired) hold is
        never the victim, even when the budget forces evictions."""
        pool = _pool(num_slots=4, max_length=64, num_pages=33)
        cache = PagedPrefixCache(pool, fraction=0.25)    # 8-page budget
        self._seed_entry(pool, cache, [1] * 32)          # 2 pages
        pinned, _ = cache.lookup([1] * 32)
        cache.acquire(pinned)                            # refs=1: pinned
        pinned_pages = tuple(pinned.slot.pages)
        for base in range(2, 6):                         # force pressure
            self._seed_entry(pool, cache, [base] * 48)   # 3 pages each
        assert cache.held_pages <= cache.budget_pages
        assert cache._counts['evictions'] >= 1
        assert pinned.slot is not None, 'pinned entry was evicted'
        assert tuple(pinned.slot.pages) == pinned_pages
        for pid in pinned_pages:
            assert pool._page_refs[pid] >= 1
        # unpin: now reclaimable, eviction may take it
        cache.release(pinned)
        assert cache.reclaimable_pages == cache.held_pages
        cache.clear()
        assert cache.held_pages == 0
        assert _all_pages_free(pool)

    def test_engine_prefix_hit_shares_pages_and_cow_splits(self, gpt):
        """End-to-end: a shared 32-token system prompt prefills once;
        later requests attach its 2 pages read-only and outputs stay
        exactly greedy."""
        sys_prompt = _prompts([32], seed=9)[0]
        suffixes = _prompts([5, 7, 3], seed=10)
        eng = InferenceEngine(gpt, num_slots=4, max_length=64,
                              decode_block=4, kv_page_size=16,
                              prefix_cache=0.5)
        refs, outs = [], []
        for sfx in suffixes:
            prompt = sys_prompt + sfx
            refs.append(_ref_generate(gpt, prompt, 6))
            h = eng.submit(prompt, SamplingParams(
                max_new_tokens=6, eos_token_id=NO_EOS))
            eng.run()
            outs.append(h.tokens)
        assert outs == refs
        cst = eng.prefix_cache.stats()
        assert cst['hits'] >= 2 and cst['tokens_reused'] >= 64
        assert cst['held_pages'] >= 2
        pst = eng.pool.stats()
        assert pst['holds_live'] >= 1
        # every page accounted: held by cache or free
        eng.prefix_cache.clear(force=True)
        assert _all_pages_free(eng.pool)


# ---------------------------------------------------------------------------
# engine: parity, recompiles, capacity, failover
# ---------------------------------------------------------------------------

class TestPagedEngine:

    def test_paged_greedy_parity_vs_row_and_generate(self, gpt):
        prompts = _prompts([3, 9, 5, 14, 7, 11])
        news = [6, 9, 4, 12, 8, 5]
        params = [SamplingParams(max_new_tokens=n, eos_token_id=NO_EOS)
                  for n in news]
        row = InferenceEngine(gpt, num_slots=3, max_length=64,
                              decode_block=4)
        paged = InferenceEngine(gpt, num_slots=3, max_length=64,
                                decode_block=4, kv_page_size=16)
        hr = row.generate_many(prompts, params)
        hp = paged.generate_many(prompts, params)
        for h_row, h_paged, p, n in zip(hr, hp, prompts, news):
            ref = _ref_generate(gpt, p, n)
            assert h_row.tokens == ref
            assert h_paged.tokens == ref, 'paged diverged from generate()'
        assert paged.stats()['kv_layout'] == 'paged'
        assert row.stats()['kv_layout'] == 'row'
        assert _all_pages_free(paged.pool)

    def test_paged_zero_recompiles_after_warmup(self, gpt):
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, kv_page_size=16)
        eng.generate_many(
            _prompts([3, 9, 6], seed=1),
            [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 3)
        traces = dict(eng.stats()['traces'])
        assert traces.get('paged_decode_step', 0) <= 1
        assert traces.get('paged_decode_step_half', 0) <= 1
        compiles_before = obs.get_registry().value(
            'paddle_jit_compiles_total')
        hs = eng.generate_many(
            _prompts([4, 8, 5, 16, 7], seed=2),
            [SamplingParams(max_new_tokens=6, eos_token_id=NO_EOS)] * 5)
        assert all(h.status == FINISHED for h in hs)
        assert eng.stats()['traces'] == traces, \
            'paged admission retraced a program'
        assert obs.get_registry().value('paddle_jit_compiles_total') \
            == compiles_before, 'paged admission triggered an XLA compile'

    def test_paged_int8_engine_decodes_clean(self, gpt):
        """int8 KV drifts logits but must stay a working engine; early
        greedy tokens agree with the f32 reference on a tiny model."""
        prompts = _prompts([6, 11], seed=4)
        eng = InferenceEngine(gpt, num_slots=2, max_length=64,
                              decode_block=2, kv_page_size=16,
                              kv_quant='int8')
        hs = eng.generate_many(
            prompts,
            [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)] * 2)
        agree = total = 0
        for h, p in zip(hs, prompts):
            assert h.status == FINISHED
            ref = _ref_generate(gpt, p, 4)
            agree += sum(g == w for g, w in zip(h.tokens[:2], ref[:2]))
            total += 2
        assert agree / total >= 0.75
        assert _all_pages_free(eng.pool)

    def test_paged_admits_3x_concurrent_at_equal_hbm(self, gpt):
        """The acceptance headline: same pool bytes, short requests —
        the paged pool seats >= 3x the row pool's concurrency (page-
        granular reservations vs whole max_length rows)."""
        prompts = _prompts([6] * 15, seed=6)
        params = [SamplingParams(max_new_tokens=4, eos_token_id=NO_EOS)
                  for _ in prompts]
        row = InferenceEngine(gpt, num_slots=4, max_length=64,
                              decode_block=2)
        paged = InferenceEngine(gpt, num_slots=15, max_length=64,
                                decode_block=2, kv_page_size=16,
                                kv_pages=16)
        # 16 pages x 16 rows == 4 slots x 64 rows: equal KV HBM
        assert paged.pool.pool_bytes <= row.pool.pool_bytes
        for eng in (row, paged):
            for p, sp in zip(prompts, params):
                eng.submit(p, sp)
            eng.step()                       # one admission pass
        row_seated = row.pool.used_count
        paged_seated = paged.pool.used_count
        assert row_seated == 4               # slot-bound
        assert paged_seated >= 3 * row_seated
        for eng in (row, paged):             # finish clean
            eng.run()
        assert row.stats()['completed'] == 15
        assert paged.stats()['completed'] == 15

    def test_requeue_on_page_exhaustion_completes_everyone(self, gpt):
        """Oversubscribed pages: admission requeues on PagePoolExhausted
        and every request still finishes with greedy parity."""
        prompts = _prompts([6, 9, 5, 12, 7, 4, 10, 8], seed=8)
        eng = InferenceEngine(gpt, num_slots=8, max_length=64,
                              decode_block=2, kv_page_size=16,
                              kv_pages=17)   # ~4 concurrent short reqs
        hs = eng.generate_many(
            prompts,
            [SamplingParams(max_new_tokens=5, eos_token_id=NO_EOS)] * 8)
        for h, p in zip(hs, prompts):
            assert h.status == FINISHED
            assert h.tokens == _ref_generate(gpt, p, 5)
        assert eng.stats()['failed'] == 0
        assert _all_pages_free(eng.pool)

    def test_evict_all_returns_every_page_100_cycles(self, gpt):
        """Failover loop: kill (evict_all) mid-flight and resubmit, 100
        cycles — the page pool must end every cycle fully accounted
        (free + cache-held == all pages) and fully free at the end."""
        eng = InferenceEngine(gpt, num_slots=4, max_length=64,
                              decode_block=2, kv_page_size=16,
                              prefix_cache=0.25)
        prompts = _prompts([6, 21], seed=12)
        params = [SamplingParams(max_new_tokens=8, eos_token_id=NO_EOS)
                  for _ in prompts]
        total = eng.pool.num_pages - 1
        for cycle in range(100):
            for p, sp in zip(prompts, params):
                eng.submit(p, sp)
            eng.step()                       # seat + prefill, mid-flight
            orphans = eng.evict_all()
            assert len(orphans) == 2, f'cycle {cycle} lost a handle'
            assert eng.pool.used_count == 0
            held = eng.prefix_cache.held_pages
            assert eng.pool.free_page_count + held == total, \
                f'cycle {cycle} leaked pages'
            assert eng.pool.used_page_count == held
        assert eng.prefix_cache.held_pages <= \
            eng.prefix_cache.budget_pages
        eng.prefix_cache.clear(force=True)
        assert _all_pages_free(eng.pool)
        # the engine stays serviceable after the 100th kill
        h = eng.submit(prompts[0], params[0])
        eng.run()
        assert h.status == FINISHED
        assert h.tokens == _ref_generate(gpt, prompts[0], 8)


# ---------------------------------------------------------------------------
# tier-1 bench guard: the paged_ab acceptance bars at smoke scale
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_paged_guard():
    """The ISSUE-16 acceptance bars, asserted on the real bench function
    at guard scale: equal-or-smaller pool bytes, >= 3x concurrent
    admissions, bit-exact greedy parity on both arms, zero recompiles
    after warmup, prefill reuse through shared pages, and the int8
    logit-RMSE quality bound.

    Full-gate tier: every bar here is independently asserted by the
    fast-tier functional tests above (TestPagedEngine parity /
    zero-recompile / 3x-admission / int8, TestPagedPrefixCache page
    sharing) — this end-to-end A/B re-proves them through bench.py at
    ~50 s, which the fast tier's wall-clock budget can't carry."""
    import bench
    res = bench.paged_ab(num_requests=6, cap_requests=18, trials=1)
    assert res['equal_hbm'], 'paged pool used MORE bytes than row pool'
    assert res['capacity_ratio'] >= 3.0, \
        f'paged admitted only {res["capacity_ratio"]}x the row pool'
    assert res['cap_completed'] == res['cap_requests']
    assert res['parity'], 'paged/row outputs diverged from generate()'
    assert res['recompiles_after_warmup'] == 0, \
        'paged trace recompiled after warmup'
    assert res['prefill_reuse_paged'] > 0
    assert res['int8']['within_bound'], \
        f"int8 logit RMSE {res['int8']['logit_rmse_rel']} above bound"
    assert res['int8']['greedy_agree_rate'] >= 0.75
