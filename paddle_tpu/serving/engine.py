"""Continuous-batching inference engine over a slot-pooled KV cache.

`GenerationMixin.generate()` is batch-synchronous: the whole batch is
admitted together, decodes in lock-step, and every sequence waits for
the slowest one. This engine is the iteration-level alternative (Orca,
Yu et al. OSDI'22): a fixed pool of KV slots (kv_pool.SlotPool), an
FCFS scheduler that admits queued requests into freed slots BETWEEN
decode steps (scheduler.FCFSScheduler), and ONE compiled decode step
that advances every occupied slot a block of tokens at a time with
per-slot position offsets, an active-slot mask, and per-slot sampling
params carried as arrays — so heterogeneous requests (different prompt
lengths, token budgets, temperatures, eos ids) share a single XLA
program and admission/retirement never recompiles anything.

A decode round RUNS AHEAD of the host whenever nothing could be seated
or retired between it and the next (`InferenceEngine.step`): the next
block is dispatched before the tokens of the one in flight are fetched,
so the device never waits for the fetch, the emission and the dispatch.
Its slot state is advanced on the host at dispatch time and its pending
tokens stay on the device (`SlotState.carried`).

Compiled-program inventory (asserted by the zero-recompile tests):
- the decode-block step (shapes fixed by num_slots/max_length/block)
  at two lengths of attention: over every row of a slot, and over the
  first half — picked per round from the slots' positions, both built
  at the first decode dispatch (`_decode_program`),
- one prefill program per length bucket (right-padded prompts; pad KV
  lands above the live position where the slot-causal mask hides it
  until the slot's own decode overwrites it — the stale-slot argument
  speculative decoding already relies on; a model whose cache also holds
  recurrent state or a ring of a window's rows, which no mask can hide,
  gets the prompt's real length into the same program:
  `_state_prefill_fn`),
and, when the latency stack is enabled (ISSUE 9):
- one chunk-prefill program per chunk bucket (chunked prefill AND
  prefix-cache suffix prefill — `start`/`slot`/`src` are traced),
- one speculation round per k (draft + verify; replaces the decode
  block when a draft model is configured),
- one draft prefill program per bucket.

Copy surface: the pool is ONE stacked array per layer leaf
(kv_pool.SlotPool), DONATED into the decode block and the speculation
round, which carry it through their scan and return it: the pool is
updated in place and a round copies none of it. Prefill/chunk programs
take and return one row, undonated, and the pool's own seat program
writes that row into its slot in place (`SlotPool.set_row`). Donation
never changes values, and the engine guards the failure mode it
introduces: a donated program (decode, speculation, seat, copy) dying
mid-call invalidates the pool it was given, so the engine rebuilds a
zero pool and force-clears the prefix cache before re-raising
(`_recover_pool`) — the error still fails over normally, but the engine
stays serviceable.

Greedy requests take the raw argmax exactly like `generate()`, so their
outputs are token-for-token identical to a per-request generate() call
(the bench.py `serving` phase guards this bit-for-bit).

Resilience: host<->device transfers ride `resilience.call_with_retry`
(transient blips retried with backoff); any prefill/transfer failure is
a REQUEST-level error — the handle turns FAILED, the slot frees, and
the engine keeps serving everyone else.
"""
from __future__ import annotations

import collections
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..observability import reqledger as _reqledger
from ..jit import functional_state
from ..nlp.generation import (_NEG_INF, cached_forward, experts_touched,
                              latent_layers, ring_layers, routing_scope,
                              state_layers, state_scope)
from ..ops import pallas as _pallas
from ..resilience import RetryPolicy, call_with_retry
from ..tensor import Tensor
from .adapters.apply import adapter_scope as _adapter_scope
from .api import GREEDY, RUNNING, RequestHandle, SamplingParams
from .kv_pool import (PagePoolExhausted, PagedSlotPool, PoolLostError,
                      SlotPool, gather_pages, scatter_pages)
from .prefix_cache import PagedPrefixCache, RadixPrefixCache
from .scheduler import FCFSScheduler
from .slot_state import SlotState

#: A decode round in flight: dispatched, its tokens not fetched yet.
#: `toks` ([num_slots, block]) and `routing` (the routing counts, or
#: None) are still the device's; `parts` is the (slot, handle) of every
#: slot that decoded in it AS DISPATCHED, so its tokens reach nobody
#: seated in a slot since and nobody retired since; `t0` its dispatch
#: instant on the host clock.
_Round = collections.namedtuple('_Round', 'toks routing parts t0')

# occupancy is a ratio; the latency-shaped default buckets are wrong here
_OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


def _to_device(x):
    """Host->device staging of prompts (module-level so fault-injection
    tests can patch it; production call sites wrap it in retry)."""
    return jnp.asarray(x)


def _from_device(x):
    """Device->host fetch of sampled tokens (patchable, see _to_device)."""
    return np.asarray(x)


@jax.named_scope('sample')
def sample_rows(logits, temp, topk, topp, greedy, keys, steps):
    """Vectorized per-row sampling over a [N, V] logits slab with PER-ROW
    params (arrays, not static config — one compiled program serves every
    request mix). Greedy rows take the raw argmax — bit-identical to
    `_next_token`'s greedy path — so a greedy request's tokens never
    depend on its batch neighbours. Sampling rows apply temperature, then
    top-k, then top-p (the `_process_logits` order) and draw
    categorically with their own folded key."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def do_sample(_):
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        # per-row top-k: k <= 0 or >= v disables (mirrors _process_logits)
        srt = jax.lax.top_k(scaled, v)[0]                   # descending
        k_eff = jnp.where((topk > 0) & (topk < v), topk,
                          v).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, k_eff[:, None] - 1, axis=-1)
        x = jnp.where(scaled < kth, _NEG_INF, scaled)
        # per-row top-p over the already-top-k-filtered slab
        srt_p = jax.lax.top_k(x, v)[0]
        probs = jax.nn.softmax(srt_p, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum((cum - probs) < topp[:, None], axis=-1) - 1
        cutoff = jnp.take_along_axis(
            srt_p, jnp.clip(cutoff_idx, 0, v - 1)[:, None], axis=-1)
        x = jnp.where((topp[:, None] < 1.0) & (x < cutoff), _NEG_INF, x)
        keys_f = jax.vmap(jax.random.fold_in)(keys, steps)
        return jax.vmap(jax.random.categorical)(keys_f,
                                                x).astype(jnp.int32)

    # all-greedy batches (the common serving mix) skip the two full-vocab
    # sorts + RNG entirely — lax.cond picks the branch at RUN time, so
    # the mix can change step to step without recompiling
    sampled = jax.lax.cond(jnp.all(greedy), lambda _: greedy_tok,
                           do_sample, None)
    return jnp.where(greedy, greedy_tok, sampled)


# Why each engine mode cannot serve a model that keeps recurrent slot
# state (a cache entry that is not K and V: `generation.state_layers`).
# Rows of K and V can be shared up to a position and hidden above one by
# a mask; a state stands at ONE position and can be neither.
_STATE_REFUSALS = {
    'prefix_cache':
        'a retained row\'s state stands at the END of its donor\'s '
        'prompt, not at the shared prefix, so a hit would seat the wrong '
        'state: reuse needs a snapshot of the state at the prefix',
    'prefill_chunk_tokens':
        'a chunk would have to carry the state from the chunk before and '
        'stop before the last prompt token, and a tail chunk shifted '
        'down to fit the slot forwards tokens twice — harmless for K '
        'and V, wrong for a state',
    'draft_model':
        'speculation rejects proposed tokens by moving the position back, '
        'and a state cannot be moved back: it needs a snapshot per '
        'proposed token',
    'kv_page_size / kv_pages':
        'the paged pool holds [pages, page, H, D] leaves and a page '
        'table; a state leaf has no rows to page',
    'kv_quant':
        'int8 KV lives in the paged pool (per-page scales), which cannot '
        'hold a state leaf',
}


# Why each engine mode cannot serve a model one of whose layers keeps a
# RING: a (K, V) entry of fewer rows than the slot, position p in row
# `p mod rows` (`generation.ring_layers`). A ring's rows are K and V,
# but it stands at ONE position like a state: a row written past that
# position has replaced one the window still needs.
_RING_REFUSALS = {
    'prefix_cache':
        'a retained row\'s ring stands at the END of its donor\'s '
        'prompt: the rows of the shared prefix\'s last window have been '
        'replaced by what followed, so a hit would seat the wrong ring: '
        'reuse needs a snapshot of the ring at the prefix',
    'prefill_chunk_tokens':
        'a chunk would have to find the ring as the chunk before left it '
        'and stop at the prompt\'s real end, and a tail chunk shifted '
        'down to fit the slot forwards tokens twice at rows the ring has '
        'already given away',
    'draft_model':
        'speculation rejects proposed tokens by moving the position back, '
        'and a ring cannot be moved back: the rejected tokens have '
        'already replaced the rows a window back',
    'kv_page_size / kv_pages':
        'the paged pool has one page geometry and one table of '
        'max_length rows for every layer; a ring is a leaf of another '
        'length, with other heads',
    'kv_quant':
        'int8 KV lives in the paged pool (per-page scales), which cannot '
        'hold a ring',
}


# Why an engine mode cannot serve a model whose cache entries are LATENT:
# rows with no head axis (`generation.latent_layers`). Its rows are what
# K and V rows are, so the prefix cache, chunked prefill and speculation
# serve it as they serve K and V (`copy_slot` maps over any leaf; a chunk
# and a verify are calls against rows held); only what reasons BY HEAD
# does not.
_LATENT_REFUSALS = {
    'kv_page_size / kv_pages':
        'the paged pool holds [pages, page, H_kv, D] leaves, gathers them '
        'to [N, max_length, H_kv, D] and scatters whole [H_kv, D] rows '
        'back; a latent leaf is [slot, row, C], with no head axis',
    'kv_quant':
        'int8 KV lives in the paged pool with one scale a (page, head); a '
        'latent row has no heads, and the one norm that made it spans all '
        'of them',
}


def _whole_prefill(fwd, ids, row_spec):
    """Forward a whole prompt (batch-1, right-padded to its bucket) over
    a zero row of `row_spec` -> the row it wrote. The one body of every
    whole-prefill program (the row pool's, the draft's, the paged
    pool's): the slot is the literal 0 and there is no mask, which is
    how `cached_forward` is told that the call brings all its rows."""
    slab = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), row_spec)
    _, slab = fwd(ids, slab, jnp.int32(0), 0, None)
    return slab


def _refuse_modes(model, asked, keeps, refusals):
    """ValueError naming the first engine mode in `asked` ({mode: was
    it asked for}) that a model whose cache `keeps` such an entry cannot
    run under, with `refusals`' reason. Nothing runs and is silently
    wrong."""
    for mode, reason in refusals.items():
        if asked.get(mode):
            raise ValueError(
                f'{type(model).__name__} keeps {keeps}, which {mode} '
                f'cannot serve: {reason}')


class InferenceEngine:
    """Single-host continuous-batching engine around one causal-LM.

    Args:
        model: any `GenerationMixin` model honoring the `init_cache` /
            cached-forward contract (weights are snapshotted at
            construction). Put the model in eval() yourself if it holds
            dropout state; the engine forces eval.
        num_slots: KV slots = max concurrently decoding requests.
        max_length: per-slot cache length; every request needs
            prompt_len + max_new_tokens <= max_length.
        decode_block: tokens decoded per compiled step (device-side
            lax.scan). Larger blocks amortize host dispatch; a request
            finishing mid-block wastes at most block-1 sub-steps.
        buckets: prefill length buckets (default: powers of two).
        max_prefill_tokens: per-iteration prefill budget (scheduler).
        eos_token_id: default eos (-1 = never); per-request params win.
        retry_policy: resilience.RetryPolicy for host<->device
            transfers (default: flag-configured policy).
        prefix_cache: radix prefix cache over the slot pool — shared
            prompt prefixes (system prompts) prefill once. True = cache
            at the default 0.5 pool fraction, a float = that fraction,
            a ready `RadixPrefixCache` = use it, None/False = off.
        prefill_chunk_tokens: prompts longer than this prefill in
            bucket-shaped chunks across successive decode rounds
            (Sarathi-Serve-style interleaving) instead of stalling
            every in-flight request's TPOT behind one long prefill.
            None = whole-prompt prefill (the PR-4 behavior).
        draft_model: optional smaller causal LM for per-slot
            speculative decoding: each round it proposes
            `num_draft_tokens` greedily and the decode step verifies
            k+1 positions in ONE target forward, accepting the longest
            matching prefix (output identical to plain greedy). Draft
            KV lives in a parallel SlotPool. Sampling requests in the
            same engine simply decode one token per round.
        num_draft_tokens: draft proposals per speculation round (k).
        kv_page_size: setting this (or kv_pages/kv_quant) switches the
            KV cache to the PAGED layout (kv_pool.PagedSlotPool):
            fixed-size pages + a per-slot page table, reservation-based
            admission (page exhaustion requeues instead of failing),
            prefix retention by PAGE (copy-on-write shared), and the
            paged decode/prefill/spec programs that gather/scatter
            through the table. max_length must be a multiple.
        kv_pages: total pages in the paged pool (page 0 is the null
            page). Default num_slots * pages_per_slot + 1 — set LOWER
            to oversubscribe HBM: short requests then reserve only the
            pages they can touch, admitting more concurrent requests
            than row slots would at the same byte budget.
        kv_quant: 'int8' stores paged KV as int8 with per-(page, head)
            absmax scales (half/quarter the bytes of bf16/f32 KV);
            gather dequantizes, scatter requantizes touched pages. The
            bench `paged_ab` phase measures the logit-RMSE cost.
        adapter_bank: a `serving.adapters.AdapterBank` attached to this
            model — enables `submit(..., adapter_id=)` multi-tenant
            LoRA serving: the packed bank arrays and a per-slot adapter
            row vector ride every decode/prefill/spec program as TRACED
            inputs, so one compiled program serves any heterogeneous
            adapter mix (loads/evictions/hot-swaps never recompile).
            Requests pin their bank slot at admission and release it at
            retirement; the prefix cache keys adapter requests under
            `(adapter_id, adapter_version)` namespaces so tenants never
            share prefix KV across adapters.

    Not thread-safe: one engine is one event loop; drive it with
    `step()`, `run()`, `stream()`, or `generate_many()`.
    """

    @_obs.telemetry.constructing('serving.engine_init', lambda self: {
        'slots': self.pool.num_slots, 'max_length': self.pool.max_length,
        'pool_bytes': self.pool.pool_bytes,
        'programs_resolved': self.programs_preloaded})
    def __init__(self, model, num_slots: int = 8, max_length: int = 256,
                 decode_block: int = 4,
                 buckets: Optional[Sequence[int]] = None,
                 max_prefill_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 dtype=None, retry_policy: Optional[RetryPolicy] = None,
                 max_wait_s: Optional[float] = None,
                 prefix_cache=None,
                 prefill_chunk_tokens: Optional[int] = None,
                 draft_model=None, num_draft_tokens: int = 4,
                 weight_version: int = 0,
                 kv_page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 adapter_bank=None):
        cfg = getattr(model, 'config', None)
        max_pos = getattr(cfg, 'max_position_embeddings', None)
        if max_pos is not None and max_length > max_pos:
            raise ValueError(
                f'max_length {max_length} exceeds the model\'s '
                f'max_position_embeddings {max_pos}')
        if decode_block < 1:
            raise ValueError('decode_block must be >= 1')
        asked = {'prefix_cache': prefix_cache,
                 'prefill_chunk_tokens': prefill_chunk_tokens,
                 'draft_model': draft_model is not None,
                 'kv_page_size / kv_pages': kv_page_size is not None
                 or kv_pages is not None,
                 'kv_quant': kv_quant is not None}
        for m in (model, draft_model):
            if m is None:
                continue
            entries = jax.eval_shape(lambda: m.init_cache(1, max_length))
            if state_layers(entries):
                _refuse_modes(m, asked, 'recurrent slot state (cache '
                              'entries that are not K and V)',
                              _STATE_REFUSALS)
            if ring_layers(entries, max_length):
                _refuse_modes(m, asked, 'a ring of a window\'s rows '
                              '(cache entries shorter than the slot)',
                              _RING_REFUSALS)
            if latent_layers(entries):
                _refuse_modes(m, asked, 'latent rows (cache entries with '
                              'rows and no heads)', _LATENT_REFUSALS)
        model.eval()
        self.model = model
        self._params, self._frozen, self._buffers = functional_state(model)
        # monotone weight-version tag: bumped by swap_weights (the
        # trainer→serving hot-swap path); every request is stamped with
        # the version it decodes under at admission
        self.weight_version = int(weight_version)
        self.eos_token_id = int(
            getattr(cfg, 'eos_token_id', -1) if eos_token_id is None
            else eos_token_id)
        self.decode_block = int(decode_block)
        self._paged = (kv_page_size is not None or kv_pages is not None
                       or kv_quant is not None)
        if self._paged:
            self.pool = PagedSlotPool(
                model, num_slots, max_length, dtype, buckets,
                page_size=int(kv_page_size) if kv_page_size else 16,
                num_pages=kv_pages, quant=kv_quant)
        else:
            self.pool = SlotPool(model, num_slots, max_length, dtype,
                                 buckets)
        self.scheduler = FCFSScheduler(max_prefill_tokens,
                                       max_wait_s=max_wait_s)
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError('prefill_chunk_tokens must be >= 1')
        self.prefill_chunk_tokens = (int(prefill_chunk_tokens)
                                     if prefill_chunk_tokens else None)
        self.pool.prefill_chunk_tokens = self.prefill_chunk_tokens
        if isinstance(prefix_cache, RadixPrefixCache):
            if self._paged != isinstance(prefix_cache, PagedPrefixCache):
                raise ValueError(
                    'prefix cache layout does not match the pool: a '
                    'paged engine needs a PagedPrefixCache (and a row '
                    'engine a RadixPrefixCache)')
            self.prefix_cache: Optional[RadixPrefixCache] = prefix_cache
        elif prefix_cache:
            fraction = (0.5 if prefix_cache is True
                        else float(prefix_cache))
            cache_cls = (PagedPrefixCache if self._paged
                         else RadixPrefixCache)
            self.prefix_cache = cache_cls(self.pool, fraction)
        else:
            self.prefix_cache = None
        if self.prefix_cache is not None:
            budget = (self.prefix_cache.budget_pages if self._paged
                      else self.prefix_cache.budget_slots)
            if budget < 1:
                raise ValueError(
                    'prefix cache budget rounds to zero '
                    + ('pages' if self._paged else 'slots')
                    + '; raise the fraction or the pool size (retention '
                    'must leave capacity for decode)')
        if self.prefix_cache is not None:
            self.prefix_cache.set_version(self.weight_version)
        self.draft_model = draft_model
        self.spec_k = int(num_draft_tokens)
        if draft_model is not None:
            if self.spec_k < 1:
                raise ValueError('num_draft_tokens must be >= 1')
            d_cfg = getattr(draft_model, 'config', None)
            d_pos = getattr(d_cfg, 'max_position_embeddings', None)
            if d_pos is not None and max_length > d_pos:
                raise ValueError(
                    f'max_length {max_length} exceeds the DRAFT model\'s '
                    f'max_position_embeddings {d_pos}')
            draft_model.eval()
            self._draft_state = functional_state(draft_model)
            # parallel draft KV: same slot indices as the target pool
            # (never alloc/freed itself — slot i of both pools always
            # belongs to the same request)
            self.draft_pool = SlotPool(draft_model, num_slots,
                                       max_length, dtype, buckets)
        else:
            self._draft_state = None
            self.draft_pool = None
        # multi-tenant LoRA serving (ISSUE 19): the bank's packed
        # factor arrays + a per-slot adapter row vector are TRACED
        # inputs to every program below — adapter loads, evictions and
        # hot-swaps move array contents, never avals, so the compiled
        # set is exactly the bank-less engine's (one decode block, one
        # prefill per bucket, ...), just with wider signatures
        self.adapter_bank = adapter_bank
        # slot -> [handle, prefill cursor]: slots mid-chunked-prefill
        # (inactive for decode until the cursor reaches the prompt end)
        self._prefilling: dict = {}
        self._retry = retry_policy or RetryPolicy()
        self._draining = False
        self._drain_deadline_s: Optional[float] = None
        self._preempt = None
        # observability scope for degraded-state notes: None = the whole
        # process (single-engine deployments); the router tags each
        # replica's engine 'replica:N' so /healthz and placement can
        # tell WHICH replica is draining
        self.obs_scope: Optional[str] = None

        n = self.pool.num_slots
        # per-slot decode state + sampling params, host-authoritative:
        # ONE buffer (`SlotState`), which every decode program takes as
        # one argument and one transfer. The names are views into it
        # with the dtypes they always had; admission, emission and
        # retirement write through them (never rebind one), so the
        # buffer IS the state and nothing is packed per round (the KV
        # pool stays on device)
        slots = self._slot_state = SlotState(n)
        self._tok = slots.tok           # pending (last emitted)
        self._pos = slots.pos           # its cache slot/position
        self._steps = slots.steps       # per-request sample index
        self._active = slots.active
        self._temp = slots.temp
        self._topk = slots.topk
        self._topp = slots.topp
        self._greedy = slots.greedy
        self._keys = slots.keys
        self._eos_arr = slots.eos       # spec accept stop
        self._adapter_rows = slots.adapter_rows   # 0 = base adapter
        self._carried = slots.carried   # the pending token is the device's
        self._slot_req: dict = {}               # slot -> RequestHandle
        # host only: each slot's token budget (`max_new_tokens`), so
        # that "nobody ends by length inside the round in flight" is
        # `_steps < _budget` — arithmetic, no token value needed
        self._budget = np.zeros(n, np.int32)
        # decode rounds dispatched and not yet fetched, oldest first:
        # none in the serial order, one while the engine runs ahead (two
        # between an ahead dispatch and the fetch that follows it)
        self._rounds: collections.deque = collections.deque()
        # the tokens of the block dispatched last, on the device: every
        # decode program takes them beside the slot state and reads the
        # `carried` slots' pending tokens off their last column
        self._prev_toks = jnp.zeros((n, self.decode_block), jnp.int32)
        self._t_emitted = 0.0           # the last emission's instant

        # per layer, the most rows a query can see (a window layer's
        # window, else the slot): what `needed_rows` is counted from
        # — of the layers that ATTEND: a layer whose cache entry is a
        # state leaf reads no rows
        n_layers = len(self.pool.row_spec)
        windows = getattr(model, 'attention_windows',
                          lambda: (None,) * n_layers)()
        attending = [i for i in range(n_layers)
                     if i not in self.pool.state_layers]
        self._layer_rows = np.array(
            [self.pool.max_length if windows[i] is None
             else min(int(windows[i]), self.pool.max_length)
             for i in attending], np.int64)
        # which of those keep a ring, and the rows every slot holds there:
        # a ring is read whole whatever the round's program
        self._ring = np.array([i in self.pool.ring_layers
                               for i in attending], bool)
        self._ring_rows = sum(self.pool.row_spec[i][0].shape[1]
                              for i in self.pool.ring_layers)
        # the decode block exists at two lengths of attention: every
        # row of a slot, and the first half — which `_decode_round`
        # picks while the batch's positions allow it. 0 where a block
        # alone would pass the half: then it could never be picked
        half = self.pool.max_length // 2
        self._half_rows = half if half > self.decode_block else 0
        self._num_experts = int(getattr(cfg, 'num_experts', 0) or 0)
        # how many streams of the hidden size a layer hands the next,
        # where the model's residual path is wider than one (0: one,
        # and a decode round's span says nothing)
        self._residual_streams = int(
            getattr(model, 'residual_streams', 0) or 0)
        # bucket -> the query-key pairs ONE layer's attention computes
        # in a whole prefill, where that prefill attends over its own
        # tokens (the model's to say; None: the span says nothing)
        self._own_tokens_pairs = getattr(model, 'own_tokens_pairs', None)
        # bucket -> what a whole prefill's program runs, by the model's
        # own dispatch (likewise; a dict of the span's attributes: the
        # chunks ONE layer's scan walks, `kda_chunks`, `ssm_chunks`; the
        # layers run as ONE kernel, `ssm_`, `expert_kernel_layers`)
        self._scan_chunks = getattr(model, 'scan_chunks', None)
        # how many state layers' recurrences a decode sub-step runs as a
        # kernel, where the model has one to ask (its own dispatch, with
        # the leaves as the pool holds them; None: the span says nothing)
        by_kernel = getattr(model, 'state_kernel_layers', None)
        self._state_kernel_layers = None if by_kernel is None else int(
            by_kernel(self.pool.row_spec, self.pool.num_slots))
        # either program's rows -> per attending layer, the row tile by
        # which its decode attention is bounded per slot there, 0 where
        # it reads every row: what `read_rows` counts such a layer by
        self._attending = attending
        self._tiles = {
            rows: self._bounded_tiles(rows)
            for rows in (self.pool.max_length, self._half_rows) if rows}

        self._trace_counts = collections.Counter()
        self._counts = collections.Counter()
        # enrolled in the program store: per-program FLOPs/bytes/peak
        # attribution for the decode block and each prefill bucket, off
        # the same single compile each program costs anyway — and, with
        # a persistent store, a cold replica LOADS these instead of
        # compiling. The statics cover what the avals cannot: the model
        # body/config and the engine geometry (decode_block is a scan
        # length, invisible in any input aval). Sibling replicas over
        # the same model produce identical keys, so N replicas compile
        # (or load) each program once.
        from .. import programs as _programs
        store = _programs.get_store()
        engine_statics = {
            'model': type(model).__qualname__,
            'model_src': _programs.code_token(type(model)),
            'config': _programs.describe_statics(cfg),
            'num_slots': self.pool.num_slots,
            'max_length': self.pool.max_length,
            'decode_block': self.decode_block,
        }
        if self.adapter_bank is not None:
            # ONLY the packed geometry + target-site set ride the key:
            # which adapters are resident is array CONTENT, invisible
            # to the program — but an adapter engine must never share
            # a store key with a base engine (different signatures)
            engine_statics['adapters'] = \
                self.adapter_bank.describe_statics()
        if self._paged:
            # page geometry is invisible in the contiguous avals the
            # decode scan sees (the table aval only fixes num_slots x
            # pages_per_slot), so it MUST ride the statics — and paged
            # vs row programs must never share a store key
            engine_statics.update(
                kv_layout='paged',
                kv_page_size=self.pool.page_size,
                kv_pages=self.pool.num_pages,
                kv_quant=self.pool.quant or 'none')
        # `rows` rides the half program's statics (a slice length,
        # invisible in any input aval); the whole program keeps the
        # name and the statics it always had
        half_statics = dict(engine_statics, rows=self._half_rows)
        self._decode_half_jit = None
        self._decode_resolved = False
        # nothing to settle where no leaf asks (every backend but a TPU;
        # a paged pool, whose pages are another geometry)
        self._pool_layout_settled = self._paged or not any(
            fmt is not None for fmt in self.pool.own_layout)
        if self._paged:
            # page buffers (and scales) are donated exactly like the
            # row pool: decode/spec alias the pool in place;
            # prefill/chunk stay UNDONATED so a prefill failure remains
            # request-level (a donated prefill dying would invalidate
            # the whole pool)
            self._decode_jit = store.wrap_jit(
                self._paged_decode_fn, name='serving.paged_decode_block',
                kind='serving', statics=engine_statics,
                donate_argnums=(3, 4))
            if self._half_rows:
                self._decode_half_jit = store.wrap_jit(
                    self._paged_decode_half_fn,
                    name=f'serving.paged_decode_block_r{self._half_rows}',
                    kind='serving', statics=half_statics,
                    donate_argnums=(3, 4))
            self._prefill_jit = store.wrap_jit(   # 1 trace per bucket
                self._paged_prefill_fn,
                name_fn=lambda args: f'serving.paged_prefill_'
                                     f'{args[6].shape[1]}',
                kind='serving', statics=engine_statics)
            self._chunk_prefill_jit = store.wrap_jit(
                self._paged_chunk_prefill_fn,
                name_fn=lambda args: f'serving.paged_chunk_prefill_'
                                     f'{args[6].shape[1]}',
                kind='serving', statics=engine_statics)
        else:
            # the pool is argument 3 and the second result of both. The
            # whole-length program CHOOSES the layout of the leaves that
            # ask for one of their own (`SlotPool.own_layout`: compiled
            # with AUTO there, `_settle_pool_layout`); every other
            # program is compiled to what the pool then holds
            self._decode_jit = store.wrap_jit(
                self._decode_block_fn, name='serving.decode_block',
                kind='serving', statics=engine_statics,
                donate_argnums=(3,),
                pool_io=self.pool.pool_io(3, (1,), chooses=True))
            if self._half_rows:
                self._decode_half_jit = store.wrap_jit(
                    self._decode_block_half_fn,
                    name=f'serving.decode_block_r{self._half_rows}',
                    kind='serving', statics=half_statics,
                    donate_argnums=(3,),
                    pool_io=self.pool.pool_io(3, (1,)))
            self._prefill_jit = store.wrap_jit(   # 1 trace per bucket
                self._state_prefill_fn if self.pool.stands_at_one_position
                else self._prefill_fn,
                name_fn=lambda args: f'serving.prefill_'
                                     f'{args[3].shape[1]}',
                kind='serving', statics=engine_statics)
            self._chunk_prefill_jit = store.wrap_jit(  # 1 / chunk bucket
                self._chunk_prefill_fn,
                name_fn=lambda args: f'serving.chunk_prefill_'
                                     f'{args[4].shape[1]}',
                kind='serving', statics=engine_statics)
        if draft_model is not None:
            spec_statics = dict(
                engine_statics,
                draft_model=type(draft_model).__qualname__,
                draft_src=_programs.code_token(type(draft_model)),
                draft_config=_programs.describe_statics(
                    getattr(draft_model, 'config', None)),
                spec_k=self.spec_k)
            # one compiled speculation round per k: the drafts/verify
            # shapes are internal, invisible in any input aval, so k
            # MUST ride the statics
            if self._paged:
                self._spec_jit = store.wrap_jit(
                    self._paged_spec_fn,
                    name=f'serving.paged_spec_decode_k{self.spec_k}',
                    kind='serving', statics=spec_statics,
                    donate_argnums=(3, 4, 9))
            else:
                self._spec_jit = store.wrap_jit(
                    self._spec_decode_fn,
                    name=f'serving.spec_decode_k{self.spec_k}',
                    kind='serving', statics=spec_statics,
                    donate_argnums=(3, 7),
                    pool_io=self.pool.pool_io(3, (2,)))
            self._draft_prefill_jit = store.wrap_jit(
                self._draft_prefill_fn,
                name_fn=lambda args: f'serving.draft_prefill_'
                                     f'{args[3].shape[1]}',
                kind='serving', statics=spec_statics)
        self._init_metrics()
        # programs the construction itself resolved (the span's
        # `programs_resolved`): those a persistent store held
        self.programs_preloaded = 0
        if store.persistent:
            # cold-replica warm start: materialize persisted serving
            # executables BEFORE the first request (holds the
            # ref-counted /healthz `warming` state while loading);
            # idempotent, so sibling replicas after the first skip it
            self.programs_preloaded = self.preload_programs()['loaded']

    def preload_programs(self) -> dict:
        """Bulk-load this engine's persisted executables (decode block,
        prefill buckets) from the program store into memory, so the
        first submitted request decodes instead of compiling. No-op
        without a persistent store."""
        from .. import programs as _programs
        return _programs.get_store().preload(match='serving.')

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------
    def _init_metrics(self):
        reg = _obs.get_registry()
        self._m_requests = reg.counter(
            'paddle_serving_requests_total',
            'serving requests by lifecycle event', ('status',))
        self._m_tokens = reg.counter(
            'paddle_serving_tokens_total', 'generated tokens')
        self._m_prefills = reg.counter(
            'paddle_serving_prefills_total', 'prefills by length bucket',
            ('bucket',))
        self._m_prefill_tokens = reg.counter(
            'paddle_serving_prefill_tokens_total',
            'real (unpadded) prompt tokens prefilled')
        self._m_decode_steps = reg.counter(
            'paddle_serving_decode_steps_total',
            'single-token decode sub-steps executed')
        self._m_rounds = reg.counter(
            'paddle_serving_decode_rounds_total',
            'compiled decode-block invocations')
        self._m_rounds_ahead = reg.counter(
            'paddle_serving_decode_rounds_ahead_total',
            'decode blocks dispatched before the tokens of the block in '
            'flight were fetched (nothing could be seated or retired '
            'between the two)')
        self._m_slots = reg.gauge(
            'paddle_serving_slots', 'KV slot capacity')
        self._m_active = reg.gauge(
            'paddle_serving_active_slots', 'slots currently decoding')
        self._m_occupancy = reg.histogram(
            'paddle_serving_slot_occupancy',
            'occupied-slot fraction per decode round',
            buckets=_OCCUPANCY_BUCKETS)
        self._m_ttft = reg.histogram(
            'paddle_serving_ttft_seconds',
            'submit -> first token latency')
        self._m_tpot = reg.histogram(
            'paddle_serving_tpot_seconds',
            'mean inter-token latency per finished request')
        self._m_chunk_rounds = reg.counter(
            'paddle_serving_chunk_rounds_total',
            'chunked-prefill rounds executed')
        self._m_chunk_tokens = reg.counter(
            'paddle_serving_chunk_tokens_total',
            'prompt tokens prefilled via chunk rounds')
        self._m_spec_rounds = reg.counter(
            'paddle_serving_spec_rounds_total',
            'speculation rounds (draft + verify) executed')
        self._m_spec_proposed = reg.counter(
            'paddle_serving_spec_proposed_total',
            'draft tokens proposed to the verifier')
        self._m_spec_accepted = reg.counter(
            'paddle_serving_spec_accepted_total',
            'draft tokens accepted by the verifier')
        # one reporting surface with standalone speculative_generate():
        # the paddle_spec_* family, labeled by source
        self._m_spec_shared = reg.counter(
            'paddle_spec_rounds_total',
            'speculative-decode rounds by source', ('source',))
        self._m_spec_shared_prop = reg.counter(
            'paddle_spec_proposed_drafts_total',
            'draft tokens proposed by source', ('source',))
        self._m_spec_shared_acc = reg.counter(
            'paddle_spec_accepted_drafts_total',
            'draft tokens accepted by source', ('source',))
        self._m_rows_read = reg.counter(
            'paddle_serving_decode_rows_read_total',
            'cache rows a layer\'s decode attention read (slots x the '
            'program\'s rows), summed over decode sub-steps')
        self._m_experts_touched = reg.counter(
            'paddle_serving_moe_experts_touched_total',
            'distinct experts active slots routed to, summed over decode '
            'sub-steps and expert layers')
        self._m_picks_held = reg.counter(
            'paddle_serving_moe_picks_held_total',
            'picks of active slots that landed on an expert held here, '
            'summed over decode sub-steps and expert layers (a layer that '
            'holds a share of its router\'s experts; a layer that holds '
            'them all counts nothing here)')
        self._m_expert_kernel = reg.counter(
            'paddle_serving_moe_expert_kernel_substeps_total',
            'expert-layer decode sub-steps whose routed experts ran as '
            'the pallas kernel (every one on a TPU, none where the loop '
            'over blocks runs)')
        self._m_state_bytes = reg.counter(
            'paddle_serving_slot_state_bytes_total',
            'bytes of slot state that is not K and V (a conv layer\'s '
            'last inputs) read and written by decode sub-steps: active '
            'slots x state layers x leaf bytes x 2')
        self._m_own_layout = reg.gauge(
            'paddle_serving_pool_own_layout_leaves',
            'KV pool leaves held in a layout of their own, the one the '
            'decode block reads them in (a head size that is not whole '
            'lanes, on a TPU); 0 where every leaf keeps the default')
        self._m_latent_row_bytes = reg.gauge(
            'paddle_serving_pool_latent_row_bytes',
            'logical bytes of one cache row over the layers whose entries '
            'are latent (rows with no head axis: latent attention); 0 '
            'where every entry is K and V by head')
        if _obs.enabled():
            self._m_slots.set(self.pool.num_slots)
            self._m_own_layout.set(0)
            self._m_latent_row_bytes.set(self.pool.latent_row_bytes)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _decode_block_fn(self, params, frozen, buffers, pool, state, prev,
                         adapters=None):
        """One compiled program: `decode_block` single-token steps over
        ALL slots (lax.scan), per-slot positions/masks/sampling. `pool`
        is the stacked pool (leaves [num_slots, max_length, H, D]): it
        is the scan's carry and comes back as the second result, so
        the program, which takes it donated, updates it in place.
        `state` is the slot state's one buffer (`SlotState`), unpacked
        here into the values the scan takes; `prev` the tokens of the
        block before ([num_slots, block], the device's own result handed
        back), whose last column is the pending token of every `carried`
        slot. `adapters` (bank-attached engines only) is the packed LoRA
        banks; the per-slot bank rows ride `state` — traced inputs all,
        so any adapter mix replays this same program."""
        self._trace_counts['decode_step'] += 1   # python-level trace count
        fwd = cached_forward(self.model, params, frozen, buffers)
        return self._scan_slots(fwd, pool, self._slot_state.unpack(state),
                                prev, adapters)

    def _decode_block_half_fn(self, params, frozen, buffers, pool, state,
                              prev, adapters=None):
        """`_decode_block_fn` with attention over the first
        `max_length // 2` rows of every slot: a program of its own,
        which `_decode_round` runs while no active slot comes near that
        row."""
        self._trace_counts['decode_step_half'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        return self._scan_slots(fwd, pool, self._slot_state.unpack(state),
                                prev, adapters, rows=self._half_rows)

    def _scan_slots(self, fwd, pool, slots, prev, adapters, rows=None):
        """`_decode_scan` over the slot state as the programs receive
        it: one buffer, unpacked on the device (`SlotState.unpack`: a
        handful of slices and bitcasts, once a block, outside the scan)
        into `slots` — the nine values the scan has always taken, the
        adapter rows and `carried`. A `carried` slot's pending token is
        the last token of `prev`, the block before — ONE `where`,
        outside the scan: the same program runs whether the host had
        fetched that block before it dispatched this one or not, so
        neither order compiles anything of its own."""
        tok = jnp.where(slots.carried, prev[:, -1], slots.tok)
        return self._decode_scan(fwd, pool, tok, *slots[1:9], adapters,
                                 slots.adapter_rows, rows)

    def _decode_scan(self, fwd, pool, tok, pos, steps, active, temp, topk,
                     topp, greedy, keys, adapters=None, adapter_rows=None,
                     rows=None):
        """The per-token scan every decode program runs over a
        contiguous [num_slots, max_length, H, D] view. -> (tokens
        [num_slots, block], the pool) and, where the model has expert
        layers, a third result: int32 [block, 2, expert layers], the
        number of distinct experts the ACTIVE slots routed to in each
        sub-step and layer and, under it, 1 where that layer's routed
        experts ran as the kernel, gathered as the model is traced
        (`routing_scope`). A model without experts leaves nothing
        there, and its program is the one it was.

        `rows` (a Python int; None is `max_length`) is how much of a
        slot attention READS: the mask has `rows` columns and the models'
        cache attention contracts over the first `rows` rows of every
        leaf (`generation.attended_rows`). The caller promises that no
        active slot reaches row `rows` within the block; an inactive
        slot's output is discarded whatever it read. The write never
        narrows: it scatters into the whole leaf, and the whole pool is
        the carry that comes back. At `max_length` nothing is sliced and
        the program is the one that ever was. A layer that keeps a RING
        (`generation.ring_layers`: fewer rows than the slot, position p
        in row `p mod rows`) reads neither the mask nor `rows`: it
        derives what it sees from `pos`, and its leaf is read whole by
        either program.

        The pool is the scan's carry, read (attention) and written (one
        row a slot and leaf, `update_kv_cache` under scope `kv_write`)
        in one body. The write is one scatter a leaf, which the v5e
        compiler runs natively; its former vmapped form became a `while`
        over the slots, twice a layer. What the compiler still does to
        such a body: for one of a layer's two leaves it brings the whole
        leaf into its fast memory for attention, lets the write land
        there and copies the leaf back out (PERF.md sections 5 and 7:
        what turns that off, and why it is not turned off yet)."""
        max_len = self.pool.max_length
        k_slot = jnp.arange(max_len if rows is None else rows,
                            dtype=jnp.int32)

        def sub(carry, _):
            tok, pos, steps, pool = carry
            # pending token writes its KV at slot `pos` and attends to
            # every slot <= pos; freed/stale rows above are masked out
            # (a window layer narrows the mask by itself, from `pos`)
            mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
            with routing_scope(active) as picks:
                logits, pool = fwd(tok[:, None], pool, pos, pos, mask)
            nxt = sample_rows(logits[:, -1], temp, topk, topp, greedy,
                              keys, steps)
            nxt = jnp.where(active, nxt, 0).astype(jnp.int32)
            pos = jnp.minimum(pos + 1, jnp.int32(max_len - 1))
            touched = experts_touched(picks, active)
            return (nxt, pos, steps + 1, pool), \
                ((nxt,) if touched is None else (nxt, touched))

        # the scope is trace-time thread-local state: every tagged
        # Linear the scan body traces adds its gathered per-row delta
        with _adapter_scope(adapters, adapter_rows):
            (tok, pos, steps, pool), (toks, *touched) = jax.lax.scan(
                sub, (tok, pos, steps, pool), None,
                length=self.decode_block)
        return (jnp.transpose(toks), pool, *touched)  # [num_slots, block]

    def _prefill_fn(self, params, frozen, buffers, ids,
                    adapters=None, adapter_rows=None):
        """Prefill ONE request (batch-1, right-padded to its bucket) and
        return the resulting KV ROW — the pool's seat program writes it
        into the slot, so this program never holds the pool and its
        failure costs one request.
        KV-only and fully async: no logits leave the device — the
        request's FIRST token falls out of the next decode block, which
        re-forwards the last prompt token at position s-1 (an identical
        overwrite of its KV slot) and samples from the same
        last-position logits the prefill computed. One compile per
        bucket (ids.shape), everything else traced."""
        self._trace_counts[f'prefill_{ids.shape[1]}'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        with _adapter_scope(adapters, adapter_rows):
            return _whole_prefill(fwd, ids, self.pool.row_spec)

    def _state_prefill_fn(self, params, frozen, buffers, ids, length,
                          adapters=None, adapter_rows=None):
        """`_prefill_fn` for a model that keeps recurrent slot state or
        a ring (`SlotPool.stands_at_one_position`): it also takes the
        prompt's real `length` (traced: still one compile a bucket) and
        seats the state as it stands BEFORE the last prompt token.
        Neither of `_prefill_fn`'s two liberties is harmless to a state:
        the padding up to the bucket would be folded in, and the decode
        block's re-forward of token `length - 1` would fold that token
        in twice. Folding `length - 1` tokens leaves the re-forward to
        complete the state; a one-token prompt seats zeros. A ring takes
        the first harm and not the second: padding written into it
        replaces rows the window still needs, while the re-forward
        writes row `(length - 1) mod rows` again with the same values.
        K and V rows of a full layer are written for the whole bucket
        as ever, and masked by position as ever."""
        with state_scope(length - 1):
            return self._prefill_fn(params, frozen, buffers, ids, adapters,
                                    adapter_rows)

    def _chunk_prefill_fn(self, params, frozen, buffers, row, ids, start,
                          adapters=None, adapter_rows=None):
        """Prefill ONE chunk of ONE request's prompt at positions
        [start, start+chunk): the shared program behind both chunked
        prefill and prefix-cache suffix prefill. Forwards against an
        EXISTING row — the slot's own row for follow-up chunks, the
        RETAINED row on a prefix-cache hit's first chunk (the prefix
        copy IS the row input, so a hit costs exactly one row write,
        never copy + prefill) — with an explicit slot-causal mask
        because `start` is traced. Takes and returns ONE row; one
        compile per chunk bucket (ids.shape); `start` traced."""
        self._trace_counts[f'chunk_prefill_{ids.shape[1]}'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        b = ids.shape[1]
        k_slot = jnp.arange(self.pool.max_length, dtype=jnp.int32)
        q_pos = start + jnp.arange(b, dtype=jnp.int32)
        mask = (k_slot[None, :] <= q_pos[:, None])[None, None]
        with _adapter_scope(adapters, adapter_rows):
            _, row = fwd(ids, row, start, start, mask)
        return row

    def _draft_prefill_fn(self, params, frozen, buffers, ids):
        """`_prefill_fn` for the DRAFT model/pool: the draft needs its
        own prompt KV before it can propose. One compile per bucket."""
        self._trace_counts[f'draft_prefill_{ids.shape[1]}'] += 1
        fwd = cached_forward(self.draft_model, params, frozen, buffers)
        return _whole_prefill(fwd, ids, self.draft_pool.row_spec)

    def _spec_decode_fn(self, params, frozen, buffers, pool,
                        d_params, d_frozen, d_buffers, d_pool, state,
                        adapters=None):
        """One compiled SPECULATION round over all slots (replaces the
        plain decode block when a draft model is configured): the draft
        proposes k tokens autoregressively for every slot, the target
        verifies [pending, d_1..d_k] — k+1 positions — in ONE forward,
        and each greedy slot accepts its longest matching draft prefix
        plus the target's own next token (`_spec_decode_jit` semantics:
        output EXACTLY plain greedy, in fewer target passes). Sampling
        slots ignore the drafts and sample one token from the pending
        position's logits, exactly like the plain block. Rejected draft
        KV (target and draft pools) is stale-above-live and overwritten
        next round before anything attends it.

        Returns (tokens [N, k+1], accepted-counts [N], new pools)."""
        k = self.spec_k
        self._trace_counts[f'spec_decode_k{k}'] += 1
        (tok, pos, steps, active, temp, topk, topp, greedy, keys, eos,
         adapter_rows, _) = self._slot_state.unpack(state)
        fwd_t = cached_forward(self.model, params, frozen, buffers)
        fwd_d = cached_forward(self.draft_model, d_params, d_frozen,
                               d_buffers)
        max_len = self.pool.max_length
        k_slot = jnp.arange(max_len, dtype=jnp.int32)
        n = tok.shape[0]

        def draft_body(j, carry):
            cur, d_pool, drafts = carry
            p = pos + j
            mask = (k_slot[None, :] <= p[:, None])[:, None, None, :]
            lg, d_pool = fwd_d(cur[:, None], d_pool, p, p, mask)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return nxt, d_pool, drafts.at[:, j].set(nxt)

        _, d_pool, drafts = jax.lax.fori_loop(
            0, k, draft_body,
            (tok, d_pool, jnp.zeros((n, k), jnp.int32)))

        # target scores [pending, d_1..d_k] at positions pos..pos+k —
        # the adapter scope covers ONLY the target verify: the draft
        # model is untagged (drafts stay base-model proposals; a miss
        # costs acceptance rate, never correctness — the verify's
        # adapter logits decide what is emitted)
        block = jnp.concatenate([tok[:, None], drafts], axis=1)
        q_pos = pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None]
        mask = (k_slot[None, None, :] <= q_pos[:, :, None])[:, None]
        with _adapter_scope(adapters, adapter_rows):
            logits, pool = fwd_t(block, pool, pos, pos, mask)

        choice = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [N,k+1]
        # longest accepted draft prefix; acceptance stops at EOS
        # (everything after an emitted EOS is discarded anyway) and is
        # zero for sampling rows — they take the plain-sampling path
        match = ((drafts == choice[:, :k])
                 & (drafts != eos[:, None]) & greedy[:, None])
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        sampled = sample_rows(logits[:, 0], temp, topk, topp, greedy,
                              keys, steps)
        v_new = jnp.where(
            greedy,
            jnp.take_along_axis(choice, a[:, None], axis=1)[:, 0],
            sampled)
        j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        draft_ext = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
        toks = jnp.where(j < a[:, None], draft_ext,
                         jnp.where(j == a[:, None], v_new[:, None], 0))
        toks = jnp.where(active[:, None], toks, 0).astype(jnp.int32)
        counts = jnp.where(active, a + 1, 0).astype(jnp.int32)
        return toks, counts, pool, d_pool

    # ------------------------------------------------------------------
    # compiled programs: PAGED layout
    # ------------------------------------------------------------------
    def _paged_decode_fn(self, params, frozen, buffers, pages, scales,
                         table, state, prev, adapters=None, rows=None):
        """The decode block over the PAGE-TABLE pool: gather every
        slot's pages into the contiguous [N, max_length, H, D] view the
        row-pool scan already consumes (dequantizing int8 pages in the
        same expression), run the IDENTICAL per-token scan, then scatter
        only the pages overlapping [pos, pos+block) back — untouched
        pages are never rewritten, which makes the unquantized path a
        bit-exact writeback and keeps settled int8 pages from
        requantization drift. Inactive slots (parked mid-prefill, free)
        have their table row redirected to the null page so their junk
        token-0 writes can land nowhere real. `pages`/`scales` are
        donated (argnums 3, 4) so the pool aliases in place. `rows` is
        the scan's: how much of the gathered view attention reads;
        `prev` the block before's tokens (`_decode_block_fn`)."""
        self._trace_counts['paged_decode_step' if rows is None
                           else 'paged_decode_step_half'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        slots = self._slot_state.unpack(state)
        sc = scales if self.pool.quant else None
        table = jnp.where(slots.active[:, None], table, 0)
        contig = gather_pages(pages, table, sc,
                              out_dtype=self.pool.compute_dtype)
        toks, contig, *touched = self._scan_slots(
            fwd, contig, slots, prev, adapters, rows)
        pages, sc = scatter_pages(pages, table, contig, slots.pos,
                                  self.decode_block,
                                  self.pool.page_size, sc)
        return (toks, pages, sc if sc is not None else (), *touched)

    def _paged_decode_half_fn(self, params, frozen, buffers, pages,
                              scales, table, state, prev, adapters=None):
        """`_paged_decode_fn` with the scan's attention over the first
        `max_length // 2` rows of the gathered view: the paged pool's
        second decode program (`_decode_block_half_fn`)."""
        return self._paged_decode_fn(params, frozen, buffers, pages, scales,
                                     table, state, prev, adapters,
                                     rows=self._half_rows)

    def _paged_prefill_fn(self, params, frozen, buffers, pages, scales,
                          table, ids, adapters=None, adapter_rows=None):
        """Whole-prompt prefill into the PAGE pool: same batch-1 forward
        over a zero slab as `_prefill_fn`, then one scatter of
        [0, bucket) through the slot's table row ([1, P]). Pad rows past
        the reservation fall on null-table entries and vanish. UNDONATED
        on purpose: a prefill failure must stay request-level."""
        b = ids.shape[1]
        self._trace_counts[f'paged_prefill_{b}'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        with _adapter_scope(adapters, adapter_rows):
            slab = _whole_prefill(fwd, ids, self.pool.row_spec)
        sc = scales if self.pool.quant else None
        pages, sc = scatter_pages(pages, table, slab,
                                  jnp.zeros(1, jnp.int32), b,
                                  self.pool.page_size, sc)
        return pages, sc if sc is not None else ()

    def _paged_chunk_prefill_fn(self, params, frozen, buffers, pages,
                                scales, table, ids, start, floor,
                                adapters=None, adapter_rows=None):
        """One chunk of one prompt through the PAGE table: gather the
        slot's contiguous view (attached prefix pages included — the
        chunk attends the shared prefix through its own table, no src
        row needed), forward [start, start+chunk) with the slot-causal
        mask, scatter back. `floor` is the prefix-attach boundary
        (page-aligned): a tail-shifted window re-forwards rows below the
        cursor with bit-identical values, and the floor redirect makes
        sure those duplicate writes can never touch a SHARED page (int8
        requantization there would drift siblings)."""
        b = ids.shape[1]
        self._trace_counts[f'paged_chunk_prefill_{b}'] += 1
        fwd = cached_forward(self.model, params, frozen, buffers)
        sc = scales if self.pool.quant else None
        row = gather_pages(pages, table, sc,
                           out_dtype=self.pool.compute_dtype)
        k_slot = jnp.arange(self.pool.max_length, dtype=jnp.int32)
        q_pos = start + jnp.arange(b, dtype=jnp.int32)
        mask = (k_slot[None, :] <= q_pos[:, None])[None, None]
        with _adapter_scope(adapters, adapter_rows):
            _, row = fwd(ids, row, start, start, mask)
        pages, sc = scatter_pages(pages, table, row,
                                  jnp.reshape(start, (1,)), b,
                                  self.pool.page_size, sc,
                                  floor=jnp.reshape(floor, (1,)))
        return pages, sc if sc is not None else ()

    def _paged_spec_fn(self, params, frozen, buffers, pages, scales,
                       table, d_params, d_frozen, d_buffers, d_pool, state,
                       adapters=None):
        """The speculation round over the PAGED target pool: identical
        draft-propose / k+1-verify / longest-prefix-accept math as
        `_spec_decode_fn`, with the target KV gathered through the page
        table and the verify's k+1-row span scattered back (reservation
        headroom guarantees the span never clamps past max_length). The
        DRAFT pool stays a row SlotPool — it is small and never shared.
        Donates pages, scales, and the draft pool (argnums 3, 4, 9)."""
        k = self.spec_k
        self._trace_counts[f'paged_spec_decode_k{k}'] += 1
        (tok, pos, steps, active, temp, topk, topp, greedy, keys, eos,
         adapter_rows, _) = self._slot_state.unpack(state)
        fwd_t = cached_forward(self.model, params, frozen, buffers)
        fwd_d = cached_forward(self.draft_model, d_params, d_frozen,
                               d_buffers)
        sc = scales if self.pool.quant else None
        table = jnp.where(active[:, None], table, 0)
        pool = gather_pages(pages, table, sc,
                            out_dtype=self.pool.compute_dtype)
        max_len = self.pool.max_length
        k_slot = jnp.arange(max_len, dtype=jnp.int32)
        n = tok.shape[0]

        def draft_body(j, carry):
            cur, d_pool, drafts = carry
            p = pos + j
            mask = (k_slot[None, :] <= p[:, None])[:, None, None, :]
            lg, d_pool = fwd_d(cur[:, None], d_pool, p, p, mask)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return nxt, d_pool, drafts.at[:, j].set(nxt)

        _, d_pool, drafts = jax.lax.fori_loop(
            0, k, draft_body,
            (tok, d_pool, jnp.zeros((n, k), jnp.int32)))

        block = jnp.concatenate([tok[:, None], drafts], axis=1)
        q_pos = pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None]
        mask = (k_slot[None, None, :] <= q_pos[:, :, None])[:, None]
        with _adapter_scope(adapters, adapter_rows):
            logits, pool = fwd_t(block, pool, pos, pos, mask)

        choice = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        match = ((drafts == choice[:, :k])
                 & (drafts != eos[:, None]) & greedy[:, None])
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        sampled = sample_rows(logits[:, 0], temp, topk, topp, greedy,
                              keys, steps)
        v_new = jnp.where(
            greedy,
            jnp.take_along_axis(choice, a[:, None], axis=1)[:, 0],
            sampled)
        j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        draft_ext = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
        toks = jnp.where(j < a[:, None], draft_ext,
                         jnp.where(j == a[:, None], v_new[:, None], 0))
        toks = jnp.where(active[:, None], toks, 0).astype(jnp.int32)
        counts = jnp.where(active, a + 1, 0).astype(jnp.int32)
        pages, sc = scatter_pages(pages, table, pool, pos, k + 1,
                                  self.pool.page_size, sc)
        return toks, counts, pages, sc if sc is not None else (), d_pool

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    @staticmethod
    def _normalize_prompt(prompt) -> List[int]:
        if isinstance(prompt, Tensor):
            prompt = prompt.numpy()
        arr = np.asarray(prompt)
        if arr.ndim == 2 and arr.shape[0] == 1:
            arr = arr[0]
        if arr.ndim != 1 or arr.size < 1:
            raise ValueError(
                f'prompt must be a non-empty 1-D token sequence, got '
                f'shape {arr.shape}')
        return [int(t) for t in arr]

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               priority: Optional[int] = None,
               adapter_id: Optional[str] = None, **kwargs
               ) -> RequestHandle:
        """Queue one request; returns its live handle. Validation errors
        raise HERE (caller bug); runtime failures mark the handle
        FAILED instead. `priority` sets the scheduler admission class
        (PRIORITY_HIGH/NORMAL/LOW; default NORMAL). `adapter_id` decodes
        the request under that LoRA adapter from the engine's bank
        (None = base model); an unknown/unservable adapter fast-fails
        HERE with `adapters.AdapterUnavailable` — the typed miss the
        router maps onto `AdmissionRejected(reason=
        'adapter_unavailable')`."""
        if params is None:
            params = SamplingParams(**kwargs)
        elif kwargs:
            raise TypeError('pass params= or keyword sampling args, '
                            'not both')
        if adapter_id is not None:
            from .adapters.bank import AdapterUnavailable
            if self.adapter_bank is None:
                raise ValueError(
                    f'adapter_id={adapter_id!r} needs an engine built '
                    f'with adapter_bank=')
            if not self.adapter_bank.available(adapter_id):
                raise AdapterUnavailable(
                    adapter_id, 'not resident and no servable store '
                                'version')
        self._check_drain()
        if self._draining:
            self._counts['rejected'] += 1
            if _obs.enabled():
                self._m_requests.labels(status='rejected').inc()
            raise RuntimeError(
                'engine is draining (preemption signal received): not '
                'admitting new requests')
        toks = self._normalize_prompt(prompt)
        self.pool.bucket_for(len(toks))   # raises when no bucket fits
        # speculating engines verify a [pos, pos+k] block every round,
        # so every slot needs k tokens of cache headroom past its
        # budget (and the headroom is what keeps clamped block writes
        # above every retained prefix's kv_len)
        headroom = self.spec_k if self.draft_model is not None else 0
        if len(toks) + params.max_new_tokens + headroom \
                > self.pool.max_length:
            raise ValueError(
                f'prompt ({len(toks)}) + max_new_tokens '
                f'({params.max_new_tokens})'
                + (f' + speculation headroom ({headroom})' if headroom
                   else '')
                + f' exceeds the slot length ({self.pool.max_length})')
        h = RequestHandle(toks, params, engine=self)
        h.adapter_id = adapter_id
        if priority is not None:
            h.priority = int(priority)
        h._eos = int(self.eos_token_id if params.eos_token_id is None
                     else params.eos_token_id)
        self._counts['submitted'] += 1
        if _obs.enabled():
            self._m_requests.labels(status='submitted').inc()
        if _reqledger.enabled():
            rec = _reqledger.get_ledger().open_for(h)
            if rec is not None:
                rec.queue_enter(h._t_submit, 'priority_queued')
        self.scheduler.submit(h)
        return h

    # ------------------------------------------------------------------
    # graceful drain (preemption)
    # ------------------------------------------------------------------
    @property
    def draining(self) -> bool:
        return self._draining

    def enable_graceful_drain(self, handler=None, deadline_s: float = 30.0,
                              signals=None):
        """Wire a `resilience.PreemptionHandler` into the engine: on
        SIGTERM (the pod eviction grace window) the engine stops
        admitting NEW submissions, finishes every already-accepted
        request — queued and in-flight — under `deadline_s`, flips
        /healthz to a 503 `draining` state so routers stop sending
        traffic, and `run()`/`drain()` return so the caller can exit 0.
        Pass a ready handler to share one across subsystems; returns
        the handler in use."""
        if handler is None:
            import signal as _signal
            from ..resilience.preemption import PreemptionHandler
            handler = PreemptionHandler(
                signals=signals or (_signal.SIGTERM,)).install()
        self._preempt = handler
        self._drain_deadline_s = float(deadline_s)
        return handler

    def _check_drain(self):
        if (not self._draining and self._preempt is not None
                and self._preempt.requested):
            self._begin_drain()

    def begin_drain(self):
        """Stop admitting new submissions NOW, without driving decode:
        the non-blocking half of `drain()`. The router uses this to take
        one replica out of rotation (its scoped `draining` state excludes
        it from placement) while router steps keep finishing its
        accepted requests."""
        self._begin_drain()

    def _begin_drain(self):
        if self._draining:
            return
        self._settle()      # what is in flight is counted as it ended
        self._draining = True
        self._drain_t0 = time.monotonic()
        info = {'queued': self.scheduler.queue_depth,
                'in_flight': len(self._slot_req)}
        # 503 from here on: the replica is leaving the pool
        _obs.note_degraded('draining', info, scope=self.obs_scope)
        _obs.emit('serving_drain_begin', **info)

    def _detach_slot(self, slot: int, h: RequestHandle):
        """Common slot teardown for fail/evict/retire: drop the engine's
        references, release the request's prefix pin, and unpin its
        adapter bank slot. Does NOT free the pool slot — retirement may
        hand it to the prefix cache."""
        del self._slot_req[slot]
        self._active[slot] = False
        self._prefilling.pop(slot, None)
        if h._prefix_node is not None:
            self.prefix_cache.release(h._prefix_node)
            h._prefix_node = None
        self._unpin_adapter(slot, h)

    def _unpin_adapter(self, slot: int, h: RequestHandle):
        """Release the request's adapter bank pin (idempotent) and point
        the pool slot's adapter row back at the zero base adapter. The
        handle keeps `adapter_id`/`adapter_version` — failover resubmits
        it elsewhere, and the version stamp is a per-response fact."""
        if h._adapter_pin is not None:
            self.adapter_bank.unpin(h._adapter_pin)
            h._adapter_pin = None
        self._adapter_rows[slot] = 0

    def _prefix_ns(self, h: RequestHandle):
        """The prefix-cache namespace this request's KV belongs to:
        adapter requests key under (adapter_id, adapter_version) — an
        adapter's prefill KV contains its LoRA deltas, so tenants with
        different adapters (or versions of one) must NEVER share a
        cached prefix; base requests share the default namespace."""
        if h.adapter_id is None:
            return None
        return (h.adapter_id, h.adapter_version)

    def _fail_remaining(self, exc: BaseException):
        self._rounds.clear()        # nobody is left to take their tokens
        for h in self.scheduler.drain():
            h._fail(exc)
            self._counts['failed'] += 1
            if _obs.enabled():
                self._m_requests.labels(status='failed').inc()
        for slot, h in list(self._slot_req.items()):
            self._detach_slot(slot, h)
            self.pool.free(slot)
            h._fail(exc)
            self._counts['failed'] += 1
            if _obs.enabled():
                self._m_requests.labels(status='failed').inc()
        if _obs.enabled():
            self._m_active.set(len(self._slot_req))

    def evict_all(self) -> List[RequestHandle]:
        """Pull every accepted request — queued AND in-flight — out of
        the engine WITHOUT failing it, returning the handles in
        submission order (queued first is irrelevant to the router; it
        re-sorts). This is the failover hand-off: when the router
        declares this replica dead, the orphans are resubmitted
        elsewhere, so their handles must leave this engine untouched.
        Slots free, actives clear; the engine itself stays serviceable
        (a transient device blip doesn't scrap the pool). A round in
        flight is DROPPED, not fetched (the replica may be dying, and
        its requests start over elsewhere): whatever it still writes
        stays in rows of the slots it was dispatched for, and the next
        request's seat is ordered behind it by the pool."""
        self._rounds.clear()
        out = self.scheduler.drain()
        for slot, h in list(self._slot_req.items()):
            self._detach_slot(slot, h)
            self.pool.free(slot)
            out.append(h)
        if _obs.enabled():
            self._m_active.set(len(self._slot_req))
        return out

    def drain(self, deadline_s: Optional[float] = None) -> bool:
        """Stop admitting new submissions and drive decode until every
        accepted request (queued + in-flight) finishes, bounded by the
        deadline. Past the deadline the stragglers FAIL (handles carry
        the TimeoutError) rather than being silently dropped. Returns
        True when everything completed in time. /healthz stays
        `draining` afterwards — the process is expected to exit."""
        if deadline_s is None:
            deadline_s = self._drain_deadline_s
        self._begin_drain()
        timed_out = False
        # the drain span books this window as `preemption_drain` in the
        # goodput ledger — minus the nested decode/prefill spans, which
        # stay productive serving time
        with _obs.span('serving.drain'):
            while self.has_work:
                if deadline_s is not None and \
                        time.monotonic() - self._drain_t0 > deadline_s:
                    timed_out = True
                    self._fail_remaining(TimeoutError(
                        f'drain deadline {deadline_s}s exceeded'))
                    break
                self.step()
        _obs.emit('serving_drain_complete',
                  timed_out=timed_out,
                  seconds=round(time.monotonic() - self._drain_t0, 3))
        return not timed_out

    # ------------------------------------------------------------------
    # online weight updates (trainer→serving hot-swap, ISSUE 12)
    # ------------------------------------------------------------------
    def swap_weights(self, state, *, version: int, strict: bool = True):
        """Replace the engine's weights IN PLACE with a published
        host-canonical snapshot (``{name: array}`` as produced by
        ``Layer.state_dict()`` / ``hotswap.WeightStore.load``), without
        touching a single compiled program: every staged leaf must match
        the live leaf's shape and is cast to its dtype, so the decode /
        prefill avals — and therefore the ProgramStore keys — are
        bit-identical before and after (zero XLA recompiles on swap,
        tier-1-guarded).

        Requires a DRAINED engine (no queued or in-flight requests):
        that is what makes the per-request ``weight_version`` stamp a
        whole-response guarantee. The `ReplicaUpdater` drains through
        the router first; direct callers get a loud error instead of a
        torn batch.

        `strict=True` (default) demands every live param present in
        `state`; buffers may be absent (non-persistable buffers never
        travel through `state_dict`) and keep their current values.

        Returns the PREVIOUS weight state — an opaque token for
        `restore_weights`, which the updater holds for the rollback
        path (the old device arrays stay alive by reference, so a
        revert is a pointer swap, not a reload)."""
        self._settle()
        if self._slot_req or self.scheduler.queue_depth > 0:
            raise RuntimeError(
                f'swap_weights requires a drained engine, but '
                f'{len(self._slot_req)} slot(s) are decoding and '
                f'{self.scheduler.queue_depth} request(s) are queued '
                f'(drain through the router/updater first)')
        prev = (self._params, self._frozen, self._buffers,
                self.weight_version)
        self._params = self._stage_swap(self._params, state,
                                        'parameter', strict)
        self._frozen = self._stage_swap(self._frozen, state,
                                        'frozen parameter', strict)
        self._buffers = self._stage_swap(self._buffers, state,
                                         'buffer', False)
        self._set_weight_version(version)
        return prev

    def restore_weights(self, prev):
        """Roll back to a weight state captured by `swap_weights` (the
        failed-health-gate path). Same drained-engine requirement; the
        prefix cache's entries for the restored version re-validate for
        free (they were never flushed, only version-shadowed)."""
        self._settle()
        if self._slot_req or self.scheduler.queue_depth > 0:
            raise RuntimeError(
                'restore_weights requires a drained engine')
        self._params, self._frozen, self._buffers, version = prev
        self._set_weight_version(version)

    def _set_weight_version(self, version: int):
        self.weight_version = int(version)
        if self.prefix_cache is not None:
            # no flush: entries from other versions go stale and are
            # lazily reclaimed; this version's survivors serve again
            self.prefix_cache.set_version(self.weight_version)
        _obs.note_weight_version(self.weight_version,
                                 scope=self.obs_scope)
        if _obs.enabled():
            _obs.get_registry().gauge(
                'paddle_weight_version',
                'live weight version per serving scope',
                ('scope',)).labels(
                    scope=self.obs_scope or 'engine').set(
                        self.weight_version)

    @staticmethod
    def _stage_swap(old_dict, state, kind: str, strict: bool):
        """Stage one functional-state dict from a published snapshot:
        shape-checked against the live aval (a mismatch means the
        checkpoint is structurally different — fail the SWAP, loudly,
        before any program could retrace) and cast to the live dtype so
        the program key cannot move."""
        new = {}
        for name, old in old_dict.items():
            if name not in state:
                if strict:
                    raise KeyError(
                        f'published weights missing {kind} {name!r}: '
                        f'refusing a partial swap')
                new[name] = old
                continue
            arr = np.asarray(getattr(state[name], 'value', state[name]))
            if tuple(arr.shape) != tuple(old.shape):
                raise ValueError(
                    f'{kind} {name!r} shape {tuple(arr.shape)} does not '
                    f'match the live aval {tuple(old.shape)}: swapping '
                    f'it would change the program key and force a '
                    f'recompile')
            new[name] = jnp.asarray(arr, dtype=old.dtype)
        return new

    # ------------------------------------------------------------------
    # the iteration loop
    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self._slot_req or self._rounds) \
            or self.scheduler.queue_depth > 0

    def step(self) -> int:
        """ONE scheduler iteration: admit queued requests into free
        slots, advance every mid-prefill slot one chunk, then advance
        every ACTIVE slot one decode round (a plain block, or one
        speculation round when a draft model is configured). Returns
        the number of requests that progressed.

        A plain round is two halves, dispatch and settle (fetch its
        tokens, emit them, retire who ended), and a step runs them in
        the order that what the engine can observe allows:

        - SERIAL — admit, dispatch round N, settle N — whenever
          something could be seated or retired before round N+1: a slot
          is free or parked mid chunked prefill, a participant reaches
          `max_new_tokens` inside N, or a draft model speculates (its
          accepted counts are data). Nothing is in flight when the step
          returns, and a request that could be seated never waits behind
          a queued block.
        - AHEAD otherwise (`_may_run_ahead`): N stays in flight when the
          step returns, and the next step dispatches N+1 — its slot
          state advanced on the host when N was dispatched, its pending
          tokens the device's (`SlotState.carried`) — BEFORE it settles
          N. The device always has the next block queued behind the one
          it runs, and the fetch, the emission, the caller's bookkeeping
          and the dispatch all happen under a running block. A step that
          finds a round in flight and the rule no longer holding settles
          it (`serving.settle`) and returns: the serial order's second
          half.

        An end the host could not foresee (an EOS inside N) costs that
        slot the one block already queued behind N: its share of N+1 is
        dropped, not emitted (`blocks_discarded`), and its writes stay
        in the slot's own rows, above everything the next request there
        will read before it has overwritten it."""
        with _obs.span('serving.step'):
            self._check_drain()
            if not self._pool_layout_settled:
                self._settle_pool_layout()
            if self._rounds and (len(self._rounds) > 1
                                 or not self._may_run_ahead()):
                n = len(self._slot_req)
                self._settle_oldest()
                return n
            with _obs.span('serving.admit') as sp:
                sp.set(admitted=self._admit())
            self._advance_prefills()
            n = len(self._slot_req)
            if not np.any(self._active):
                return n        # chunk-prefill-only progress this round
            if self.draft_model is not None:
                t_round0 = time.perf_counter()
                toks, counts = self._spec_round()
                with _obs.span('serving.emit'):
                    self._emit_round(
                        [(slot, h) for slot, h in self._slot_req.items()
                         if self._active[slot]], toks, counts, t_round0)
            else:
                self._decode_round()
            return n

    def _may_run_ahead(self) -> bool:
        """Whether the next decode block may be dispatched BEFORE the
        newest one dispatched is settled — from what the engine holds,
        no token value: no draft model, every slot holds a DECODING
        request (none free, none parked mid chunked prefill: nothing can
        be seated), and no participant reaches its `max_new_tokens`
        inside the round in flight (`_steps` already counts it: nothing
        ends by length). An EOS is the one end this cannot see."""
        return (self.draft_model is None and bool(self._active.all())
                and bool((self._steps < self._budget).all()))

    def _settle_oldest(self):
        """Fetch the oldest round in flight and hand its tokens out,
        with no dispatch before it: the serial order's second half.
        Under `serving.settle`, not `serving.decode_round` — there is
        one of those a block DISPATCHED, with the block's counts — which
        the goodput ledger books as decode like it: the wait is a whole
        block's."""
        with _obs.span('serving.settle') as settle_span:
            fetched = self._fetch(settle_span)
        with _obs.span('serving.emit'):
            self._emit_round(*fetched)

    def _settle(self):
        """Settle every round in flight: what assumes a quiet engine
        (a weight swap, the start of a drain, `stats`) calls this
        first."""
        while self._rounds:
            self._settle_oldest()

    def _emit_round(self, live, toks, counts, t_round0: float):
        """Hand a round's tokens to the requests that decoded in it and
        are still seated where they were (`live`: (slot, handle);
        `counts` None: a plain block, `decode_block` tokens each):
        ledger booking, emission and retirement. A plain block's slots
        were advanced when it was dispatched (`_advance_slots`); a
        speculation round's accepted counts are data, so its slots
        advance here."""
        now = time.perf_counter()
        # ledger BEFORE the emission loop, so the round that produced a
        # request's first token still lands in its TTFT sub-book
        # (mark_first fires inside _emit below). Waterfall book: every
        # active participant waited the full round wall; fair-share
        # book: the wall splits evenly, closing to the engine decode
        # wall. The wall runs from the LATER of the round's dispatch and
        # the emission before it: a round dispatched ahead overlaps the
        # one before it, and those seconds are booked once.
        _reqledger.get_ledger().note_round(
            now - max(t_round0, self._t_emitted),
            [h._ledger_rec for _, h in live],
            'spec_verify' if self.draft_model is not None else 'decode',
            now=now, absorb=True)
        self._t_emitted = now
        self._counts['decode_rounds'] += 1
        if _obs.enabled():
            self._m_rounds.inc()
            self._m_occupancy.observe(self.pool.occupancy)
        for slot, h in live:
            c = self.decode_block if counts is None else \
                int(counts[slot])  # paddle-lint: disable=host-sync -- spec accept counts gate the emission loop; one d2h per round, already materialized by toks
            if self.draft_model is not None and self._greedy[slot]:
                self._counts['spec_proposed'] += self.spec_k
                self._counts['spec_accepted'] += c - 1
                if _obs.enabled():
                    self._m_spec_proposed.inc(self.spec_k)
                    self._m_spec_accepted.inc(c - 1)
                    self._m_spec_shared_prop.labels(
                        source='engine').inc(self.spec_k)
                    self._m_spec_shared_acc.labels(
                        source='engine').inc(c - 1)
            done = False
            emitted = 0
            first = not h.tokens
            for j in range(c):
                t = int(toks[slot, j])  # paddle-lint: disable=host-sync -- THE emission d2h: tokens must reach the client; one blocking read per round for all slots
                h._emit(t, now)
                emitted += 1
                if (len(h.tokens) >= h.params.max_new_tokens
                        or t == h._eos):
                    done = True
                    break
            self._counts['tokens'] += emitted
            if _obs.enabled():
                self._m_tokens.inc(emitted)
                if first:
                    self._m_ttft.observe(h.ttft)
            if done:
                self._retire(slot, h, now)
            elif counts is not None:
                self._tok[slot] = toks[slot, c - 1]
                self._pos[slot] += c
                self._steps[slot] += 1
                # stranded-capacity accounting: rows actually written
                self.pool.note_written(slot, self._pos[slot] + 1)

    def _advance_slots(self):
        """A plain block has just been dispatched: move every decoding
        slot to where the block leaves it — position and sample index
        `decode_block` on, the pending token the device's — so that the
        buffer is the NEXT block's state before this one's tokens have
        been fetched. Arithmetic on what the host holds; who ends inside
        the block is found when its tokens are emitted. -> the block's
        participants."""
        c = self.decode_block
        self._pos[self._active] += c
        self._steps[self._active] += c
        self._carried[self._active] = True
        parts = [(slot, h) for slot, h in self._slot_req.items()
                 if self._active[slot]]
        for slot, _ in parts:
            # stranded-capacity accounting: rows actually written
            self.pool.note_written(slot, self._pos[slot] + 1)
        return parts

    def _settle_pool_layout(self):
        """Before the first program touches the pool: compile (or load)
        the whole-length decode block, which asks the compiler for the
        layout of every leaf that wants one of its own, and have the
        pool hold those leaves as that program takes and returns them
        (`SlotPool.adopt_formats`). The first step reaches this, so a
        server that has stepped once never relays a leaf at a block's
        edge nor compiles for it again. A decode block that could not
        be compiled ahead of time takes the default layout, and the
        pool keeps it."""
        _, program = self._decode_jit.resolve(*self._decode_args())
        if hasattr(program, 'input_formats'):
            self.pool.adopt_formats(program.input_formats[0][3],
                                    program.output_formats[1])
        self._pool_layout_settled = True
        if _obs.enabled():
            self._m_own_layout.set(self.pool.own_layout_leaves)

    def _decode_args(self) -> tuple:
        """The row pool's decode programs' arguments, as they stand."""
        return (self._params, self._frozen, self._buffers, self.pool.cache,
                *self._state_args(self._prev_toks))

    def _state_args(self, *prev) -> tuple:
        """What every decode and speculation program takes last: the
        slot state — the ONE host buffer, as it stands: one argument,
        one transfer a call, nothing packed per round — then, for a
        decode block, `prev` (the tokens of the block before, the
        device's) and, on an engine with a bank, the bank's arrays (the
        per-slot bank rows ride the buffer). A bank-less engine's
        signatures and program-store keys carry no trace of adapters.

        The call gets a COPY of the buffer (a few hundred words): the
        engine writes the buffer for the block after as soon as this
        call returns, while the program may not have started, and a
        backend may read a numpy argument where it lies instead of
        copying it during the call (jax's CPU client does, whenever the
        array happens to start on a 64-byte boundary)."""
        state = self._slot_state.buffer.copy()
        if self.adapter_bank is None:
            return (state, *prev)
        return (state, *prev, self.adapter_bank.device_arrays())

    def _recover_pool(self):
        """A DONATED program (decode, spec, seat, copy) failed mid-call:
        the pool it was given may already be invalidated, so every
        retained buffer is suspect. Rebuild a zero pool and force-clear
        the prefix cache (its KV floors are gone) BEFORE re-raising —
        the error still classifies and fails over normally, but the
        engine itself stays serviceable for the next admission. Every
        round in flight goes with the pool (a block dispatched ahead ran
        on what the failed one was to return), and the pool is rebuilt
        once."""
        self._rounds.clear()
        self._prev_toks = jnp.zeros_like(self._prev_toks)
        if self._paged:
            self.pool.reset_pages()
        else:
            self.pool.reset_rows()
        if self.draft_pool is not None:
            self.draft_pool.reset_rows()
        if self.prefix_cache is not None:
            self.prefix_cache.clear(force=True)
        _obs.emit('serving_pool_recovered',
                  slots=self.pool.num_slots)

    def _prefill_row(self, pool: SlotPool, slot: int, program, *args):
        """Dispatch a row-layout prefill `program` (it returns ONE row
        and never holds the pool) and seat that row in `pool` in place.
        First wait until what is queued on the pool has run: the runtime
        reserves a program's outputs when it is ENQUEUED, so a burst of
        admissions dispatched back to back holds a row apiece until
        their seats have run — six rows of 0.75 GiB beside a 4.5 GiB
        pool (serve-docs, PERF.md PR 25). One at a time, the peak is the
        pool plus ONE row. After a decode round the pool is ready
        already, so only the second admission of a step waits, and the
        device has the first one's prefill to run meanwhile."""
        jax.block_until_ready(pool.rows)
        row = program(*args)
        self._pool_op(pool.set_row, slot, row)

    def _pool_op(self, op, *args):
        """Run one of the row pool's donated single-slot programs
        (`set_row`, `copy_slot`). If it dies the pool may be gone with
        it: recover as after a failed donated decode round and raise
        `PoolLostError`, which no request-level handler absorbs."""
        try:
            op(*args)
        except Exception as exc:
            self._recover_pool()
            raise PoolLostError(
                f'{op.__name__} died with the pool donated to it: '
                f'{type(exc).__name__}: {exc}') from exc

    def _adapter_args(self, slot: int) -> tuple:
        """Trailing (bank arrays, the slot's bank row) appended to a
        batch-1 prefill/chunk program's call — () on a bank-less
        engine, whose signatures and program-store keys stay exactly
        the pre-adapter ones."""
        if self.adapter_bank is None:
            return ()
        return (self.adapter_bank.device_arrays(),
                self._adapter_rows[slot:slot + 1])

    def _needed_rows(self):
        """Cache rows this round's attention NEEDS, over active slots
        and layers: the rows a slot has written, and on a window layer
        at most the window -> (all of them, those on ring entries).
        What the program READS is every slot's first `rows` rows on
        every layer that keeps the slot's length (`_round_rows`), and
        the whole of every ring."""
        written = self._pos[self._active].astype(np.int64) + 1
        need = np.minimum(written[:, None], self._layer_rows[None, :])
        return int(need.sum()), int(need[:, self._ring].sum())

    def _bounded_tiles(self, rows: int):
        """Per attending layer, the row tile of the kernel that runs
        its decode attention in the program that attends over `rows`
        rows, 0 where XLA reads every row (another backend, a ring, a
        call the kernels do not take): the model's own dispatch, asked
        with the call the decode scan makes — one query a slot, the
        leaves as held, a boolean mask of `rows` columns. A latent
        entry is `ops.pallas.latent_decode_kernel`'s; K and V by head
        the model's to say (`decode_tiles`: its layers know their
        queries and their sinks)."""
        spec, slots = jax.ShapeDtypeStruct, self.pool.num_slots
        tiles = {}
        for i in self.pool.latent_layers:
            kernel = _pallas.latent_decode_kernel(
                spec((slots, 1), np.float32), self.pool.row_spec[i][0],
                spec((slots, 1, 1, rows), np.bool_))
            tiles[i] = kernel and kernel.keywords['tile']
        by_head = getattr(self.model, 'decode_tiles', None)
        if by_head is not None:
            tiles.update(enumerate(by_head(self.pool.row_spec, slots, rows)))
        return np.array([tiles.get(i) or 0 for i in self._attending],
                        np.int64)

    def _read_rows(self, rows: int) -> int:
        """Cache rows this round's attention READS, over slots and
        layers: every slot's first `rows` rows on every layer that keeps
        the slot's length and the whole of every ring — but on a layer
        whose attention is bounded per slot (`_bounded_tiles`) what the
        kernel walks (`pallas_kernels.decode_walk`, the kernel's own
        arithmetic): the row tiles from the first row a decoding slot
        sees (on a window layer the window's first) to its last, one
        tile of a slot that is not decoding."""
        slots, tiles = self.pool.num_slots, self._tiles[rows]
        bounded = tiles > 0
        read = slots * (self._ring_rows + rows * int(
            np.count_nonzero(~bounded & ~self._ring)))
        if bounded.any():
            from ..ops.pallas_kernels import decode_walk
            bound = np.where(self._active, np.minimum(
                self._pos.astype(np.int64) + 1, rows), 0)[:, None]
            first = np.maximum(bound - self._layer_rows[None, bounded], 0)
            _, walked = decode_walk(first, bound, tiles[None, bounded])
            read += int((walked * tiles[None, bounded]).sum())
        return read

    def _note_routing(self, round_span, routing):
        """Book a round's routing counts (`[decode_block, 2, expert
        layers]`: the distinct experts the active slots routed to, and
        whether the layer ran the kernel) on its span, on
        `paddle_serving_moe_experts_touched_total` and on
        `paddle_serving_moe_expert_kernel_substeps_total`."""
        touched, kernel = routing[:, 0], routing[:, 1]
        n, ran = int(touched.sum()), int(kernel.sum())
        round_span.set(experts_touched=n,
                       expert_layer_substeps=int(touched.size),
                       expert_kernel_substeps=ran,
                       experts=self._num_experts)
        if _obs.enabled():
            self._m_experts_touched.inc(n)
            self._m_expert_kernel.inc(ran)
        if routing.shape[1] > 2:
            # a layer holds a share of its router's experts: the picks
            # the active slots made, and those that landed here
            held = int(routing[:, 3].sum())
            round_span.set(picks=int(routing[:, 2].sum()), picks_held=held)
            if _obs.enabled():
                self._m_picks_held.inc(held)

    def _note_state(self, round_span):
        """Book what a round does to slot state that is not K and V, on
        its span and on `paddle_serving_slot_state_bytes_total`: every
        sub-step reads and writes every leaf of every state layer's entry
        for each active slot (an inactive slot's is garbage nobody
        needs)."""
        n = (int(np.count_nonzero(self._active)) * self.pool.state_bytes
             * 2 * self.decode_block)
        round_span.set(attn_layers=len(self._layer_rows),
                       state_layers=len(self.pool.state_layers),
                       state_bytes=n)
        if self._state_kernel_layers is not None:
            round_span.set(state_kernel_layers=self._state_kernel_layers)
        if _obs.enabled():
            self._m_state_bytes.inc(n)

    def _round_rows(self) -> int:
        """How many rows of every slot this round's attention reads:
        the half program's while the longest ACTIVE position, a block
        and one row more stay inside it, else `max_length`. A slot that
        is not decoding (free, or parked at `max_length - 1` while it
        prefills in chunks) does not count: what it reads is discarded,
        and its stray write lands in the whole leaf either way."""
        need = int(self._pos[self._active].max()) + self.decode_block + 1
        return self._half_rows if need <= self._half_rows \
            else self.pool.max_length

    def _decode_program(self, rows: int, args):
        """The decode program that attends over `rows` rows. The first
        dispatch resolves BOTH through the store (compiled or loaded,
        not run), so the round that first needs the other one finds it
        built: a server that has decoded once never compiles a decode
        program again, whatever its traffic does next."""
        if not self._decode_resolved:
            for program in (self._decode_jit, self._decode_half_jit):
                if program is not None:
                    program.resolve(*args)
            self._decode_resolved = True
        return self._decode_jit if rows == self.pool.max_length \
            else self._decode_half_jit

    def _decode_round(self):
        """The plain compiled decode block (no draft model): dispatch
        one — every active slot advances `decode_block` tokens — and
        settle one, in the order `step` describes. With a round in
        flight (`ahead` 1 on the span) the dispatch is the block AFTER
        it and the fetch is the one in flight's, which stays behind; with
        none, the block dispatched is fetched here too, unless the next
        step may dispatch ahead of it (`_may_run_ahead`): then it stays
        in flight and this span has no `serving.d2h` (a later step that
        cannot go ahead of it fetches it under `serving.settle`). There
        is ONE such span a block dispatched. The span carries,
        as scalars, `ahead`, the slots decoding (`active`), the slots
        there are, the rows that hold a real token by the pool's own
        book, the rows of a slot the dispatched program attends over
        (`rows`: `max_length` or half of it, `_round_rows`) and, over
        slots and layers, the rows it therefore reads and the rows it
        needed — all of the block DISPATCHED, from the positions as
        advanced — and of the block FETCHED the routing counts and
        `discarded` (slot-blocks dropped: their request ended in the
        round before, unforeseen); its children are
        `serving.decode_dispatch` (staging the host arrays and the page
        table, and the jitted call until it returns) and `serving.d2h`
        (the blocking fetch of a round's tokens)."""
        ahead = int(bool(self._rounds))
        with _obs.span('serving.decode_round', ahead=ahead,
                       active=int(np.count_nonzero(self._active)),
                       slots=self.pool.num_slots,
                       real_rows=self.pool.written_rows) as round_span:
            rows = self._round_rows()
            needed, needed_ring = self._needed_rows()
            round_span.set(needed_rows=needed, rows=rows,
                           read_rows=self._read_rows(rows))
            if self.pool.ring_layers:
                round_span.set(needed_rows_window=needed_ring)
            if self.pool.state_layers:
                self._note_state(round_span)
            if self.pool.latent_layers:
                round_span.set(
                    latent_layers=len(self.pool.latent_layers),
                    latent_row_bytes=self.pool.latent_row_bytes)
            if self._residual_streams:
                round_span.set(residual_streams=self._residual_streams)
            t_round0 = time.perf_counter()
            try:
                with _obs.span('serving.decode_dispatch'):
                    if self._paged:
                        pages, scales = self.pool.device_state()
                        # a copy, as of the slot state: a retirement
                        # rewrites the table while this block may run
                        table = call_with_retry(
                            _to_device, self.pool.page_table.copy(),
                            policy=self._retry, site='serving.h2d')
                        args = (self._params, self._frozen, self._buffers,
                                pages, scales, table,
                                *self._state_args(self._prev_toks))
                        toks_dev, new_pages, new_scales, *touched = \
                            self._decode_program(rows, args)(*args)
                        self.pool.set_device_state(new_pages, new_scales)
                    else:
                        args = self._decode_args()
                        toks_dev, new_pool, *touched = \
                            self._decode_program(rows, args)(*args)
                        self.pool.cache = new_pool
            except Exception:
                self._recover_pool()
                raise
            self._prev_toks = toks_dev
            self._rounds.append(_Round(
                toks_dev, touched[0] if touched else None,
                self._advance_slots(), t_round0))
            self._counts['decode_steps'] += self.decode_block
            self._counts['rounds_ahead'] += ahead
            if _obs.enabled():
                self._m_decode_steps.inc(self.decode_block)
                self._m_rounds_ahead.inc(ahead)
                self._m_rows_read.inc(
                    self.pool.num_slots * rows * self.decode_block)
            if not ahead and self._may_run_ahead():
                return      # in flight: the next step dispatches first
            fetched = self._fetch(round_span)
        with _obs.span('serving.emit'):
            self._emit_round(*fetched)

    def _fetch(self, span):
        """Block until the tokens of the OLDEST round in flight are on
        the host (`serving.d2h`) and take it off the list; a failed
        fetch leaves it there. Its routing counts and `discarded` go on
        `span`, the `serving.decode_round` or `serving.settle` open. -> what `_emit_round` takes: of its participants
        AS DISPATCHED those still seated where they were (a request
        retired since — an EOS in the round before — or evicted takes
        nothing more, and whoever holds its slot now was seated after
        the dispatch), its tokens, None, its dispatch instant."""
        rnd = self._rounds[0]
        with _obs.span('serving.d2h'):
            toks = call_with_retry(_from_device, rnd.toks,
                                   policy=self._retry,
                                   site='serving.d2h')
            if rnd.routing is not None:
                # the routing counts left the device with the
                # tokens: ready when they are, no further wait
                self._note_routing(span, call_with_retry(
                    _from_device, rnd.routing, policy=self._retry,
                    site='serving.d2h'))
        self._rounds.popleft()
        live = [(slot, h) for slot, h in rnd.parts
                if self._slot_req.get(slot) is h]
        dropped = len(rnd.parts) - len(live)
        span.set(discarded=dropped)
        self._counts['blocks_discarded'] += dropped
        _obs.note_progress('decode')   # /healthz decode liveness beat
        return live, toks, None, rnd.t0

    def _spec_round(self):
        """One compiled speculation round: k draft proposals + one
        k+1-position target verify; greedy slots advance by their
        accepted count, sampling slots by one."""
        d_params, d_frozen, d_buffers = self._draft_state
        with _obs.span('serving.spec_round', k=self.spec_k,
                       active=int(np.count_nonzero(self._active)),
                       slots=self.pool.num_slots,
                       real_rows=self.pool.written_rows):
            try:
                with _obs.span('serving.decode_dispatch'):
                    if self._paged:
                        pages, scales = self.pool.device_state()
                        table = call_with_retry(
                            _to_device, self.pool.page_table,
                            policy=self._retry, site='serving.h2d')
                        (toks_dev, counts_dev, new_pages, new_scales,
                         new_d_pool) = self._spec_jit(
                            self._params, self._frozen, self._buffers,
                            pages, scales, table, d_params, d_frozen,
                            d_buffers, self.draft_pool.cache,
                            *self._state_args())
                        self.pool.set_device_state(new_pages, new_scales)
                    else:
                        toks_dev, counts_dev, new_pool, new_d_pool = \
                            self._spec_jit(
                                self._params, self._frozen, self._buffers,
                                self.pool.cache, d_params, d_frozen,
                                d_buffers, self.draft_pool.cache,
                                *self._state_args())
                        self.pool.cache = new_pool
                    self.draft_pool.cache = new_d_pool
            except Exception:
                self._recover_pool()
                raise
            with _obs.span('serving.d2h'):
                toks = call_with_retry(_from_device, toks_dev,
                                       policy=self._retry,
                                       site='serving.d2h')
                counts = call_with_retry(_from_device, counts_dev,
                                         policy=self._retry,
                                         site='serving.d2h')
        _obs.note_progress('decode')
        self._counts['decode_steps'] += 1   # one target verify pass
        self._counts['spec_rounds'] += 1
        if _obs.enabled():
            self._m_decode_steps.inc(1)
            self._m_spec_rounds.inc()
            self._m_spec_shared.labels(source='engine').inc()
        return toks, counts

    def run(self) -> int:
        """Drive until queue and slots drain; returns decode rounds."""
        rounds = 0
        while self.has_work:
            self.step()
            rounds += 1
        return rounds

    def stream(self, handle: RequestHandle):
        """Per-token iterator for one request (see RequestHandle.stream)."""
        return handle.stream()

    def generate_many(self, prompts, params=None,
                      adapter_ids=None) -> List[RequestHandle]:
        """Submit a batch of prompts and drain the engine — the
        continuous-batching replacement for a sequential `generate()`
        loop on mixed-length workloads. `params` is one SamplingParams
        for all, or a per-prompt sequence; `adapter_ids` is one adapter
        id (or None) for all, or a per-prompt sequence — a mixed batch
        decodes every adapter in the same compiled step."""
        if params is None or isinstance(params, SamplingParams):
            params = [params or SamplingParams()] * len(prompts)
        if len(params) != len(prompts):
            raise ValueError('one SamplingParams per prompt')
        if adapter_ids is None or isinstance(adapter_ids, str):
            adapter_ids = [adapter_ids] * len(prompts)
        if len(adapter_ids) != len(prompts):
            raise ValueError('one adapter id (or None) per prompt')
        handles = [self.submit(p, sp, adapter_id=aid)
                   for p, sp, aid in zip(prompts, params, adapter_ids)]
        self.run()
        return handles

    # ------------------------------------------------------------------
    # admission / retirement
    # ------------------------------------------------------------------
    def _admission_cost(self, prompt_len: int) -> int:
        """Prefill cost charged against the scheduler's per-iteration
        budget: with chunking, an admission costs ONE chunk bucket this
        round (the rest spreads over later rounds); without, the whole
        prompt's bucket."""
        if self.prefill_chunk_tokens:
            prompt_len = min(prompt_len, self.prefill_chunk_tokens)
        return self.pool.bucket_for(prompt_len)

    def _effective_free(self) -> int:
        """Slots admissible right now: free-list + (row mode) zero-ref
        cached prefixes the pool can reclaim on demand. Paged retention
        pins PAGES, not slots, so there the free list is the truth —
        page pressure surfaces at reservation and requeues."""
        free = self.pool.free_count
        if self.prefix_cache is not None and not self._paged:
            free += self.prefix_cache.reclaimable_count
        return free

    def _alloc_slot(self) -> int:
        if (not self._paged and self.pool.free_count == 0
                and self.prefix_cache is not None):
            # pool pressure: retained prefixes yield to live requests
            self.prefix_cache.evict_lru()
        return self.pool.alloc()

    def _requeue_blocked(self, handles, reason: str):
        """Requeue (queue FRONT, original order, first-submit timestamp
        preserved) and sample the blocking reason into each request's
        ledger record: elapsed queue time settles under the reason that
        was just observed, and a fresh interval opens."""
        now = time.perf_counter()
        for h in handles:
            rec = h._ledger_rec
            if rec is not None:
                if rec._q_mark is None and now > rec._last_touch:
                    # this handle reached _begin_request (queue_exit
                    # ran) before the seat aborted: the aborted seating
                    # work — an adapter store load that found the bank
                    # full, the page-reservation walk — is admission
                    # time, not a residual
                    rec.add('admission', now - rec._last_touch, now=now)
                rec.queue_block(now, reason)
        for back in reversed(handles):
            self.scheduler.requeue(back)

    def _admit(self):
        """Seat what the scheduler admits; -> how many took a slot."""
        admitted = self.scheduler.admissible(self._effective_free(),
                                             self._admission_cost)
        seated = 0
        for idx, h in enumerate(admitted):
            try:
                slot = self._alloc_slot()
            except RuntimeError:
                # the reclaimable slot this admission was promised got
                # pinned mid-pass (a sibling admission hit its prefix):
                # not a failure — THIS handle and everything behind it
                # in the popped batch go back to the queue front in
                # order (admissible() already removed them)
                self._requeue_blocked(admitted[idx:], 'pool_exhausted')
                break
            try:
                self._begin_request(slot, h)
            except PagePoolExhausted as exc:
                # paged admission could not reserve its pages even after
                # reclaiming retention: NOT a failure — free the slot
                # (returning whatever was attached) and send this handle
                # and everything behind it back to the queue front; the
                # pages free up as in-flight requests retire
                self.pool.free(slot)
                _obs.emit('page_pool_exhausted',
                          request_id=h.request_id,
                          queued=self.scheduler.queue_depth,
                          detail=str(exc))
                self._requeue_blocked(admitted[idx:], 'pool_exhausted')
                break
            except PoolLostError:
                # every seated request lost its KV: the step fails, as
                # after a failed donated decode round. What was popped
                # behind this handle goes back to the queue first, so
                # that evict_all() finds it.
                for back in reversed(admitted[idx + 1:]):
                    self.scheduler.requeue(back)
                raise
            except Exception as exc:
                from .adapters.bank import AdapterUnavailable
                if isinstance(exc, AdapterUnavailable) \
                        and exc.transient:
                    # adapter bank momentarily full of PINNED slots:
                    # pins free as in-flight requests retire, so this
                    # is back-pressure, not a failure — requeue just
                    # this handle and keep admitting the rest
                    self.pool.free(slot)
                    _obs.emit('adapter_bank_saturated',
                              request_id=h.request_id,
                              adapter_id=h.adapter_id,
                              detail=str(exc))
                    self._requeue_blocked([h], 'adapter_pinned')
                    continue
                # REQUEST-level failure: free the slot, fail the handle,
                # keep the engine serving everyone else
                if slot in self._slot_req:
                    self._detach_slot(slot, h)
                self.pool.free(slot)
                h._fail(exc)
                self._counts['failed'] += 1
                if _obs.enabled():
                    self._m_requests.labels(status='failed').inc()
                    _obs.emit('serving_request_failed',
                              request_id=h.request_id,
                              error=type(exc).__name__)
            else:
                seated += 1
        if _obs.enabled():
            self._m_active.set(len(self._slot_req))
        return seated

    def _seat_paged(self, slot: int, h: RequestHandle, s: int):
        """Page-table admission, BEFORE any handle/engine bookkeeping:
        attach the longest PAGE-ALIGNED cached prefix read-only, then
        reserve every page the request can touch (prompt + token budget
        + speculation headroom) all-or-nothing, reclaiming zero-ref
        retained holds under pressure. Raises PagePoolExhausted with the
        handle untouched — still QUEUED — so `_admit` can requeue it.
        Returns (node, cursor): cursor is the page-aligned prefix rows
        already seated (suffix prefill starts there, in fresh pages)."""
        ps = self.pool.page_size
        node, cursor = None, 0
        if self.prefix_cache is not None:
            t_pfx = time.perf_counter()
            node, matched = self.prefix_cache.lookup(
                h.prompt_tokens, namespace=self._prefix_ns(h))
            if node is not None:
                # whole pages only: the suffix [cursor, s) prefills
                # into FRESH exclusive pages, so a shared page is never
                # in any suffix/decode scatter window
                cursor = (min(matched, node.slot.kv_len) // ps) * ps
                if cursor < 1:
                    node = None
                else:
                    self.prefix_cache.acquire(node)
            if h._ledger_rec is not None:
                t1 = time.perf_counter()
                h._ledger_rec.add('prefix_lookup', t1 - t_pfx, now=t1)
        try:
            if node is not None:
                self.pool.attach_prefix(slot, node.slot, cursor // ps)
            headroom = (self.spec_k if self.draft_model is not None
                        else 0)
            self._reserve_pages(
                slot, min(s + h.params.max_new_tokens + headroom,
                          self.pool.max_length))
            if cursor >= s:
                # full-page hit: the pending-token re-forward at s-1
                # writes INTO the last shared page — COW-split it first
                while True:
                    try:
                        if self.pool.ensure_exclusive(slot, s - 1):
                            _obs.emit('paged_cow', slot=slot,
                                      request_id=h.request_id, pos=s - 1)
                        break
                    except PagePoolExhausted:
                        if self.prefix_cache is None or \
                                not self.prefix_cache.evict_lru():
                            raise
        except PagePoolExhausted:
            if node is not None:
                self.prefix_cache.release(node)
            raise
        return node, cursor

    def _reserve_pages(self, slot: int, total: int):
        """`PagedSlotPool.reserve` with pressure relief: zero-ref
        retained prefix holds yield their pages to live admissions,
        LRU-first, until the reservation fits or nothing is left."""
        while True:
            try:
                self.pool.reserve(slot, total)
                return
            except PagePoolExhausted:
                if self.prefix_cache is None or \
                        not self.prefix_cache.evict_lru():
                    raise

    def _begin_request(self, slot: int, h: RequestHandle):
        """Admission: claim the longest cached prefix (row mode: jitted
        row copy + suffix-only prefill; paged mode: read-only page
        attach + page reservation), then either whole-prompt prefill
        (short cold prompts — the PR-4 path, one compile per bucket) or
        enter the chunked-prefill state machine."""
        t_adm0 = time.perf_counter()
        rec = h._ledger_rec
        pfx0 = 0.0
        if rec is not None:
            rec.queue_exit(t_adm0)   # queue_wait ends; admission begins
            pfx0 = rec.phases['prefix_lookup']
        s = len(h.prompt_tokens)
        cursor = 0
        src = slot
        node = None
        if h.adapter_id is not None:
            # pin BEFORE the prefix lookup: the namespace key needs the
            # version this request will actually decode under (pin()
            # hot-swaps to the store's latest good version, so this is
            # also where a published v2 takes effect for new requests).
            # AdapterUnavailable propagates as a request-level failure.
            pin, version = self.adapter_bank.pin(h.adapter_id)
            h._adapter_pin = pin
            h.adapter_version = version
            self._adapter_rows[slot] = pin
        else:
            self._adapter_rows[slot] = 0
        if self._paged:
            # seating raises PagePoolExhausted BEFORE any bookkeeping:
            # the handle stays queueable for the requeue path (the
            # adapter pin must roll back with it)
            try:
                node, cursor = self._seat_paged(slot, h, s)
            except PagePoolExhausted:
                self._unpin_adapter(slot, h)
                raise
            if node is not None:
                h._prefix_node = node
                h._prefix_len = cursor
        elif self.prefix_cache is not None:
            t_pfx = time.perf_counter()
            node, matched = self.prefix_cache.lookup(
                h.prompt_tokens, namespace=self._prefix_ns(h))
            if node is not None:
                self.prefix_cache.acquire(node)
                h._prefix_node = node
                h._prefix_len = matched
                cursor = matched
                src = node.slot
            if rec is not None:
                t1 = time.perf_counter()
                rec.add('prefix_lookup', t1 - t_pfx, now=t1)
        # submit to admission: the request's trace id (request_id)
        # threads every span and event it touches
        _obs.record_span('serving.queue', h._t_submit,
                         request_id=h.request_id)
        self._slot_req[slot] = h
        h.status = RUNNING
        # the no-mixed-version guarantee: stamped ONCE, here — a hot
        # swap requires a drained engine, so every token this request
        # emits decodes under this version
        h.weight_version = self.weight_version
        if rec is not None:
            # admission = seating work since queue exit, minus the
            # prefix-lookup seconds already booked inside this window
            # (phases stay non-overlapping in seconds)
            t1 = time.perf_counter()
            rec.add('admission', (t1 - t_adm0)
                    - (rec.phases['prefix_lookup'] - pfx0), now=t1)
        if node is not None:
            _obs.emit('prefix_hit', request_id=h.request_id,
                      matched=h._prefix_len, prompt_len=s, slot=slot)
        if cursor >= s:
            # full-prompt hit: ZERO prefill — row mode copies the
            # retained row; paged mode already shares the pages — then
            # the pending token re-forwards the last prompt position
            if not self._paged:
                self._pool_op(self.pool.copy_slot, src, slot)
            self.pool.note_written(slot, s)
            self._activate(slot, h)
            return
        chunk = self.prefill_chunk_tokens
        if cursor == 0 and (chunk is None or s <= chunk):
            self._whole_prefill(slot, h)
            self._activate(slot, h)
            return
        # suffix and/or long prompt: per-slot cursor, one bucket-shaped
        # chunk per scheduler iteration (the first lands this step via
        # _advance_prefills, gathering its KV floor from `src` — the
        # retained row on a prefix hit); the slot stays inactive for
        # decode — its position parks at the last row, where stray
        # inactive-row KV writes land above every live position
        self._pos[slot] = self.pool.max_length - 1
        self._tok[slot] = 0
        self._active[slot] = False
        self._prefilling[slot] = [h, cursor, src]
        self._counts['chunked_prefills'] += 1

    def _note_prefill(self, h: RequestHandle, t0: float):
        """Ledger: the prefill that just ran books as `prefill` for its
        owner and `prefill_wait` for every OTHER seated request — the
        chunked-prefill convoy, named instead of smeared."""
        now = time.perf_counter()
        _reqledger.get_ledger().note_prefill(
            now - t0, h._ledger_rec,
            [o._ledger_rec for o in self._slot_req.values()], now=now)

    def _whole_prefill(self, slot: int, h: RequestHandle):
        s = len(h.prompt_tokens)
        bucket = self.pool.bucket_for(s)
        t_pf0 = time.perf_counter()
        with _obs.span('serving.prefill', request_id=h.request_id,
                       bucket=bucket, slot=slot, prompt_len=s) as span:
            if self._own_tokens_pairs is not None:
                span.set(attn_pairs_scored=self._own_tokens_pairs(bucket),
                         attn_pairs_causal=s * (s + 1) // 2)
            if self._scan_chunks is not None:
                span.set(**self._scan_chunks(bucket))
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :s] = h.prompt_tokens
            ids_dev = call_with_retry(_to_device, ids, policy=self._retry,
                                      site='serving.h2d')
            if self._paged:
                pages, scales = self.pool.device_state()
                table = call_with_retry(
                    _to_device, self.pool.page_table[slot:slot + 1],
                    policy=self._retry, site='serving.h2d')
                new_pages, new_scales = self._prefill_jit(
                    self._params, self._frozen, self._buffers,
                    pages, scales, table, ids_dev,
                    *self._adapter_args(slot))
                self.pool.set_device_state(new_pages, new_scales)
            else:
                self._prefill_row(
                    self.pool, slot, self._prefill_jit,
                    self._params, self._frozen, self._buffers, ids_dev,
                    *((np.int32(s),) if self.pool.stands_at_one_position
                      else ()),
                    *self._adapter_args(slot))
        self.pool.note_written(slot, s)
        self._note_prefill(h, t_pf0)
        self._counts['prefills'] += 1
        self._counts['prefill_tokens'] += s
        if _obs.enabled():
            self._m_prefills.labels(bucket=bucket).inc()
            self._m_prefill_tokens.inc(s)

    def _advance_prefills(self):
        """Drive every mid-prefill slot forward one bucket-shaped chunk
        (FCFS by admission). A slot whose cursor reaches the prompt end
        activates for decode in the same round."""
        for slot in list(self._prefilling):
            h, cursor, src = self._prefilling[slot]
            try:
                self._prefill_chunk(slot, h, cursor, src)
            except PoolLostError:
                raise               # the step's failure, not this request's
            except Exception as exc:
                self._detach_slot(slot, h)
                self.pool.free(slot)
                h._fail(exc)
                self._counts['failed'] += 1
                if _obs.enabled():
                    self._m_requests.labels(status='failed').inc()
                    _obs.emit('serving_request_failed',
                              request_id=h.request_id,
                              error=type(exc).__name__)

    def _prefill_chunk(self, slot: int, h: RequestHandle, cursor: int,
                       src: int):
        s = len(h.prompt_tokens)
        c = min(self.prefill_chunk_tokens or s, s - cursor)
        bucket = self.pool.bucket_for(c)
        # tail chunks whose bucket would overrun the slot shift their
        # window start down and RE-forward already-prefilled tokens —
        # an identical KV overwrite (the pending-token trick), so the
        # window always fits and pad queries stay above the prompt
        start = min(cursor, self.pool.max_length - bucket)
        window = h.prompt_tokens[start:start + bucket]
        t_pf0 = time.perf_counter()
        with _obs.span('serving.prefill_chunk', request_id=h.request_id,
                       bucket=bucket, slot=slot, start=start,
                       cursor=cursor, prompt_len=s):
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :len(window)] = window
            ids_dev = call_with_retry(_to_device, ids, policy=self._retry,
                                      site='serving.h2d')
            if self._paged:
                # the slot's own table carries any attached prefix
                # pages, so there is no src row: the chunk gathers,
                # attends, and scatters through the table. The floor
                # (the page-aligned attach boundary) keeps tail-shifted
                # duplicate writes out of the shared pages.
                pages, scales = self.pool.device_state()
                table = call_with_retry(
                    _to_device, self.pool.page_table[slot:slot + 1],
                    policy=self._retry, site='serving.h2d')
                new_pages, new_scales = self._chunk_prefill_jit(
                    self._params, self._frozen, self._buffers,
                    pages, scales, table, ids_dev, jnp.int32(start),
                    jnp.int32(h._prefix_len), *self._adapter_args(slot))
                self.pool.set_device_state(new_pages, new_scales)
            else:
                # forwards against a copy of the src ROW (the retained
                # row on a prefix hit's first chunk, the slot's own row
                # after) and returns the slot's new row — one-row
                # surface either way
                self._prefill_row(
                    self.pool, slot, self._chunk_prefill_jit,
                    self._params, self._frozen, self._buffers,
                    self.pool.row(src), ids_dev, jnp.int32(start),
                    *self._adapter_args(slot))
        new_cursor = min(start + bucket, s)
        self.pool.note_written(slot, new_cursor)
        self._note_prefill(h, t_pf0)
        self._prefilling[slot][1] = new_cursor
        self._prefilling[slot][2] = slot   # later chunks extend own row
        self._counts['chunk_rounds'] += 1
        self._counts['prefill_tokens'] += new_cursor - cursor
        if _obs.enabled():
            self._m_chunk_rounds.inc()
            self._m_chunk_tokens.inc(new_cursor - cursor)
            self._m_prefill_tokens.inc(new_cursor - cursor)
        if new_cursor >= s:
            del self._prefilling[slot]
            self._activate(slot, h)

    def _activate(self, slot: int, h: RequestHandle):
        """Prompt KV complete (prefilled, copied, or both): arm the slot
        for decode. The pending token is the LAST prompt token at
        position s-1 — the next decode round re-forwards it (identical
        KV overwrite) and its sampled output is the request's first
        generated token. That token is the HOST's (`carried` cleared):
        whatever block the slot's last request left on the device is
        not this one's."""
        p = h.params
        s = len(h.prompt_tokens)
        if self.draft_model is not None:
            self._draft_prefill(slot, h)
        greedy = p.strategy == GREEDY
        key = (np.zeros(2, np.uint32) if greedy else np.asarray(  # paddle-lint: disable=host-sync -- once per admission, not per round: seeds the per-slot sampling key row
            jax.random.PRNGKey(h.request_id if p.seed is None
                               else p.seed), np.uint32))
        self._tok[slot] = h.prompt_tokens[-1]
        self._carried[slot] = False
        self._pos[slot] = s - 1
        self._steps[slot] = 0
        self._budget[slot] = p.max_new_tokens
        self._active[slot] = True
        self._temp[slot] = p.temperature
        self._topk[slot] = p.top_k
        self._topp[slot] = p.top_p
        self._greedy[slot] = greedy
        self._keys[slot] = key
        self._eos_arr[slot] = h._eos

    def _draft_prefill(self, slot: int, h: RequestHandle):
        """Whole-bucket prompt prefill into the DRAFT pool row (the
        draft proposes from its own KV). Runs once at activation —
        deliberately un-chunked and un-cached: the draft is small, and
        keeping its path trivial keeps the compiled set bounded."""
        s = len(h.prompt_tokens)
        bucket = self.pool.bucket_for(s)
        d_params, d_frozen, d_buffers = self._draft_state
        t_pf0 = time.perf_counter()
        with _obs.span('serving.draft_prefill', request_id=h.request_id,
                       bucket=bucket, slot=slot):
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :s] = h.prompt_tokens
            ids_dev = call_with_retry(_to_device, ids, policy=self._retry,
                                      site='serving.h2d')
            self._prefill_row(self.draft_pool, slot,
                              self._draft_prefill_jit, d_params, d_frozen,
                              d_buffers, ids_dev)
        self._note_prefill(h, t_pf0)

    def _retire(self, slot: int, h: RequestHandle, now: float):
        h._finish(now)
        self._detach_slot(slot, h)
        retained = False
        if self.prefix_cache is not None:
            # retention costs nothing: the slot's rows [0, prompt_len)
            # ARE the prompt's prefill KV (generated-token KV above is
            # stale-by-construction for the next user). Adapter prefill
            # KV carries the adapter's deltas — it retains under the
            # (adapter_id, version) namespace, never the base trie.
            retained = self.prefix_cache.insert(
                h.prompt_tokens, slot, namespace=self._prefix_ns(h))
        if not retained:
            self.pool.free(slot)
        self._counts['completed'] += 1
        if _obs.enabled():
            self._m_requests.labels(status='completed').inc()
            self._m_active.set(len(self._slot_req))
            tpot = h.tpot
            if tpot is not None:
                self._m_tpot.observe(tpot)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Host-side counters + compile-trace counts (the zero-recompile
        assertions read `traces`: after warmup it must stop growing
        across admissions). A round in flight is settled first, so the
        counters are those of a quiet engine."""
        self._settle()
        traces = collections.Counter(self._trace_counts)
        for pool in (self.pool, self.draft_pool):
            if isinstance(pool, SlotPool):   # its seat/copy/slice programs
                traces.update(pool.traces)
        out = {
            'submitted': self._counts['submitted'],
            'completed': self._counts['completed'],
            'failed': self._counts['failed'],
            'tokens': self._counts['tokens'],
            'prefills': self._counts['prefills'],
            'prefill_tokens': self._counts['prefill_tokens'],
            'decode_rounds': self._counts['decode_rounds'],
            'decode_steps': self._counts['decode_steps'],
            'rounds_ahead': self._counts['rounds_ahead'],
            'blocks_discarded': self._counts['blocks_discarded'],
            'chunked_prefills': self._counts['chunked_prefills'],
            'chunk_rounds': self._counts['chunk_rounds'],
            'queue_depth': self.scheduler.queue_depth,
            'active_slots': len(self._slot_req),
            'weight_version': self.weight_version,
            'kv_layout': 'paged' if self._paged else 'row',
            'traces': dict(traces),
            'pool': self.pool.stats(),
        }
        if self.prefix_cache is not None:
            out['prefix_cache'] = self.prefix_cache.stats()
        if self.adapter_bank is not None:
            out['adapters'] = self.adapter_bank.stats()
        if self.draft_model is not None:
            proposed = self._counts['spec_proposed']
            out['spec'] = {
                'k': self.spec_k,
                'rounds': self._counts['spec_rounds'],
                'proposed': proposed,
                'accepted': self._counts['spec_accepted'],
                'acceptance_rate': (self._counts['spec_accepted']
                                    / proposed if proposed else 0.0),
            }
        return out

    def reset_stats(self):
        """Zero the host-side counters (trace counts survive — they
        track compiles, which persist in the jit caches)."""
        self._counts.clear()
